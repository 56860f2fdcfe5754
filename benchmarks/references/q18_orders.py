"""The block that defines TPC-H Q18 (spec 2.4.18): ``l_orderkey`` and
``sum(l_quantity)`` of every order whose sum exceeds QUANTITY, from the
large orders kept at generation (``q18_okey`` / ``q18_qty``: every order
whose sum reaches ``KEEP_FROM``).  numpy and Python integers."""

import numpy as np

from .common import dec

#: least quantity sum, scaled by 100, of the orders the generator keeps:
#: 250.00, under Q18's validation value (300) and its range (312..315)
KEEP_FROM = 25000


def expected(stats, params):
    limit = int(params["QUANTITY"]) * 100
    if limit < int(stats["q18_keep_from"]):
        raise ValueError(
            f"Q18 reference holds orders from {int(stats['q18_keep_from'])} "
            f"(scaled by 100); QUANTITY {params['QUANTITY']} asks for less")
    keep = np.nonzero(stats["q18_qty"] > limit)[0]
    return [(int(stats["q18_okey"][i]), dec(stats["q18_qty"][i], 2))
            for i in keep]
