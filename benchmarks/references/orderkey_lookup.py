"""Routed projection ``WHERE l_orderkey = $1``: the rows of one order
of the sample kept at generation (``lookup_*``)."""

import numpy as np

from .common import RETURNFLAGS, date_of, dec


def expected(stats, params):
    hit = np.nonzero(stats["lookup_okey"] == int(params["KEY"]))[0]
    return [(int(stats["lookup_okey"][i]), dec(stats["lookup_qty"][i], 2),
             dec(stats["lookup_price"][i], 2), date_of(stats["lookup_ship"][i]),
             RETURNFLAGS[int(stats["lookup_rf"][i])]) for i in hit]
