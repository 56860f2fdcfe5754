"""TPC-H Q3 (spec 2.4.3, Shipping Priority) in numpy and Python
integers over the candidate rows ``generators/tpch_q3_tables.py`` kept:
the join of ``customer``, ``orders`` and ``lineitem``, the exact sum of
``l_extendedprice * (1 - l_discount)`` per order, the ORDER BY (revenue
descending, then ``o_orderdate``) and the first ten rows.  No engine
code.

The harness compares an ordered answer row for row, and the spec's
ORDER BY does not order two rows that tie on both keys: ``expected``
RAISES where two of the first eleven rows do, rather than pick one.
"""

import datetime

import numpy as np

from .common import date_of, days, dec

SEGMENTS = ("AUTOMOBILE", "BUILDING", "FURNITURE", "HOUSEHOLD", "MACHINERY")
FIRST = 10


def expected(stats, params):
    date = days(datetime.date(1995, 3, int(params["DATE"])))
    wanted = SEGMENTS.index(params["SEGMENT"])
    order_ok = (stats["q3_o_segment"] == wanted) \
        & (stats["q3_o_orderdate"] < date)
    keep = order_ok[stats["q3_l_order"]] & (stats["q3_l_shipdate"] > date)
    revenue = np.zeros(stats["q3_o_orderkey"].size, np.int64)
    np.add.at(revenue, stats["q3_l_order"][keep],
              stats["q3_l_revenue"][keep])
    groups = np.unique(stats["q3_l_order"][keep])
    # revenue descending, then the order date; the key only to make the
    # sort total (a tie on both is refused below)
    by = np.lexsort((stats["q3_o_orderkey"][groups],
                     stats["q3_o_orderdate"][groups], -revenue[groups]))
    top = groups[by[:FIRST + 1]]
    keys = [(int(revenue[i]), int(stats["q3_o_orderdate"][i])) for i in top]
    if len(set(keys)) < len(keys):
        raise ValueError(
            f"Q3 {params}: two of the first {FIRST + 1} rows tie on "
            f"(revenue, o_orderdate): the ORDER BY does not order them")
    return [(int(stats["q3_o_orderkey"][i]), dec(revenue[i], 4),
             date_of(stats["q3_o_orderdate"][i]), 0) for i in top[:FIRST]]
