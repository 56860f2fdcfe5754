"""TPC-H Q10 (spec 2.4.10, Returned Item Reporting) in numpy and Python
integers over the candidate orders ``generators/tpch_q10_tables.py``
kept and that generator's customer and nation columns: the join of
``customer``, ``orders``, ``lineitem`` and ``nation``, the exact sum of
``l_extendedprice * (1 - l_discount)`` over the ``R`` lines of a
quarter's orders per customer, the ORDER BY (revenue descending) and
the first twenty rows, each with the customer's name, balance, nation,
address, phone and comment.  No engine code.

The harness compares an ordered answer row for row, and the spec's
ORDER BY does not order two rows that tie on revenue: ``expected``
RAISES where two of the first twenty-one rows do, rather than pick one.
"""

import datetime

import numpy as np

from ..generators import tpch_q10_tables as tables
from ..generators.tpch_q3_tables import _customer
from .common import dec

FIRST = 20
QUARTER = 3


def groups(stats, params):
    """-> (custkeys, revenue) of every group of the draw, revenue
    scaled by 10**4, in no order."""
    date = datetime.date.fromisoformat(params["DATE"])
    first = (date.year - tables.FIRST_MONTH[0]) * 12 \
        + date.month - tables.FIRST_MONTH[1]
    if date.day != 1 or not 0 <= first <= tables.N_MONTHS - QUARTER:
        raise ValueError(f"Q10 DATE {params['DATE']}: not the first of a "
                         f"month whose quarter the statistics hold")
    month = stats["q10_o_month"]
    keep = (month >= first) & (month < first + QUARTER)
    revenue = np.zeros(int(stats["q10_customers"]) + 1, np.int64)
    np.add.at(revenue, stats["q10_o_custkey"][keep],
              stats["q10_o_revenue"][keep])
    custkeys = np.unique(stats["q10_o_custkey"][keep])
    return custkeys, revenue[custkeys]


def expected(stats, params):
    custkeys, revenue = groups(stats, params)
    # revenue descending; the key only to make the sort total (a tie on
    # revenue is refused below)
    by = np.lexsort((custkeys, -revenue))[:FIRST + 1]
    if len(set(revenue[by].tolist())) < by.size:
        raise ValueError(
            f"Q10 {params}: two of the first {FIRST + 1} rows tie on "
            f"revenue: the ORDER BY does not order them")
    by = by[:FIRST]
    seed = int(stats["q10_data_seed"])
    c = _customer(seed, int(stats["q10_customers"]))
    keys = custkeys[by]
    nations = c["c_nationkey"][keys - 1]
    # the generator makes the words of ascending keys
    up = np.argsort(keys)
    text = tables.customer_text(seed, keys[up], nations[up])
    place = {int(k): i for i, k in enumerate(keys[up])}
    rows = []
    for key, rev, nation in zip(keys.tolist(), revenue[by].tolist(),
                                nations.tolist()):
        i = place[key]
        rows.append((key, text["c_name"][i], dec(rev, 4),
                     dec(c["c_acctbal"][key - 1], 2),
                     tables.NATIONS[nation][0], text["c_address"][i],
                     text["c_phone"][i], text["c_comment"][i]))
    return rows
