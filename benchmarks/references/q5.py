"""TPC-H Q5 (spec 2.4.5, Local Supplier Volume) in numpy and Python
integers over the sums ``generators/tpch_q5_tables.py`` kept: the join
of ``customer``, ``orders``, ``lineitem``, ``supplier``, ``nation`` and
``region`` -- a line counts where its order is dated in DATE's year,
its supplier's nation lies in REGION and its order's customer is of the
supplier's nation --, the exact sum of ``l_extendedprice * (1 -
l_discount)`` per nation of the region, and the ORDER BY (revenue
descending).  No engine code.

The harness compares an ordered answer row for row, and the spec's
ORDER BY does not order two nations that tie on revenue: ``expected``
RAISES where two do, rather than pick one.
"""

import datetime

from ..generators.tpch_q10_tables import NATIONS
from .common import dec

REGIONS = ("AFRICA", "AMERICA", "ASIA", "EUROPE", "MIDDLE EAST")
FIRST_YEAR, N_YEARS = 1993, 5


def expected(stats, params):
    date = datetime.date.fromisoformat(params["DATE"])
    year = date.year - FIRST_YEAR
    if (date.month, date.day) != (1, 1) or not 0 <= year < N_YEARS:
        raise ValueError(f"Q5 DATE {params['DATE']}: not the first of "
                         f"January of a year the statistics hold")
    region = REGIONS.index(params["REGION"])
    rows = [(name, int(stats["q5_revenue"][n, year]))
            for n, (name, r) in enumerate(NATIONS)
            if r == region and int(stats["q5_rows"][n, year])]
    rows.sort(key=lambda r: -r[1])
    if len({v for _, v in rows}) < len(rows):
        raise ValueError(f"Q5 {params}: two nations tie on revenue: the "
                         f"ORDER BY does not order them")
    return [(name, dec(v, 4)) for name, v in rows]
