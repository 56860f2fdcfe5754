"""TPC-H Q15's view ``revenue`` (spec 2.4.15) for any DATE that is the
first day of a month: ``l_suppkey`` and ``sum(l_extendedprice * (1 -
l_discount))`` of every supplier with a lineitem shipped in the three
months from DATE, from the statistics kept at generation
(``q15_revenue[supplier, month]`` scaled by 10**4, ``q15_rows[supplier,
month]``; month 0 is January 1992).  numpy and Python integers."""

import datetime

import numpy as np

from .common import SHIP_LO, dec

#: January 1992, month 0 of the statistics' month axis
FIRST_MONTH = np.datetime64("1992-01", "M")


def month_of_ship_day(day):
    """Month index of ship day(s) counted from ``SHIP_LO``."""
    date = np.datetime64("1970-01-01", "D") + (np.asarray(day) + SHIP_LO)
    return (date.astype("datetime64[M]") - FIRST_MONTH).astype(np.int64)


def _first_month(params) -> int:
    date = datetime.date.fromisoformat(params["DATE"])
    if date.day != 1:
        raise ValueError(f"Q15 reference holds whole months; DATE {date} "
                         "is not the first day of one")
    return (date.year - 1992) * 12 + date.month - 1


def expected(stats, params):
    lo = _first_month(params)
    months = stats["q15_rows"].shape[1]
    if lo < 0 or lo + 3 > months:
        raise ValueError(f"Q15 reference holds months 0..{months - 1}; "
                         f"DATE {params['DATE']} asks for {lo}..{lo + 2}")
    rows = stats["q15_rows"][:, lo:lo + 3].sum(axis=1, dtype=np.int64)
    revenue = stats["q15_revenue"][:, lo:lo + 3]
    return [(int(s), dec(sum(int(x) for x in revenue[s]), 4))
            for s in np.nonzero(rows)[0]]
