"""TPC-H Q12 (spec 2.4.12, Shipping Modes and Order Priority) in numpy
and Python integers: the join of ``orders`` and ``lineitem``, the lines
of two ship modes with ``l_shipdate < l_commitdate < l_receiptdate``
received in one year, counted per mode by the priority class of their
order, in ``l_shipmode`` order.  No engine code.

``expected`` answers from the 7 x 5 x 2 counts
``generators/tpch_q12_tables.py`` kept; ``joined`` computes the same
answer from the generated columns by a join written here -- the
reference of the reference, for the tests and at a rehearsal's size.
"""

import datetime

import numpy as np

from .common import days

SHIPMODES = ("REG AIR", "AIR", "RAIL", "SHIP", "TRUCK", "MAIL", "FOB")
HIGH = ("1-URGENT", "2-HIGH")
YEARS = (1993, 1994, 1995, 1996, 1997)


def modes_of(params) -> list:
    """SHIPMODES is one parameter: the two modes as qgen writes them
    between the IN list's outer quotes, ``MAIL', 'SHIP``."""
    modes = params["SHIPMODES"].split("', '")
    if len(modes) != 2 or modes[0] == modes[1] \
            or not set(modes) <= set(SHIPMODES):
        raise ValueError(f"Q12 {params}: SHIPMODES names two distinct modes")
    return modes


def expected(stats, params):
    year = YEARS.index(int(params["DATE"]))
    counts = stats["q12"]
    return [(m, int(counts[SHIPMODES.index(m), year, 0]),
             int(counts[SHIPMODES.index(m), year, 1]))
            for m in sorted(modes_of(params))
            if counts[SHIPMODES.index(m), year].sum()]


def joined(orders, lineitem, params):
    """The same rows from the generated columns (``generate_chunk``'s
    ``orders`` and ``lineitem``, concatenated over the chunks): every
    line looks its order up by key."""
    lo = days(datetime.date(int(params["DATE"]), 1, 1))
    hi = days(datetime.date(int(params["DATE"]) + 1, 1, 1))
    priority = {int(k): int(p) for k, p in zip(orders["o_orderkey"],
                                               orders["o_orderpriority"])}
    out = {}
    wanted = [SHIPMODES.index(m) for m in modes_of(params)]
    for key, mode, ship, commit, receipt in zip(
            lineitem["okey"].tolist(), lineitem["mode"].tolist(),
            lineitem["ship"].tolist(), lineitem["commit"].tolist(),
            lineitem["receipt"].tolist()):
        if mode in wanted and commit < receipt and ship < commit \
                and lo <= receipt < hi and key in priority:
            high, low = out.get(mode, (0, 0))
            is_high = priority[key] < len(HIGH)
            out[mode] = (high + is_high, low + (not is_high))
    return sorted((SHIPMODES[m], int(h), int(l)) for m, (h, l) in out.items())
