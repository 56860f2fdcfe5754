"""TPC-H Q1 for any DELTA, from the statistics table
``q1[flag, status, ship day, k]``."""

import datetime

from .common import (LINESTATUSES, RETURNFLAGS, avg_dec, days, dec,
                     exact_sum, SHIP_LO)


def expected(stats, params):
    cutoff = days(datetime.date(1998, 12, 1)) - int(params["DELTA"])
    n_days = max(min(cutoff - SHIP_LO + 1, stats["q1"].shape[2]), 0)
    rows = []
    for f, flag in enumerate(RETURNFLAGS):
        for s, status in enumerate(LINESTATUSES):
            qty, price, disc_price, charge, disc, n = (
                exact_sum(stats["q1"][f, s, :n_days, k]) for k in range(6))
            if n:
                rows.append((flag, status, dec(qty, 2), dec(price, 2),
                             dec(disc_price, 4), dec(charge, 6),
                             avg_dec(qty, n, 2), avg_dec(price, n, 2),
                             avg_dec(disc, n, 2), n))
    return rows
