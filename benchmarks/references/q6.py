"""TPC-H Q6 for any (YEAR, DISCOUNT, QUANTITY), from the statistics
table ``q6[ship day, discount, quantity class, k]`` (classes: < 24,
== 24, >= 25, so QUANTITY may be 24 or 25)."""

import datetime

from .common import days, dec, exact_sum, SHIP_LO


def _window(stats, params):
    year, disc, qty = (int(params[k]) for k in ("YEAR", "DISCOUNT", "QUANTITY"))
    if qty not in (24, 25):
        raise ValueError(f"Q6 reference holds QUANTITY 24 and 25, not {qty}")
    n_days = stats["q6"].shape[0]
    lo = min(max(days(datetime.date(year, 1, 1)) - SHIP_LO, 0), n_days)
    hi = min(max(days(datetime.date(year + 1, 1, 1)) - SHIP_LO, 0), n_days)
    d_lo, d_hi = max(disc - 1, 0), min(disc + 1, stats["q6"].shape[1] - 1)
    return stats["q6"][lo:hi, d_lo:d_hi + 1, :qty - 23]


def matching_rows(stats, params) -> int:
    return exact_sum(_window(stats, params)[..., 1])


def expected(stats, params):
    if not matching_rows(stats, params):
        return [(None,)]
    return [(dec(exact_sum(_window(stats, params)[..., 0]), 4),)]
