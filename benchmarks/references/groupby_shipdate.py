"""GROUP BY l_shipdate (count, sum qty, max price), from ``by_day``."""

import numpy as np

from .common import date_of, dec, SHIP_LO


def expected(stats, params):
    by_day = stats["by_day"]
    return [(date_of(SHIP_LO + d), int(by_day[d, 0]), dec(by_day[d, 1], 2),
             dec(by_day[d, 2], 2)) for d in np.nonzero(by_day[:, 0])[0]]
