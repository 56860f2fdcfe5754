"""GROUP BY l_shipdate, l_discount, l_tax (count, sum qty), from
``by_day_disc_tax``."""

import numpy as np

from .common import N_DISC, N_TAX, date_of, dec, SHIP_LO


def expected(stats, params):
    t = stats["by_day_disc_tax"]
    rows = []
    for g in np.nonzero(t[:, 0])[0]:
        rest, tax = divmod(int(g), N_TAX)
        day, disc = divmod(rest, N_DISC)
        rows.append((date_of(SHIP_LO + day), dec(disc, 2), dec(tax, 2),
                     int(t[g, 0]), dec(t[g, 1], 2)))
    return rows
