"""The hourly rollup over taxi trips: one row per hour that holds a
trip -- the hour, its trips, and the decimal averages of fare_amount and
total_amount -- from the statistics ``hourly[hour, k]`` kept at
generation (k = trips, sum fare, sum total, in cents).  numpy and Python
integers."""

import datetime

import numpy as np

from .common import avg_dec

EPOCH = datetime.datetime(1970, 1, 1)


def expected(stats, params):
    first = EPOCH + datetime.timedelta(
        microseconds=int(stats["hour_first_us"]))
    rows = []
    for h in np.nonzero(stats["hourly"][:, 0])[0]:
        n, fare, total = (int(x) for x in stats["hourly"][h])
        rows.append((first + datetime.timedelta(hours=int(h)), n,
                     avg_dec(fare, n, 2), avg_dec(total, n, 2)))
    return rows
