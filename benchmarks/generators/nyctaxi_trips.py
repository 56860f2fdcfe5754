"""NYC yellow-taxi ``trips`` in the shape of the public trip records,
from a seed, in numpy.

Written from what is known of the TLC trip records and of the
``nyc-taxi-data`` PostgreSQL loading (there is no network here), so every
constant below is also listed under ``assumed`` in the configuration
file, and the hour-of-week intensity profile stands in that file itself.

A trip is an "order" of the harness: ``orders`` trips over ``days`` days
from ``start_date``.  Pickups follow the hour-of-week profile (the
inverse of its cumulative intensity, stratified so that chunk ``i``
holds the trips of one contiguous stretch of time); within a chunk the
rows are emitted in DROP-OFF order, as a meter reports them, so arrival
is nearly but not exactly pickup order.  A chunk is seeded by
``[data_seed, chunk_index]``.  While the table is made, the per-hour
sufficient statistics of the hourly rollup are accumulated: trip count
and the exact integer sums of ``fare_amount`` and ``total_amount``.
No engine code is used here.
"""

import datetime

import numpy as np

# the exact integer bincount (float64 partial sums checked below 2**53)
from .tpch_lineitem import _bincount

#: part of the persisted data set's key: bump on any change to the draws
#: or to the statistics kept beside the table
GENERATOR_VERSION = 1

EPOCH = datetime.datetime(1970, 1, 1)
HOUR_US = 3_600_000_000
PAYMENT_TYPES = np.array(["CRD", "CSH", "NOC", "DIS", "UNK"])
PAYMENT_SHARES = (0.55, 0.43, 0.01, 0.005, 0.005)
PASSENGER_SHARES = (0.70, 0.14, 0.05, 0.02, 0.06, 0.03)     # 1..6
#: card tips as a share of the fare, in percent, and how often each
TIP_PERCENTS = np.array([0, 15, 18, 20, 22, 25, 30])
TIP_SHARES = (0.06, 0.10, 0.20, 0.34, 0.15, 0.10, 0.05)


def first_hour_us(params) -> int:
    start = datetime.datetime.fromisoformat(params["start_date"])
    return int((start - EPOCH).total_seconds()) * 1_000_000


def n_hours(params) -> int:
    return int(params["days"]) * 24


def n_chunks(params) -> int:
    return -(-params["orders"] // params["chunk_orders"])


def hour_weights(params) -> np.ndarray:
    """Intensity of every hour of the span, from the 168 hour-of-week
    weights (index 0 is Monday 00:00)."""
    profile = np.asarray(params["hour_of_week_weights"], float)
    if profile.shape != (168,) or profile.min() <= 0:
        raise ValueError("hour_of_week_weights: 168 positive weights")
    start = datetime.datetime.fromisoformat(params["start_date"])
    first = start.weekday() * 24 + start.hour
    return profile[(first + np.arange(n_hours(params))) % 168]


def generate_chunk(params, data_seed: int, chunk_index: int) -> dict:
    """Trips ``[chunk_index * chunk_orders, ...)`` in drop-off order, as
    integer columns (money in cents, distance in hundredths of a mile,
    times in microseconds since 1970)."""
    lo = chunk_index * params["chunk_orders"]
    n = min(params["chunk_orders"], params["orders"] - lo)
    rng = np.random.default_rng([data_seed, chunk_index])
    # pickup: trip k of the table lies at quantile (k + u) / orders of the
    # span's cumulative intensity, uniform inside its hour
    cdf = np.concatenate([[0.0], np.cumsum(hour_weights(params))])
    q = (lo + np.arange(n) + rng.random(n)) / params["orders"] * cdf[-1]
    hours = np.interp(q, cdf, np.arange(cdf.size))
    pickup_s = np.minimum((hours * 3600.0).astype(np.int64),
                          n_hours(params) * 3600 - 1)
    duration_s = np.clip(np.exp(rng.normal(6.5, 0.6, n)), 60, 10_800
                         ).astype(np.int64)
    mph = np.clip(rng.normal(11.5, 4.0, n), 3.0, 45.0)
    distance = np.maximum((duration_s * mph / 36.0).astype(np.int64), 1)
    # the meter: 2.50 at the drop of the flag, 0.50 for every fifth of a
    # mile, 0.50 for every minute of the stretch driven slowly
    slow_percent = rng.integers(20, 51, n)
    fare = 250 + 50 * (distance // 20 + duration_s * slow_percent // 6000)
    hour_of_day = (pickup_s // 3600) % 24
    day = (pickup_s // 86400 + datetime.datetime.fromisoformat(
        params["start_date"]).weekday()) % 7
    surcharge = (50 + 50 * ((hour_of_day >= 20) | (hour_of_day < 6))
                 + 100 * ((hour_of_day >= 16) & (hour_of_day < 20) & (day < 5)))
    tolls = 533 * (rng.random(n) < 0.05)
    payment = rng.choice(len(PAYMENT_TYPES), n, p=PAYMENT_SHARES)
    tip_percent = TIP_PERCENTS[rng.choice(len(TIP_PERCENTS), n, p=TIP_SHARES)]
    tip = np.where(payment == 0, fare * tip_percent // 100, 0)
    passengers = 1 + rng.choice(6, n, p=PASSENGER_SHARES)
    vendor = 1 + (rng.random(n) < 0.52)
    t0 = first_hour_us(params)
    pickup = t0 + pickup_s * 1_000_000
    dropoff = pickup + duration_s * 1_000_000
    order = np.argsort(dropoff, kind="stable")
    columns = {"vendor": vendor.astype(np.int32), "pickup": pickup,
               "dropoff": dropoff, "passengers": passengers.astype(np.int32),
               "distance": distance, "fare": fare, "tip": tip,
               "total": fare + surcharge + tolls + tip, "payment": payment}
    out = {k: v[order] for k, v in columns.items()}
    out["trip_id"] = lo + np.arange(n, dtype=np.int64)
    return out


def copy_columns(chunk: dict) -> dict:
    """The chunk as ``Cluster.copy_from`` takes it."""
    return {
        "trip_id": chunk["trip_id"],
        "vendor_id": chunk["vendor"],
        "pickup_datetime": chunk["pickup"],
        "dropoff_datetime": chunk["dropoff"],
        "passenger_count": chunk["passengers"],
        "trip_distance": chunk["distance"] / 100.0,
        "fare_amount": chunk["fare"] / 100.0,
        "tip_amount": chunk["tip"] / 100.0,
        "total_amount": chunk["total"] / 100.0,
        "payment_type": PAYMENT_TYPES[chunk["payment"]].tolist(),
    }


class Statistics:
    """Sufficient statistics of the hourly rollup, chunk by chunk:
    ``hour_first_us`` (the span's first hour, microseconds since 1970),
    ``hourly[hour, k]``: k = trips, sum fare_amount, sum total_amount
    (both in cents), by the hour of the PICKUP; ``rows``."""

    def __init__(self, params):
        self.first = first_hour_us(params)
        self.rows = np.zeros((), np.int64)
        self.hourly = np.zeros((n_hours(params), 3), np.int64)

    def add(self, c: dict) -> None:
        hour = (c["pickup"] - self.first) // HOUR_US
        size = self.hourly.shape[0]
        self.rows += hour.size
        for k, w in enumerate((None, c["fare"], c["total"])):
            self.hourly[:, k] += _bincount(hour, w, size)

    def arrays(self) -> dict:
        return {"rows": self.rows, "hourly": self.hourly,
                "hour_first_us": np.int64(self.first)}
