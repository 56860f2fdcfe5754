"""TPC-H ``lineitem`` in dbgen's shapes, from a seed, in numpy.

Written from the TPC-H specification (section 4.2.3) as remembered --
there is no network here -- so every constant below is also listed
under ``assumed`` in the configuration files.  numpy's ``default_rng``
stands in for dbgen's random streams: the distributions are the
spec's, the exact values are not dbgen's.

The table is made chunk by chunk (a chunk is a contiguous range of
orders, seeded by ``[data_seed, chunk_index]``), and while it is made
the sufficient statistics that the plain references answer from are
accumulated: integer tables small enough to keep, from which any
parameter draw of Q1 and Q6 can be answered exactly without the rows.
No engine code is used here.
"""

import datetime

import numpy as np

# the layout of the statistics is what generator and references share
from ..references.common import N_DISC, N_TAX, SHIP_LO, days
from ..references.common import LINESTATUSES as _LINESTATUSES
from ..references.common import RETURNFLAGS as _RETURNFLAGS

#: part of the persisted data set's key: bump on any change to the draws
GENERATOR_VERSION = 1

START_DATE = days(datetime.date(1992, 1, 1))        # o_orderdate lower bound
END_ORDER_DATE = days(datetime.date(1998, 8, 2))    # ENDDATE - 151 days
CURRENT_DATE = days(datetime.date(1995, 6, 17))
SHIP_DAYS = END_ORDER_DATE + 121 - SHIP_LO + 1      # distinct l_shipdate values
RETURNFLAGS = np.array(_RETURNFLAGS)
LINESTATUSES = np.array(_LINESTATUSES)
#: l_quantity classes Q6 needs: < 24, == 24, >= 25 (QUANTITY is 24 or 25)
N_QTY_CLASS = 3


def order_key(index):
    """dbgen's sparse order keys: 8 used of every 32."""
    return (index // 8) * 32 + index % 8 + 1


def retail_price_cents(partkey):
    """p_retailprice of the spec, in cents."""
    return 90000 + (partkey // 10) % 20001 + 100 * (partkey % 1000)


def n_chunks(params) -> int:
    return -(-params["orders"] // params["chunk_orders"])


def generate_chunk(params, data_seed: int, chunk_index: int) -> dict:
    """The lineitems of orders ``[chunk_index * chunk_orders, ...)`` as
    integer columns (money in cents, dates in days since 1970)."""
    lo = chunk_index * params["chunk_orders"]
    n_orders = min(params["chunk_orders"], params["orders"] - lo)
    rng = np.random.default_rng([data_seed, chunk_index])
    orderdate = rng.integers(START_DATE, END_ORDER_DATE + 1, n_orders)
    lines = rng.integers(1, 8, n_orders)
    of_order = np.repeat(np.arange(n_orders), lines)
    n = of_order.size
    qty = rng.integers(1, 51, n)
    partkey = rng.integers(1, params["parts"] + 1, n)
    ship = orderdate[of_order] + rng.integers(1, 122, n)
    receipt = ship + rng.integers(1, 31, n)
    returned = rng.integers(0, 2, n) * 2            # 'A' or 'R'
    return {
        "lines_per_order": lines,
        "order_index": lo + of_order,
        "okey": order_key(lo + of_order),
        "qty": qty * 100,
        "price": qty * retail_price_cents(partkey),
        "disc": rng.integers(0, N_DISC, n),
        "tax": rng.integers(0, N_TAX, n),
        "rf": np.where(receipt <= CURRENT_DATE, returned, 1),
        "ls": (ship > CURRENT_DATE).astype(np.int64),
        "ship": ship.astype(np.int32),
    }


def copy_columns(chunk: dict) -> dict:
    """The chunk as ``Cluster.copy_from`` takes it."""
    return {
        "l_orderkey": chunk["okey"],
        "l_quantity": chunk["qty"] / 100.0,
        "l_extendedprice": chunk["price"] / 100.0,
        "l_discount": chunk["disc"] / 100.0,
        "l_tax": chunk["tax"] / 100.0,
        "l_returnflag": RETURNFLAGS[chunk["rf"]].tolist(),
        "l_linestatus": LINESTATUSES[chunk["ls"]].tolist(),
        "l_shipdate": chunk["ship"],
    }


def _bincount(index, weights, size):
    """Exact integer bincount: float64 partial sums of integers stay
    exact below 2**53, which is checked, not assumed."""
    out = np.bincount(index, weights=weights, minlength=size)
    if out.size and out.max() >= 2.0 ** 53:
        raise OverflowError("chunk too large for an exact float64 bincount")
    return out.astype(np.int64)


class Statistics:
    """Sufficient statistics of the table, accumulated chunk by chunk.

    ``q1[flag, status, ship day, k]``: k = sum qty, sum price,
    sum price*(100-disc), sum price*(100-disc)*(100+tax), sum disc, count.
    ``q6[ship day, discount, quantity class, k]``: k = sum price*disc, count.
    ``by_day[ship day, k]``: count, sum qty, max price.
    ``by_day_disc_tax[(day*11 + disc)*9 + tax, k]``: count, sum qty.
    ``lookup_*``: every row of a fixed sample of orders (one order in
    ``orders // lookup_sample_orders``), for routed lookups by key.
    """

    def __init__(self, params):
        self.stride = max(params["orders"] // params["lookup_sample_orders"], 1)
        self.rows = np.zeros((), np.int64)
        self.q1 = np.zeros((3, 2, SHIP_DAYS, 6), np.int64)
        self.q6 = np.zeros((SHIP_DAYS, N_DISC, N_QTY_CLASS, 2), np.int64)
        self.by_day = np.zeros((SHIP_DAYS, 3), np.int64)
        self.by_day_disc_tax = np.zeros((SHIP_DAYS * N_DISC * N_TAX, 2),
                                        np.int64)
        self.lines_hist = np.zeros(8, np.int64)
        self._lookup = {k: [] for k in ("okey", "qty", "price", "ship", "rf")}

    def add(self, c: dict) -> None:
        qty, price, disc, tax = c["qty"], c["price"], c["disc"], c["tax"]
        day = (c["ship"] - SHIP_LO).astype(np.int64)
        self.rows += qty.size
        disc_price = price * (100 - disc)
        g = (c["rf"] * 2 + c["ls"]) * SHIP_DAYS + day
        size = 6 * SHIP_DAYS
        for k, w in enumerate((qty, price, disc_price,
                               disc_price * (100 + tax), disc, None)):
            self.q1[..., k] += _bincount(g, w, size).reshape(3, 2, SHIP_DAYS)
        qclass = (qty >= 2400).astype(np.int64) + (qty >= 2500)
        g = (day * N_DISC + disc) * N_QTY_CLASS + qclass
        size = SHIP_DAYS * N_DISC * N_QTY_CLASS
        for k, w in enumerate((price * disc, None)):
            self.q6[..., k] += _bincount(g, w, size).reshape(
                SHIP_DAYS, N_DISC, N_QTY_CLASS)
        self.by_day[:, 0] += _bincount(day, None, SHIP_DAYS)
        self.by_day[:, 1] += _bincount(day, qty, SHIP_DAYS)
        np.maximum.at(self.by_day[:, 2], day, price)
        g = (day * N_DISC + disc) * N_TAX + tax
        size = SHIP_DAYS * N_DISC * N_TAX
        self.by_day_disc_tax[:, 0] += _bincount(g, None, size)
        self.by_day_disc_tax[:, 1] += _bincount(g, qty, size)
        keep = c["order_index"] % self.stride == 0
        for k in self._lookup:
            self._lookup[k].append(c[k][keep])
        self.lines_hist += np.bincount(c["lines_per_order"], minlength=8)[:8]

    def arrays(self) -> dict:
        out = {k: getattr(self, k) for k in (
            "rows", "q1", "q6", "by_day", "by_day_disc_tax", "lines_hist")}
        for k, parts in self._lookup.items():
            out["lookup_" + k] = (np.concatenate(parts) if parts
                                  else np.zeros(0, np.int64))
        return out
