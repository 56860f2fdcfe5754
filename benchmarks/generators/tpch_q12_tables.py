"""TPC-H ``lineitem`` and ``orders`` (spec 4.2.3) from one stream, and
what Q12 (spec 2.4.12, Shipping Modes and Order Priority) needs kept at
generation.

``lineitem`` is ``tpch_lineitem``'s, draw for draw, and ``orders`` is
``tpch_q3_tables``': the same ``[data_seed, chunk]`` stream is replayed
in the same order, so the same seed gives the rows those configurations
hold.  Q12's further columns are further draws at the stream's END:
``l_shipmode`` uniform over the seven modes, ``l_commitdate`` =
``o_orderdate`` + 30..90; ``l_receiptdate`` = ``l_shipdate`` + 1..30 is
the draw ``tpch_lineitem`` already makes for ``l_returnflag``.  Shapes
as the spec has them (as remembered: see ``assumed`` in the
configuration).

Q12's parameters are two distinct ship modes and a receipt year of
1993..1997, and its two sums count the lines with ``l_shipdate <
l_commitdate < l_receiptdate`` by the priority class of their order:
the statistics keep that count per (ship mode, receipt year, class) --
7 x 5 x 2 integers -- from which ``references/q12.py`` answers any
draw.  Every line's order exists (the lines are drawn from the orders).
No engine code is used here.
"""

import datetime
import importlib.util

import numpy as np

from ..references.common import N_DISC, N_TAX, days
from . import tpch_lineitem as _base
from .tpch_lineitem import n_chunks  # noqa: F401

#: part of the persisted data set's key: bump on any change to the draws
#: or to the statistics kept beside the tables
GENERATOR_VERSION = 1


def _program_exchanges() -> bool:
    """Whether the program under test has the device join's exchange
    (``citus_tpu/ops/join.py`` ``build_join_exchange``).  The module's
    text is read; nothing of the program is imported for it."""
    spec = importlib.util.find_spec("citus_tpu.ops.join")
    if spec is None or not spec.origin:
        return False
    with open(spec.origin) as fh:
        return "def build_join_exchange" in fh.read()


# The deployment joins ON THE DEVICE, its build relation exchanged
# between the chips.  A program without that entry point (the parent of
# PR 43 and before) plans the statement the same but answers it through
# host frames: every relation pulled whole into host numpy by the raw
# stripe reader, 75 M rows a statement, bucketed on the host and shipped
# up for a sort join -- minutes a statement where a run's window is 50
# seconds (PERF.md section 6, PR 43 holds the reading).  It is told so
# here, at once and before anything is ingested.
if not _program_exchanges():
    raise SystemExit(
        "tpch_q12_tables: this program's device join has no exchange "
        "(citus_tpu/ops/join.py build_join_exchange); configuration "
        "tpch_sf10_q12_4chip cannot run on it")

SHIPMODES = np.array(["REG AIR", "AIR", "RAIL", "SHIP", "TRUCK", "MAIL",
                      "FOB"])
PRIORITIES = np.array(["1-URGENT", "2-HIGH", "3-MEDIUM", "4-NOT SPECIFIED",
                       "5-LOW"])
STATUSES = np.array(["F", "O", "P"])
#: priorities of the class Q12 calls high: 1-URGENT and 2-HIGH
N_HIGH = 2
#: the receipt years DATE can name, and where each begins (and the last ends)
YEARS = (1993, 1994, 1995, 1996, 1997)
YEAR_STARTS = np.array([days(datetime.date(y, 1, 1))
                        for y in YEARS + (YEARS[-1] + 1,)])
ORDERS_PER_CUSTOMER = 10


def n_customers(params) -> int:
    """``tpch_q3_tables``' customers, cut with a rehearsal's orders:
    ``o_custkey`` is drawn over them (the table itself is not held)."""
    return max(3, min(int(params["customers"]),
                      int(params["orders"]) // ORDERS_PER_CUSTOMER))


def generate_chunk(params, data_seed: int, chunk_index: int) -> dict:
    """-> {table: {column: integers}} (money in cents, dates in days since
    1970, text as an index into its constants)."""
    lo = chunk_index * params["chunk_orders"]
    n_orders = min(params["chunk_orders"], params["orders"] - lo)
    rng = np.random.default_rng([data_seed, chunk_index])
    # ---- tpch_lineitem's draws, in its order
    orderdate = rng.integers(_base.START_DATE, _base.END_ORDER_DATE + 1,
                             n_orders)
    lines = rng.integers(1, 8, n_orders)
    of_order = np.repeat(np.arange(n_orders), lines)
    n = of_order.size
    qty = rng.integers(1, 51, n)
    partkey = rng.integers(1, params["parts"] + 1, n)
    ship = orderdate[of_order] + rng.integers(1, 122, n)
    receipt = ship + rng.integers(1, 31, n)
    returned = rng.integers(0, 2, n) * 2
    price = qty * _base.retail_price_cents(partkey)
    disc = rng.integers(0, N_DISC, n)
    tax = rng.integers(0, N_TAX, n)
    ls = (ship > _base.CURRENT_DATE).astype(np.int64)
    # ---- tpch_q3_tables' further draws of the order stream
    customers = n_customers(params)
    pick = rng.integers(0, customers - customers // 3, n_orders)
    priority = rng.integers(0, len(PRIORITIES), n_orders)
    # ---- Q12's, at the stream's end
    mode = rng.integers(0, len(SHIPMODES), n)
    commit = orderdate[of_order] + rng.integers(30, 91, n)
    open_lines = np.bincount(of_order, weights=ls, minlength=n_orders)
    total = _base._bincount(of_order, price * (100 + tax) * (100 - disc),
                            n_orders)
    return {
        "lineitem": {
            "lines_per_order": lines,
            "order_index": lo + of_order,
            "okey": _base.order_key(lo + of_order),
            "qty": qty * 100,
            "price": price,
            "disc": disc,
            "tax": tax,
            "rf": np.where(receipt <= _base.CURRENT_DATE, returned, 1),
            "ls": ls,
            "ship": ship.astype(np.int32),
            "commit": commit.astype(np.int32),
            "receipt": receipt.astype(np.int32),
            "mode": mode,
        },
        "orders": {
            "o_orderkey": _base.order_key(lo + np.arange(n_orders)),
            "o_custkey": pick + pick // 2 + 1,      # no multiple of 3
            "o_orderstatus": np.where(open_lines == 0, 0,
                                      np.where(open_lines == lines, 1, 2)),
            "o_totalprice": (total + 5000) // 10000,
            "o_orderdate": orderdate.astype(np.int32),
            "o_orderpriority": priority,
            "o_shippriority": np.zeros(n_orders, np.int32),
        }}


def copy_columns(chunk: dict) -> dict:
    """The chunk as ``Cluster.copy_from`` takes it, table by table."""
    orders = dict(chunk["orders"])
    orders["o_orderstatus"] = STATUSES[orders["o_orderstatus"]].tolist()
    orders["o_orderpriority"] = PRIORITIES[orders["o_orderpriority"]].tolist()
    orders["o_totalprice"] = orders["o_totalprice"] / 100.0
    line = chunk["lineitem"]
    lineitem = _base.copy_columns(line)
    lineitem["l_commitdate"] = line["commit"]
    lineitem["l_receiptdate"] = line["receipt"]
    lineitem["l_shipmode"] = SHIPMODES[line["mode"]].tolist()
    return {"orders": orders, "lineitem": lineitem}


class Statistics:
    """``rows.<table>`` and ``q12[ship mode, receipt year, class]``: the
    lines with ``l_shipdate < l_commitdate < l_receiptdate`` received in
    that year of 1993..1997, by their order's priority class (0: 1-URGENT
    or 2-HIGH, 1: the other three)."""

    def __init__(self, params):
        self.rows = {"orders": 0, "lineitem": 0}
        self.q12 = np.zeros((len(SHIPMODES), len(YEARS), 2), np.int64)

    def add(self, chunk: dict) -> None:
        o, l = chunk["orders"], chunk["lineitem"]
        self.rows["orders"] += o["o_orderkey"].size
        self.rows["lineitem"] += l["okey"].size
        if not l["okey"].size:
            return
        year = np.searchsorted(YEAR_STARTS, l["receipt"], side="right") - 1
        keep = (l["ship"] < l["commit"]) & (l["commit"] < l["receipt"]) \
            & (year >= 0) & (year < len(YEARS))
        # a line's order, as a position in this chunk's orders
        at = l["order_index"] - l["order_index"][0]
        low = (o["o_orderpriority"][at] >= N_HIGH).astype(np.int64)
        g = (l["mode"][keep] * len(YEARS) + year[keep]) * 2 + low[keep]
        self.q12 += np.bincount(g, minlength=self.q12.size).reshape(
            self.q12.shape)

    def arrays(self) -> dict:
        out = {"q12": self.q12}
        for table, rows in self.rows.items():
            out[f"rows.{table}"] = np.int64(rows)
        return out
