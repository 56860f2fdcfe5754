"""TPC-H ``lineitem``, ``orders`` and ``customer`` (spec 4.2.3) from one
stream, and what Q3 (spec 2.4.3) needs kept at generation.

``lineitem`` is ``tpch_lineitem``'s, draw for draw: the same
``[data_seed, chunk]`` stream is replayed (as ``tpch_lineitem_supp``
does), so the same seed gives the same rows.  ``orders`` comes from the
same order stream -- its keys and dates are the ones the lineitems
carry -- with the further draws (customer, priority) at the stream's
end; ``customer`` is drawn whole from a stream of its own and arrives
with chunk 0.  Shapes as the spec has them (as remembered: see
``assumed`` in the configuration): ``o_custkey`` uniform over the
customers whose key is not a multiple of 3, ``o_shippriority`` 0,
``c_mktsegment`` uniform over the five segments, ten orders a customer
(a rehearsal's ``--orders`` cuts the customers with them).

Q3's DATE is a day of March 1995, so an order can only count where
``o_orderdate < DATE <= 1995-03-31`` and one of its lines ships after
``DATE >= 1995-03-01``, at most 121 days after the order: the
statistics keep those CANDIDATES -- the orders dated 1994-11-01 to
1995-03-30 with their customer's segment, and their lines shipped
after 1995-03-01 with the exact revenue term -- from which
``references/q3.py`` answers any draw.  No engine code is used here.
"""

import datetime
import functools
import importlib.util

import numpy as np

from ..references.common import N_DISC, N_TAX, days
from . import tpch_lineitem as _base
from .tpch_lineitem import n_chunks  # noqa: F401

#: part of the persisted data set's key: bump on any change to the draws
#: or to the statistics kept beside the tables
GENERATOR_VERSION = 1

# The deployment joins ON THE DEVICE.  A program without the device
# join (citus_tpu/ops/join.py: the parent of PR 38 and before) would
# ingest the three tables, pull all 76.5 M rows into host numpy for
# every statement and only then refuse the published comma join ("cross
# join result too large"), minutes a statement: it is told so here, at
# once and before anything is ingested.  Only the module's presence is
# looked up (its packages are imported for that, the module is not).
if importlib.util.find_spec("citus_tpu.ops.join") is None:
    raise SystemExit(
        "tpch_q3_tables: this program has no device join "
        "(citus_tpu/ops/join.py); configuration tpch_sf10_q3_1chip "
        "cannot run on it")

SEGMENTS = np.array(["AUTOMOBILE", "BUILDING", "FURNITURE", "HOUSEHOLD",
                     "MACHINERY"])
PRIORITIES = np.array(["1-URGENT", "2-HIGH", "3-MEDIUM", "4-NOT SPECIFIED",
                       "5-LOW"])
STATUSES = np.array(["F", "O", "P"])
#: the orders any DATE of March 1995 can select, and the ship dates of
#: their lines that can count
CANDIDATE_ORDER_DAYS = (days(datetime.date(1994, 11, 1)),
                        days(datetime.date(1995, 3, 30)))
CANDIDATE_SHIP_AFTER = days(datetime.date(1995, 3, 1))
ORDERS_PER_CUSTOMER = 10


def n_customers(params) -> int:
    """The configuration's customers, cut with a rehearsal's orders."""
    return max(3, min(int(params["customers"]),
                      int(params["orders"]) // ORDERS_PER_CUSTOMER))


@functools.lru_cache(maxsize=2)
def _customer(data_seed: int, customers: int) -> dict:
    rng = np.random.default_rng([data_seed, 2 ** 31 - 1])
    return {"c_custkey": np.arange(1, customers + 1),
            "c_mktsegment": rng.integers(0, len(SEGMENTS), customers),
            "c_nationkey": rng.integers(0, 25, customers),
            "c_acctbal": rng.integers(-99_999, 1_000_000, customers)}


def customer(params) -> dict:
    return _customer(int(params["data_seed"]), n_customers(params))


def generate_chunk(params, data_seed: int, chunk_index: int) -> dict:
    """-> {table: {column: integers}} (money in cents, dates in days since
    1970, text as an index into its constants).  ``lineitem`` holds
    ``tpch_lineitem.generate_chunk``'s columns, from the same stream in
    the same order."""
    lo = chunk_index * params["chunk_orders"]
    n_orders = min(params["chunk_orders"], params["orders"] - lo)
    rng = np.random.default_rng([data_seed, chunk_index])
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
    lineitem = {
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
    }
    # the order stream's further draws, after every draw of lineitem's
    customers = n_customers(params)
    pick = rng.integers(0, customers - customers // 3, n_orders)
    open_lines = np.bincount(of_order, weights=ls, minlength=n_orders)
    # o_totalprice = sum of price x (1 + tax) x (1 - discount), in cents
    # (the spec rounds each term; here the sum is rounded once)
    total = _base._bincount(of_order, price * (100 + tax) * (100 - disc),
                            n_orders)
    chunk = {
        "lineitem": lineitem,
        "orders": {
            "o_orderkey": _base.order_key(lo + np.arange(n_orders)),
            "o_custkey": pick + pick // 2 + 1,      # no multiple of 3
            "o_orderstatus": np.where(open_lines == 0, 0,
                                      np.where(open_lines == lines, 1, 2)),
            "o_totalprice": (total + 5000) // 10000,
            "o_orderdate": orderdate.astype(np.int32),
            "o_orderpriority": rng.integers(0, len(PRIORITIES), n_orders),
            "o_shippriority": np.zeros(n_orders, np.int32),
        }}
    if chunk_index == 0:
        chunk["customer"] = customer(dict(params, data_seed=data_seed))
    return chunk


def copy_columns(chunk: dict) -> dict:
    """The chunk as ``Cluster.copy_from`` takes it, table by table."""
    orders = dict(chunk["orders"])
    orders["o_orderstatus"] = STATUSES[orders["o_orderstatus"]].tolist()
    orders["o_orderpriority"] = PRIORITIES[orders["o_orderpriority"]].tolist()
    orders["o_totalprice"] = orders["o_totalprice"] / 100.0
    out = {"orders": orders,
           "lineitem": _base.copy_columns(chunk["lineitem"])}
    if "customer" in chunk:
        c = dict(chunk["customer"])
        c["c_mktsegment"] = SEGMENTS[c["c_mktsegment"]].tolist()
        c["c_acctbal"] = c["c_acctbal"] / 100.0
        out["customer"] = c
    return out


class Statistics:
    """``rows.<table>`` and Q3's candidates: per candidate order
    ``q3_o_orderkey``, ``q3_o_orderdate``, ``q3_o_segment`` (its
    customer's, as an index into ``SEGMENTS``); per line of theirs
    shipped after 1995-03-01 ``q3_l_order`` (the order's position among
    the candidates), ``q3_l_shipdate`` and ``q3_l_revenue`` (price in
    cents x (100 - discount in cents): the sum's term scaled by 10**4)."""

    def __init__(self, params):
        self.segment_of = np.concatenate(
            [[-1], customer(params)["c_mktsegment"]])
        self.rows = {"orders": 0, "lineitem": 0,
                     "customer": n_customers(params)}
        self.kept = 0
        self._parts = {k: [] for k in (
            "q3_o_orderkey", "q3_o_orderdate", "q3_o_segment", "q3_l_order",
            "q3_l_shipdate", "q3_l_revenue")}

    def add(self, chunk: dict) -> None:
        o, l = chunk["orders"], chunk["lineitem"]
        self.rows["orders"] += o["o_orderkey"].size
        self.rows["lineitem"] += l["okey"].size
        lo, hi = CANDIDATE_ORDER_DAYS
        cand = (o["o_orderdate"] >= lo) & (o["o_orderdate"] <= hi)
        # a line's order, as a position in this chunk's orders
        at = l["order_index"] - l["order_index"][0] if l["okey"].size \
            else l["order_index"]
        line = cand[at] & (l["ship"] > CANDIDATE_SHIP_AFTER)
        position = np.cumsum(cand) - 1 + self.kept
        p = self._parts
        p["q3_o_orderkey"].append(o["o_orderkey"][cand])
        p["q3_o_orderdate"].append(o["o_orderdate"][cand])
        p["q3_o_segment"].append(
            self.segment_of[o["o_custkey"][cand]].astype(np.int8))
        p["q3_l_order"].append(position[at[line]])
        p["q3_l_shipdate"].append(l["ship"][line])
        p["q3_l_revenue"].append(l["price"][line] * (100 - l["disc"][line]))
        self.kept += int(cand.sum())

    def arrays(self) -> dict:
        out = {k: np.concatenate(v) if v else np.zeros(0, np.int64)
               for k, v in self._parts.items()}
        for table, rows in self.rows.items():
            out[f"rows.{table}"] = np.int64(rows)
        return out
