"""TPC-H ``lineitem``, ``orders``, ``customer``, ``supplier``, ``nation``
and ``region`` (spec 4.2.3), and what Q5 (spec 2.4.5, Local Supplier
Volume) needs kept at generation.

``orders``, ``customer``, ``nation`` and the columns of ``lineitem``
that ``tpch_q10_tables`` has are that generator's, draw for draw: its
``generate_chunk`` is called, not copied, so configuration
``tpch_sf10_q10_1chip``'s data seed gives its rows.  ``l_suppkey``
follows ``tpch_lineitem_supp``'s partsupp formula (its ``supplier_key``
is called) from ``l_partkey`` -- the chunk's own stream replayed as far
as that draw, which the other generators make and do not keep -- and
the index of the part's supplier, a draw of a stream of its own
(``[data_seed, SUPP_STREAM, chunk]``).  ``supplier`` (``s_nationkey``
uniform over the 25 nations, ``Supplier#`` and the key in nine digits,
an address of 10..40 and a comment of 25..100 characters, a phone as
``c_phone``) and ``region`` (the spec's 5) arrive whole with chunk 0,
beside ``customer`` and ``nation``.

Q5's REGION is one of the five regions and its DATE the first of
January of 1993..1997, and a line counts where its order is dated in
that year, its supplier's nation lies in the region and its order's
customer has the SUPPLIER'S nation: the statistics keep, per (nation of
the supplier, order year), the exact sum of the revenue terms of the
lines whose customer shares that nation, and their number -- 25 x 5
integers twice -- from which ``references/q5.py`` answers any draw.  No
engine code is used here.
"""

import datetime
import importlib.util

import numpy as np

from ..references.common import days
from . import tpch_lineitem as _base
from . import tpch_q10_tables as _q10
from . import tpch_q3_tables as _q3
from .tpch_lineitem import n_chunks  # noqa: F401
from .tpch_lineitem_supp import supplier_key
from .tpch_q10_tables import (  # noqa: F401
    ADDRESS_CHARS, COMMENT_CHARS, NATIONS, _words, n_customers,
)

#: part of the persisted data set's key: bump on any change to the draws
#: or to the statistics kept beside the tables
GENERATOR_VERSION = 1


def _program_plans_join_graphs() -> bool:
    """Whether the program under test plans a join GRAPH for the device
    (``citus_tpu/planner/join_planner.py``: a spanning tree and the
    equalities left over as ``cycle_filters``).  The module's text is
    read; nothing of the program is imported for it."""
    spec = importlib.util.find_spec("citus_tpu.planner.join_planner")
    if spec is None or not spec.origin:
        return False
    with open(spec.origin) as fh:
        return "cycle_filters" in fh.read()


# The deployment joins ON THE DEVICE.  Q5's join graph has a cycle
# (customer - orders - lineitem - supplier - customer); a program whose
# device join plans a tree from the steps alone (the parent of PR 53 and
# before) answers the published text on the HOST path ("a step's keys
# name more than two relations"): 76.6 M rows pulled into host numpy a
# statement, minutes where a run's window is 50 seconds (PERF.md section
# 6, PR 43 holds the reading of that path).  It is told so here, at once
# and before anything is ingested.
if not _program_plans_join_graphs():
    raise SystemExit(
        "tpch_q5_tables: this program's device join plans no join graph "
        "with a cycle (citus_tpu/planner/join_planner.py cycle_filters); "
        "configuration tpch_sf10_q5_1chip cannot run on it")

#: r_name by r_regionkey, as the spec lists them
REGIONS = ("AFRICA", "AMERICA", "ASIA", "EUROPE", "MIDDLE EAST")
#: the order years DATE can name, and where each begins (and the last ends)
YEARS = (1993, 1994, 1995, 1996, 1997)
YEAR_STARTS = np.array([days(datetime.date(y, 1, 1))
                        for y in YEARS + (YEARS[-1] + 1,)])
SUPP_STREAM = 2 ** 31 - 4
SUPPLIER_STREAM = 2 ** 31 - 5
REGION_STREAM = 2 ** 31 - 6
ORDERS_PER_SUPPLIER = 150


def n_suppliers(params) -> int:
    """The configuration's suppliers (10,000 x SF: one to 150 orders),
    cut with a rehearsal's orders as the customers are."""
    return max(4, min(int(params["suppliers"]),
                      int(params["orders"]) // ORDERS_PER_SUPPLIER))


def supplier(params) -> dict:
    """``supplier`` whole: integers, and its text columns as words."""
    n = n_suppliers(params)
    rng = np.random.default_rng([int(params["data_seed"]), SUPPLIER_STREAM])
    nation = rng.integers(0, len(NATIONS), n)
    digits = rng.integers([100, 100, 1000], [1000, 1000, 10000], (n, 3))
    return {
        "s_suppkey": np.arange(1, n + 1),
        "s_name": [f"Supplier#{k:09d}" for k in range(1, n + 1)],
        "s_address": _words(rng, n, 10, 40, ADDRESS_CHARS),
        "s_nationkey": nation,
        "s_phone": [f"{c + 10}-{d[0]}-{d[1]}-{d[2]}"
                    for c, d in zip(nation.tolist(), digits.tolist())],
        "s_acctbal": rng.integers(-99_999, 1_000_000, n),
        "s_comment": _words(rng, n, 25, 100, COMMENT_CHARS)}


def region(data_seed: int) -> dict:
    rng = np.random.default_rng([data_seed, REGION_STREAM])
    return {"r_regionkey": np.arange(len(REGIONS)),
            "r_name": list(REGIONS),
            "r_comment": _words(rng, len(REGIONS), 31, 115, COMMENT_CHARS)}


def _partkeys(params, data_seed: int, chunk_index: int, lines) -> np.ndarray:
    """``l_partkey`` of the chunk: the chunk's stream replayed as far as
    that draw (order dates, lines an order, quantities, part keys: the
    order ``tpch_lineitem`` draws in)."""
    n_orders = lines.size
    rng = np.random.default_rng([data_seed, chunk_index])
    rng.integers(_base.START_DATE, _base.END_ORDER_DATE + 1, n_orders)
    again = rng.integers(1, 8, n_orders)
    if not (again == lines).all():
        raise AssertionError("tpch_lineitem's stream is not replayed")
    n = int(again.sum())
    rng.integers(1, 51, n)
    return rng.integers(1, params["parts"] + 1, n)


def generate_chunk(params, data_seed: int, chunk_index: int) -> dict:
    """``tpch_q10_tables.generate_chunk``'s tables, ``lineitem`` with
    ``supp``; chunk 0 also holds ``supplier`` and ``region``."""
    chunk = _q10.generate_chunk(params, data_seed, chunk_index)
    line = dict(chunk["lineitem"])
    suppliers = n_suppliers(params)
    index = np.random.default_rng(
        [data_seed, SUPP_STREAM, chunk_index]).integers(0, 4, line["okey"].size)
    line["supp"] = supplier_key(
        _partkeys(params, data_seed, chunk_index, line["lines_per_order"]),
        index, suppliers)
    chunk["lineitem"] = line
    if chunk_index == 0:
        chunk["supplier"] = supplier(dict(params, data_seed=data_seed))
        chunk["region"] = region(data_seed)
    return chunk


def copy_columns(chunk: dict) -> dict:
    """The chunk as ``Cluster.copy_from`` takes it, table by table."""
    out = _q10.copy_columns(chunk)
    out["lineitem"]["l_suppkey"] = chunk["lineitem"]["supp"]
    if "supplier" in chunk:
        s = dict(chunk["supplier"])
        s["s_acctbal"] = s["s_acctbal"] / 100.0
        out["supplier"] = s
        out["region"] = chunk["region"]
    return out


class Statistics:
    """``rows.<table>`` and Q5's sums: ``q5_revenue[nation, year]`` --
    over the lines whose supplier is of ``nation``, whose order is dated
    in ``YEARS[year]`` and whose order's customer is of the same nation,
    the sum of price in cents x (100 - discount in cents): the query's
    sum scaled by 10**4 -- and ``q5_rows[nation, year]``, their number;
    ``q5_both`` counts the lines with a partner in ``orders`` of any of
    the five years whatever the nations (what the cycle filter sees of
    one region and year is a 25th of it, about)."""

    def __init__(self, params):
        self.params = dict(params)
        self.customer_nation = np.concatenate(
            [[-1], _q3.customer(params)["c_nationkey"]])
        self.supplier_nation = np.concatenate(
            [[-1], supplier(params)["s_nationkey"]])
        self.rows = {"orders": 0, "lineitem": 0,
                     "customer": n_customers(params),
                     "supplier": n_suppliers(params),
                     "nation": len(NATIONS), "region": len(REGIONS)}
        shape = (len(NATIONS), len(YEARS))
        self.q5_revenue = np.zeros(shape, np.int64)
        self.q5_rows = np.zeros(shape, np.int64)
        self.q5_both = np.zeros((), np.int64)

    def add(self, chunk: dict) -> None:
        o, l = chunk["orders"], chunk["lineitem"]
        n = o["o_orderkey"].size
        self.rows["orders"] += n
        self.rows["lineitem"] += l["okey"].size
        if not n:
            return
        at = l["order_index"] - l["order_index"][0]
        year = np.searchsorted(YEAR_STARTS, o["o_orderdate"][at],
                               side="right") - 1
        in_years = (year >= 0) & (year < len(YEARS))
        self.q5_both += int(in_years.sum())
        of_supplier = self.supplier_nation[l["supp"]]
        keep = in_years \
            & (self.customer_nation[o["o_custkey"][at]] == of_supplier)
        g = of_supplier[keep] * len(YEARS) + year[keep]
        size = self.q5_revenue.size
        self.q5_revenue += _base._bincount(
            g, l["price"][keep] * (100 - l["disc"][keep]), size).reshape(
                self.q5_revenue.shape)
        self.q5_rows += np.bincount(g, minlength=size).reshape(
            self.q5_rows.shape)

    def arrays(self) -> dict:
        out = {"q5_revenue": self.q5_revenue, "q5_rows": self.q5_rows,
               "q5_both": self.q5_both}
        for table, rows in self.rows.items():
            out[f"rows.{table}"] = np.int64(rows)
        return out
