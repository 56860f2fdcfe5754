"""TPC-H ``lineitem`` as ``tpch_lineitem`` draws it, with ``l_suppkey``
and what Q15's view ``revenue`` needs kept at generation: per supplier
and ship month the exact revenue and the row count.

The draws are ``tpch_lineitem``'s own: the same ``[data_seed, chunk]``
stream is replayed draw for draw, so the same seed gives the same rows;
the supplier index (which of a part's four suppliers, spec 4.2.3) is
one further draw at the stream's end.  ``l_suppkey`` follows the spec's
partsupp formula from ``l_partkey``, that index and the supplier count
S (as remembered: see ``assumed`` in the configuration).

The view's DATE is the first day of a month and its window three whole
months, so ``q15_revenue[supplier, month]`` (sum of ``l_extendedprice *
(1 - l_discount)``, scaled by 10**4) and ``q15_rows[supplier, month]``
answer any DATE exactly without the rows
(``references/q15_revenue.py``).  No engine code is used here.
"""

import numpy as np

from ..references.common import N_DISC, N_TAX, SHIP_LO
from ..references.q15_revenue import month_of_ship_day
from . import tpch_lineitem as _base
from .tpch_lineitem import n_chunks  # noqa: F401

#: part of the persisted data set's key: bump on any change to the draws
#: or to the statistics kept beside the table
GENERATOR_VERSION = 1


def supplier_key(partkey, index, suppliers: int):
    """ps_suppkey of a part's ``index``-th supplier (spec 4.2.3):
    (partkey + index * (S/4 + (partkey - 1)/S)) mod S + 1."""
    return (partkey + index * (suppliers // 4 + (partkey - 1) // suppliers)) \
        % suppliers + 1


def generate_chunk(params, data_seed: int, chunk_index: int) -> dict:
    """``tpch_lineitem.generate_chunk``'s columns, from the same stream
    in the same order, and ``supp``."""
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
    chunk = {
        "lines_per_order": lines,
        "order_index": lo + of_order,
        "okey": _base.order_key(lo + of_order),
        "qty": qty * 100,
        "price": qty * _base.retail_price_cents(partkey),
        "disc": rng.integers(0, N_DISC, n),
        "tax": rng.integers(0, N_TAX, n),
        "rf": np.where(receipt <= _base.CURRENT_DATE, returned, 1),
        "ls": (ship > _base.CURRENT_DATE).astype(np.int64),
        "ship": ship.astype(np.int32),
    }
    chunk["supp"] = supplier_key(partkey, rng.integers(0, 4, n),
                                 params["suppliers"])
    return chunk


def copy_columns(chunk: dict) -> dict:
    """The chunk as ``Cluster.copy_from`` takes it."""
    columns = _base.copy_columns(chunk)
    columns["l_suppkey"] = chunk["supp"]
    return columns


class Statistics(_base.Statistics):
    """``tpch_lineitem``'s statistics, and beside them ``suppliers`` (S),
    ``q15_revenue[supplier key, ship month]`` (sum of price in cents x
    (100 - discount in cents): the view's sum scaled by 10**4) and
    ``q15_rows[supplier key, ship month]``; month 0 is January 1992."""

    def __init__(self, params):
        super().__init__(params)
        self.suppliers = int(params["suppliers"])
        self.months = int(month_of_ship_day(_base.SHIP_DAYS - 1)) + 1
        self.month_of_day = month_of_ship_day(np.arange(_base.SHIP_DAYS))
        shape = (self.suppliers + 1, self.months)
        self.q15_revenue = np.zeros(shape, np.int64)
        self.q15_rows = np.zeros(shape, np.int32)

    def add(self, c: dict) -> None:
        super().add(c)
        month = self.month_of_day[(c["ship"] - SHIP_LO).astype(np.int64)]
        g = c["supp"] * self.months + month
        size = self.q15_revenue.size
        self.q15_revenue += _base._bincount(
            g, c["price"] * (100 - c["disc"]), size).reshape(
                self.q15_revenue.shape)
        self.q15_rows += np.bincount(g, minlength=size).astype(
            np.int32).reshape(self.q15_rows.shape)

    def arrays(self) -> dict:
        out = super().arrays()
        out["suppliers"] = np.int64(self.suppliers)
        out["q15_revenue"] = self.q15_revenue
        out["q15_rows"] = self.q15_rows
        return out
