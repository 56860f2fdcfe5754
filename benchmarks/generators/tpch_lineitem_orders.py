"""TPC-H ``lineitem`` as ``tpch_lineitem`` draws it, with what Q18's
block needs kept at generation: the quantity sum of every large order.

The draws are ``tpch_lineitem``'s own (the same ``[data_seed, chunk]``
streams give the same rows); only the statistics grow.  A chunk holds
whole orders, so an order's exact integer quantity sum is known when
its chunk is made: every order whose sum reaches ``KEEP_FROM`` (250.00,
under every QUANTITY that Q18 draws or validates with) is kept with its
key, and the orders are counted.  ``references/q18_orders.py`` answers
any QUANTITY from that without the rows.  No engine code is used here.
"""

import numpy as np

from ..references.q18_orders import KEEP_FROM
from . import tpch_lineitem as _base
from .tpch_lineitem import copy_columns, generate_chunk, n_chunks  # noqa: F401

#: part of the persisted data set's key: bump on any change to the draws
#: or to the statistics kept beside the table
GENERATOR_VERSION = 1


class Statistics(_base.Statistics):
    """``tpch_lineitem``'s statistics, and beside them
    ``orders`` (count), ``q18_keep_from`` (the least sum kept, scaled by
    100), ``q18_okey`` / ``q18_qty`` (key and scaled quantity sum of
    every order whose sum is at least that)."""

    def __init__(self, params):
        super().__init__(params)
        self.orders = np.zeros((), np.int64)
        self._large = {"okey": [], "qty": []}

    def add(self, c: dict) -> None:
        super().add(c)
        lines = c["lines_per_order"]
        first = np.cumsum(lines) - lines        # an order's first row
        total = np.add.reduceat(c["qty"], first)    # int64: exact
        large = total >= KEEP_FROM
        self.orders += lines.size
        self._large["okey"].append(c["okey"][first][large])
        self._large["qty"].append(total[large])

    def arrays(self) -> dict:
        out = super().arrays()
        out["orders"] = self.orders
        out["q18_keep_from"] = np.int64(KEEP_FROM)
        for k, parts in self._large.items():
            out["q18_" + k] = (np.concatenate(parts) if parts
                               else np.zeros(0, np.int64))
        return out
