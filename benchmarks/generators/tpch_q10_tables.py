"""TPC-H ``lineitem``, ``orders``, ``customer`` and ``nation`` (spec
4.2.3), and what Q10 (spec 2.4.10, Returned Item Reporting) needs kept
at generation.

``orders``, ``lineitem`` and the customer columns ``tpch_q3_tables``
has (key, nation key, account balance, market segment) are that
generator's, draw for draw: its ``generate_chunk`` is called, not
copied.  ``customer`` gains the spec's text columns from streams of
their own, drawn in blocks of ``TEXT_BLOCK`` customers (stream
``[data_seed, TEXT_STREAM, block]``) so that the reference can make the
words of twenty customers again without making a million and a half:
``c_name`` (``Customer#`` and the key in nine digits), ``c_address``
(10..40 characters), ``c_phone`` (country code ``c_nationkey + 10``,
then groups of three, three and four digits) and ``c_comment``
(29..116 characters).  ``nation`` is the spec's 25 rows.  Both arrive
whole with chunk 0.

Q10's DATE is the first day of a month from 1993-02-01 to 1995-01-01
and its quarter is three whole months, so an order can count only
where it is dated 1993-02-01 .. 1995-03-31 and has a line with
``l_returnflag = 'R'``: the statistics keep those orders -- customer,
month and the exact sum of the revenue terms of their ``R`` lines --
from which ``references/q10.py`` answers any draw.  No engine code is
used here.
"""

import datetime

import numpy as np

from ..references.common import days
from . import tpch_q3_tables as _q3
from .tpch_lineitem import n_chunks  # noqa: F401

#: part of the persisted data set's key: bump on any change to the draws
#: or to the statistics kept beside the tables
GENERATOR_VERSION = 1

#: (n_name, n_regionkey) by n_nationkey, as the spec lists them
NATIONS = (
    ("ALGERIA", 0), ("ARGENTINA", 1), ("BRAZIL", 1), ("CANADA", 1),
    ("EGYPT", 4), ("ETHIOPIA", 0), ("FRANCE", 3), ("GERMANY", 3),
    ("INDIA", 2), ("INDONESIA", 2), ("IRAN", 4), ("IRAQ", 4), ("JAPAN", 2),
    ("JORDAN", 4), ("KENYA", 0), ("MOROCCO", 0), ("MOZAMBIQUE", 0),
    ("PERU", 1), ("CHINA", 2), ("ROMANIA", 3), ("SAUDI ARABIA", 4),
    ("VIETNAM", 2), ("RUSSIA", 3), ("UNITED KINGDOM", 3),
    ("UNITED STATES", 1))
RETURNED = 2            # l_returnflag 'R' in tpch_lineitem's RETURNFLAGS
#: the orders any DATE can select: 26 whole months
FIRST_MONTH = (1993, 2)
N_MONTHS = 26
CANDIDATE_ORDER_DAYS = (days(datetime.date(1993, 2, 1)),
                        days(datetime.date(1995, 3, 31)))
TEXT_STREAM = 2 ** 31 - 2
NATION_STREAM = 2 ** 31 - 3
TEXT_BLOCK = 1 << 16
ADDRESS_CHARS = np.frombuffer(
    b"abcdefghijklmnopqrstuvwxyzABCDEFGHIJKLMNOPQRSTUVWXYZ0123456789 ,",
    np.uint8)
COMMENT_CHARS = np.frombuffer(b"abcdefghijklmnopqrstuvwxyz      .,;-",
                              np.uint8)

n_customers = _q3.n_customers


def month_of(day) -> np.ndarray:
    """Months since ``FIRST_MONTH`` of days since 1970."""
    d = np.asarray(day, "datetime64[D]").astype("datetime64[M]")
    first = np.datetime64(f"{FIRST_MONTH[0]:04d}-{FIRST_MONTH[1]:02d}", "M")
    return (d - first).astype(np.int64)


def _words(rng, n: int, lo: int, hi: int, chars: np.ndarray) -> list:
    """``n`` strings of ``lo..hi`` characters of ``chars``, the first
    and last never a blank."""
    length = rng.integers(lo, hi + 1, n)
    m = chars[rng.integers(0, chars.size, (n, hi))]
    solid = chars[chars != 32]
    m[:, 0] = solid[rng.integers(0, solid.size, n)]
    last = solid[rng.integers(0, solid.size, n)]
    m[np.arange(n), length - 1] = last
    m[np.arange(hi)[None, :] >= length[:, None]] = 0
    return [b.decode() for b in m.view(f"S{hi}").ravel().tolist()]


def customer_text(data_seed: int, custkeys, nationkeys) -> dict:
    """The text columns of the customers ``custkeys`` (ascending, with
    their ``c_nationkey``): block by block, each from its own stream,
    and of a block only the customers asked for."""
    custkeys = np.asarray(custkeys, np.int64)
    nationkeys = np.asarray(nationkeys, np.int64)
    out = {"c_name": [], "c_address": [], "c_phone": [], "c_comment": []}
    blocks = (custkeys - 1) // TEXT_BLOCK
    for b in np.unique(blocks):
        rng = np.random.default_rng([data_seed, TEXT_STREAM, int(b)])
        address = _words(rng, TEXT_BLOCK, 10, 40, ADDRESS_CHARS)
        digits = rng.integers([100, 100, 1000], [1000, 1000, 10000],
                              (TEXT_BLOCK, 3))
        comment = _words(rng, TEXT_BLOCK, 29, 116, COMMENT_CHARS)
        mine = blocks == b
        for key, nation in zip(custkeys[mine].tolist(),
                               nationkeys[mine].tolist()):
            at = (key - 1) % TEXT_BLOCK
            d = digits[at]
            out["c_name"].append(f"Customer#{key:09d}")
            out["c_address"].append(address[at])
            out["c_phone"].append(
                f"{nation + 10}-{d[0]}-{d[1]}-{d[2]}")
            out["c_comment"].append(comment[at])
    return out


def nation(data_seed: int) -> dict:
    rng = np.random.default_rng([data_seed, NATION_STREAM])
    return {"n_nationkey": np.arange(len(NATIONS)),
            "n_name": [n for n, _ in NATIONS],
            "n_regionkey": np.array([r for _, r in NATIONS]),
            "n_comment": _words(rng, len(NATIONS), 31, 114, COMMENT_CHARS)}


def generate_chunk(params, data_seed: int, chunk_index: int) -> dict:
    """``tpch_q3_tables.generate_chunk``'s tables; chunk 0 also holds
    ``customer`` with its text columns and ``nation``."""
    chunk = _q3.generate_chunk(params, data_seed, chunk_index)
    if "customer" in chunk:
        c = dict(chunk["customer"])
        c.update(customer_text(data_seed, c["c_custkey"], c["c_nationkey"]))
        chunk["customer"] = c
        chunk["nation"] = nation(data_seed)
    return chunk


def copy_columns(chunk: dict) -> dict:
    """The chunk as ``Cluster.copy_from`` takes it, table by table."""
    out = _q3.copy_columns(chunk)
    if "nation" in chunk:
        out["nation"] = chunk["nation"]
    return out


class Statistics:
    """``rows.<table>``, what the reference needs to make the customers'
    columns again (``q10_data_seed``, ``q10_customers``) and Q10's
    candidates: per order dated inside ``CANDIDATE_ORDER_DAYS`` with an
    ``R`` line ``q10_o_custkey``, ``q10_o_month`` (months since
    1993-02) and ``q10_o_revenue`` (the sum over its ``R`` lines of
    price in cents x (100 - discount in cents): the query's sum scaled
    by 10**4)."""

    def __init__(self, params):
        self.params = dict(params)
        self.rows = {"orders": 0, "lineitem": 0,
                     "customer": n_customers(params), "nation": len(NATIONS)}
        self._parts = {k: [] for k in (
            "q10_o_custkey", "q10_o_month", "q10_o_revenue")}

    def add(self, chunk: dict) -> None:
        o, l = chunk["orders"], chunk["lineitem"]
        n = o["o_orderkey"].size
        self.rows["orders"] += n
        self.rows["lineitem"] += l["okey"].size
        if not n:
            return
        lo, hi = CANDIDATE_ORDER_DAYS
        at = l["order_index"] - l["order_index"][0]
        returned = l["rf"] == RETURNED
        revenue = np.zeros(n, np.int64)
        np.add.at(revenue, at[returned],
                  l["price"][returned] * (100 - l["disc"][returned]))
        has = np.zeros(n, bool)
        has[at[returned]] = True
        keep = has & (o["o_orderdate"] >= lo) & (o["o_orderdate"] <= hi)
        p = self._parts
        p["q10_o_custkey"].append(o["o_custkey"][keep].astype(np.int32))
        p["q10_o_month"].append(
            month_of(o["o_orderdate"][keep]).astype(np.int8))
        p["q10_o_revenue"].append(revenue[keep])

    def arrays(self) -> dict:
        out = {k: np.concatenate(v) if v else np.zeros(0, np.int64)
               for k, v in self._parts.items()}
        out["q10_data_seed"] = np.int64(self.params["data_seed"])
        out["q10_customers"] = np.int64(self.rows["customer"])
        for table, rows in self.rows.items():
            out[f"rows.{table}"] = np.int64(rows)
        return out
