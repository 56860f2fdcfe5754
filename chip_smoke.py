#!/usr/bin/env python3
"""Chip smoke: the scan -> filter -> aggregate path on a real TPU.

One process, the entry points a user calls (``ct.Cluster(dir)``,
``cl.copy_from``, ``cl.execute``) and nothing beneath them.  It loads a
TPC-H ``lineitem`` at the SF10 row count (8 shards per device), runs the
legs listed in ``main`` and checks every answer against a plain numpy
reference that is accumulated while the data is generated and shares no
code with the engine.  Each leg also names the kernel slot it must have
used and asserts it from the query's exported trace, its ``explain``
dict and counter deltas.

It fails loudly: no accelerator, a leg that raises, a wrong answer or a
leg that ran on another path than the one named all exit non-zero and
print no result line.  The last line of a successful run is

    {"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}

The CPU rehearsal (``--rehearse-on-cpu``, tiny ``--rows``) exists to
debug the script where there is no chip; it stamps ``"platform":
"cpu", "rehearsal": true`` and is never chosen automatically.  Every
time printed here is "elapsed, not a metric": nothing is repeated, the
first call of each leg compiles, and a one-chip machine shares its
host's cores.
"""

import argparse
import contextlib
import datetime
import decimal
import json
import os
import shutil
import subprocess
import sys
import tempfile
import threading
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))

#: TPC-H lineitem cardinality at scale factor 10 (BASELINE.json config 2)
SF10_LINEITEM_ROWS = 59_986_052
SHARDS_PER_DEVICE = 8
CHUNK_ROWS = 4_000_000

EPOCH = datetime.date(1970, 1, 1)
SHIP_LO = 8036            # 1992-01-02 in days since the epoch
SHIP_DAYS = 2526          # distinct l_shipdate values (TPC-H 4.2.3)
RETURNFLAGS = np.array(["A", "N", "R"])
LINESTATUSES = np.array(["F", "O"])

#: relative tolerance for float64 results: the TPU has no native f64,
#: XLA emulates it, and partial sums combine in another order than
#: numpy's pairwise summation
F64_RTOL = 1e-9
#: DDSketch's value error bound (planner/aggregates.py documents ~2.7%)
#: plus room for an emulated-f64 log landing one bucket off at an edge
SKETCH_RTOL = 0.06

LINEITEM_DDL = """CREATE TABLE lineitem (
    l_orderkey bigint NOT NULL, l_quantity decimal(12,2),
    l_extendedprice decimal(12,2), l_discount decimal(12,2),
    l_tax decimal(12,2), l_returnflag text, l_linestatus text,
    l_shipdate date)"""

ORDERS_DDL = """CREATE TABLE orders (
    o_orderkey bigint NOT NULL, o_custkey bigint NOT NULL,
    o_totalprice decimal(12,2))"""

# TPC-H Q1 and Q6 as published (substitution parameters 90 / 1994, .06, 24)
Q1 = """SELECT l_returnflag, l_linestatus,
  sum(l_quantity) AS sum_qty,
  sum(l_extendedprice) AS sum_base_price,
  sum(l_extendedprice * (1 - l_discount)) AS sum_disc_price,
  sum(l_extendedprice * (1 - l_discount) * (1 + l_tax)) AS sum_charge,
  avg(l_quantity) AS avg_qty, avg(l_extendedprice) AS avg_price,
  avg(l_discount) AS avg_disc, count(*) AS count_order
FROM lineitem WHERE l_shipdate <= date '1998-12-01' - interval '90' day
GROUP BY l_returnflag, l_linestatus
ORDER BY l_returnflag, l_linestatus"""
Q1_SHIP_HI = (datetime.date(1998, 12, 1) - datetime.timedelta(days=90)
              - EPOCH).days

Q6_TEMPLATE = """SELECT sum(l_extendedprice * l_discount) AS revenue
FROM lineitem
WHERE l_shipdate >= date '{y}-01-01'
  AND l_shipdate < date '{y1}-01-01'
  AND l_discount BETWEEN {dlo} AND {dhi} AND l_quantity < {qty}"""
#: (year, discount lo cents, discount hi cents, quantity): the published
#: Q6 first, then a literal variant of the same family for the coalesced
#: pair
Q6_VARIANTS = [(1994, 5, 7, 24), (1995, 2, 4, 25), (1996, 6, 8, 24)]

Q_DIRECT = """SELECT l_shipdate, count(*) AS n, sum(l_quantity) AS sum_qty,
  max(l_extendedprice) AS max_price
FROM lineitem GROUP BY l_shipdate"""

# 2,527 x 12 x 10 = 303,240 slots of the keys' provable domain.  With a
# min among the partials the plan cannot ride the group product: the
# device hash table, its slots from the key domain
Q_HASH = """SELECT l_shipdate, l_discount, l_tax, count(*) AS n,
  sum(l_quantity) AS sum_qty, min(l_quantity) AS min_qty
FROM lineitem GROUP BY l_shipdate, l_discount, l_tax"""
# ... and without it, 10 planes: the direct table past 65,536 slots
# where the rows outnumber the slots twice (planner/physical.py
# _product_reaches), the hash table under that
Q_PRODUCT = """SELECT l_shipdate, l_discount, l_tax, count(*) AS n,
  sum(l_quantity) AS sum_qty
FROM lineitem GROUP BY l_shipdate, l_discount, l_tax"""
HASH_DOMAIN_SLOTS = (SHIP_DAYS + 1) * 12 * 10
# Q18's block (TPC-H 2.4.18): one group per order on the distribution
# column, so a host of several devices builds one hash table a device,
# each over its own shards; the threshold is taken from the reference
Q_ORDERS = """SELECT l_orderkey, sum(l_quantity) AS sum_qty FROM lineitem
GROUP BY l_orderkey HAVING sum(l_quantity) > {threshold}"""
#: orders the block's HAVING is set to keep
ORDERS_KEPT = 20

Q_ROUTER = """SELECT l_orderkey, l_quantity, l_extendedprice, l_shipdate,
  l_returnflag
FROM lineitem WHERE l_orderkey = $1"""

JOIN_SHIP_HI = SHIP_LO + 31
Q_JOIN = f"""SELECT count(*), sum(l.l_quantity), sum(o.o_totalprice)
FROM lineitem l JOIN orders o ON l.l_orderkey = o.o_orderkey
WHERE l.l_shipdate < date '{EPOCH + datetime.timedelta(days=JOIN_SHIP_HI)}'"""

# the colocated join on the device (ops/join.py): lineitem and an
# ``orders`` table distributed on the ORDER key are colocated, customer
# is a reference table -- TPC-H Q3's join, in the published comma form
ORDERS_K_DDL = """CREATE TABLE orders_k (
    o_orderkey bigint NOT NULL, o_custkey bigint NOT NULL,
    o_totalprice decimal(12,2))"""
CUSTOMER_DDL = """CREATE TABLE customer (
    c_custkey bigint NOT NULL, c_mktsegment text)"""
SEGMENTS = ["AUTOMOBILE", "BUILDING", "FURNITURE", "HOUSEHOLD", "MACHINERY"]
Q_JOIN_COLOCATED = f"""SELECT c_mktsegment, count(*), sum(l_quantity),
  sum(o_totalprice)
FROM customer, orders_k, lineitem
WHERE c_custkey = o_custkey AND l_orderkey = o_orderkey
  AND l_shipdate < date '{EPOCH + datetime.timedelta(days=JOIN_SHIP_HI)}'
  AND c_mktsegment <> 'MACHINERY'
GROUP BY c_mktsegment ORDER BY c_mktsegment"""

# no filter on the probe relation: every row of every batch is looked up
Q_JOIN_UNFILTERED = ("SELECT count(*) FROM orders_k, lineitem "
                     "WHERE l_orderkey = o_orderkey")

# TPC-H Q10's shape in small tables of its own (a few hundred thousand
# orders, so that the numpy arm can answer it too): four relations along
# a chain, seven group keys of which six are functions of the customer
# key, ORDER BY revenue DESC LIMIT 20
Q10_ORDERS = 300_000
Q10_DDL = (
    ("q10_orders", "o_orderkey", """CREATE TABLE q10_orders (
    o_orderkey bigint NOT NULL, o_custkey bigint, o_orderdate date)"""),
    ("q10_lineitem", "l_orderkey", """CREATE TABLE q10_lineitem (
    l_orderkey bigint NOT NULL, l_extendedprice decimal(12,2),
    l_discount decimal(12,2), l_returnflag text)"""),
    ("q10_customer", None, """CREATE TABLE q10_customer (
    c_custkey bigint NOT NULL, c_name text, c_address text,
    c_nationkey integer, c_phone text, c_acctbal decimal(15,2),
    c_comment text)"""),
    ("q10_nation", None, """CREATE TABLE q10_nation (
    n_nationkey integer NOT NULL, n_name text)"""))
Q10_DATE = datetime.date(1994, 1, 1)
Q_Q10 = f"""select c_custkey, c_name,
  sum(l_extendedprice * (1 - l_discount)) as revenue, c_acctbal, n_name,
  c_address, c_phone, c_comment
from q10_customer, q10_orders, q10_lineitem, q10_nation
where c_custkey = o_custkey and l_orderkey = o_orderkey
  and o_orderdate >= date '{Q10_DATE}'
  and o_orderdate < date '{Q10_DATE}' + interval '3' month
  and l_returnflag = 'R' and c_nationkey = n_nationkey
group by c_custkey, c_name, c_acctbal, c_phone, n_name, c_address, c_comment
order by revenue desc limit 20"""

# TPC-H Q5's shape in small tables of its own: six relations whose join
# graph has a cycle (customer - orders - lineitem - supplier - customer),
# the probe relation WITHOUT a filter and looked up in two tables a row
Q5_ORDERS = 300_000
Q5_DDL = (
    ("q5_orders", "o_orderkey", """CREATE TABLE q5_orders (
    o_orderkey bigint NOT NULL, o_custkey bigint, o_orderdate date)"""),
    ("q5_lineitem", "l_orderkey", """CREATE TABLE q5_lineitem (
    l_orderkey bigint NOT NULL, l_suppkey bigint,
    l_extendedprice decimal(12,2), l_discount decimal(12,2))"""),
    ("q5_customer", None, """CREATE TABLE q5_customer (
    c_custkey bigint NOT NULL, c_nationkey integer)"""),
    ("q5_supplier", None, """CREATE TABLE q5_supplier (
    s_suppkey bigint NOT NULL, s_nationkey integer)"""),
    ("q5_nation", None, """CREATE TABLE q5_nation (
    n_nationkey integer NOT NULL, n_name text, n_regionkey integer)"""),
    ("q5_region", None, """CREATE TABLE q5_region (
    r_regionkey integer NOT NULL, r_name text)"""))
Q5_DATE = datetime.date(1994, 1, 1)
Q5_REGIONS = ("AFRICA", "AMERICA", "ASIA", "EUROPE", "MIDDLE EAST")
Q_Q5 = f"""select n_name, sum(l_extendedprice * (1 - l_discount)) as revenue
from q5_customer, q5_orders, q5_lineitem, q5_supplier, q5_nation, q5_region
where c_custkey = o_custkey and l_orderkey = o_orderkey
  and l_suppkey = s_suppkey and c_nationkey = s_nationkey
  and s_nationkey = n_nationkey and n_regionkey = r_regionkey
  and r_name = 'ASIA' and o_orderdate >= date '{Q5_DATE}'
  and o_orderdate < date '{Q5_DATE}' + interval '1' year
group by n_name order by revenue desc"""

# float64 lanes: the TPU holds them as float32 pairs, so the hash
# fingerprint and the HLL hash of a float value take a path of their own
MEASURES_DDL = "CREATE TABLE measures (m_key bigint NOT NULL, x double precision)"
MEASURES_ROWS = 1_000_000
Q_FLOAT_KEYS = "SELECT x, count(*), sum(x) FROM measures GROUP BY x"
Q_FLOAT_HLL = "SELECT approx_count_distinct(x) FROM measures"
#: HLL with 128 registers: standard error 9%; three of them
HLL_RTOL = 0.3

Q_STDDEV = """SELECT l_returnflag, stddev(l_quantity) FROM lineitem
GROUP BY l_returnflag ORDER BY l_returnflag"""
Q_MEDIAN = """SELECT approx_percentile(0.5) WITHIN GROUP (ORDER BY l_quantity)
FROM lineitem"""
Q_ROUTER_COUNT = "SELECT count(*) FROM lineitem WHERE l_orderkey = $1"


class SmokeFailure(Exception):
    pass


def check(cond, message):
    if not cond:
        raise SmokeFailure(message)


def q6_sql(variant):
    y, dlo, dhi, qty = variant
    return Q6_TEMPLATE.format(y=y, y1=y + 1, dlo=f"0.{dlo:02d}",
                              dhi=f"0.{dhi:02d}", qty=qty)


def days(d: datetime.date) -> int:
    return (d - EPOCH).days


def dec(cents: int, scale: int) -> decimal.Decimal:
    return decimal.Decimal(int(cents)).scaleb(-scale)


def avg_dec(total: int, n: int, scale: int) -> decimal.Decimal:
    """SQL avg of a decimal(…, scale): exact quotient at scale + 6,
    rounded half up — in integers, so no float is involved."""
    q, r = divmod(total * 10 ** 6, n)
    if 2 * r >= n:
        q += 1
    return dec(q, scale + 6)


def order_custkey(orderkey, n_orders):
    """o_custkey as a function of the key (and c_mktsegment of the
    customer's key: ``SEGMENTS[key % 5]``)."""
    return (orderkey * 31 + 7) % (n_orders // 10 + 1)


def order_totalprice_cents(orderkey):
    """o_totalprice as a function of the key, so the join reference
    needs no lookup table."""
    return (orderkey * 7919 + 104_729) % 50_000_000 + 100


# ---------------------------------------------------------------- reference


class Reference:
    """Plain numpy answers, accumulated chunk by chunk at ingest.  All
    money columns are integer cents, so every sum here is exact."""

    def __init__(self, n_orders):
        self.o_qty = np.zeros(n_orders, np.int64)   # per order, in cents
        self.router_key = None            # the first row's l_orderkey
        self.q1 = {}                      # (rf, ls) -> running sums
        self.q6 = [0] * len(Q6_VARIANTS)
        self.q6_hits = [0] * len(Q6_VARIANTS)
        self.d_n = np.zeros(SHIP_DAYS, np.int64)
        self.d_qty = np.zeros(SHIP_DAYS, np.int64)
        self.d_max = np.zeros(SHIP_DAYS, np.int64)
        self.h_n = np.zeros(SHIP_DAYS * 99, np.int64)
        self.h_qty = np.zeros(SHIP_DAYS * 99, np.int64)
        self.h_min = np.full(SHIP_DAYS * 99, 1 << 62, np.int64)
        self.router_rows = []
        self.join = [0, 0, 0]             # count, sum qty, sum totalprice
        self.n_orders = n_orders
        self.join_seg = np.zeros((5, 3), np.int64)    # the same, a segment
        self.sd = [[0, 0, 0] for _ in range(3)]  # per flag: n, sum, sumsq
        self.qty_hist = np.zeros(5100, np.int64)

    def add(self, c):
        okey, qty, price = c["okey"], c["qty"], c["price"]
        disc, tax, rf, ls, ship = (c["disc"], c["tax"], c["rf"], c["ls"],
                                   c["ship"])
        if self.router_key is None:
            self.router_key = int(okey[0])
        # Q18's block: a chunk's sums are integers below 2**53
        self.o_qty += np.bincount(okey, weights=qty,
                                  minlength=self.o_qty.size).astype(np.int64)
        # Q1
        keep = ship <= Q1_SHIP_HI
        disc_price = price * (100 - disc)
        charge = disc_price * (100 + tax)
        for r in range(3):
            for s in range(2):
                m = keep & (rf == r) & (ls == s)
                n = int(m.sum())
                if not n:
                    continue
                acc = self.q1.setdefault((r, s), [0] * 6)
                for i, a in enumerate((qty, price, disc_price, charge, disc)):
                    acc[i] += int(a[m].sum())
                acc[5] += n
        # Q6 family
        for i, (y, dlo, dhi, q) in enumerate(Q6_VARIANTS):
            m = ((ship >= days(datetime.date(y, 1, 1)))
                 & (ship < days(datetime.date(y + 1, 1, 1)))
                 & (disc >= dlo) & (disc <= dhi) & (qty < q * 100))
            self.q6[i] += int((price[m] * disc[m]).sum())
            self.q6_hits[i] += int(m.sum())
        # GROUP BY l_shipdate: per-chunk float64 bincounts are exact (every
        # partial sum is an integer below 2**53)
        d = (ship - SHIP_LO).astype(np.int64)
        self.d_n += np.bincount(d, minlength=SHIP_DAYS)
        self.d_qty += np.bincount(d, weights=qty, minlength=SHIP_DAYS
                                  ).astype(np.int64)
        np.maximum.at(self.d_max, d, price)
        # GROUP BY l_shipdate, l_discount, l_tax
        g = d * 99 + disc * 9 + tax
        self.h_n += np.bincount(g, minlength=SHIP_DAYS * 99)
        self.h_qty += np.bincount(g, weights=qty, minlength=SHIP_DAYS * 99
                                  ).astype(np.int64)
        np.minimum.at(self.h_min, g, qty)
        # router key
        for i in np.nonzero(okey == self.router_key)[0]:
            self.router_rows.append((
                int(okey[i]), dec(qty[i], 2), dec(price[i], 2),
                EPOCH + datetime.timedelta(days=int(ship[i])),
                str(RETURNFLAGS[rf[i]])))
        # join: every l_orderkey has exactly one order
        m = ship < JOIN_SHIP_HI
        self.join[0] += int(m.sum())
        self.join[1] += int(qty[m].sum())
        total = order_totalprice_cents(okey[m])
        self.join[2] += int(total.sum())
        seg = order_custkey(okey[m], self.n_orders) % 5
        for k, w in enumerate((np.ones(int(m.sum()), np.int64), qty[m],
                               total)):
            np.add.at(self.join_seg[:, k], seg, w)
        # stddev(l_quantity) per returnflag, median of l_quantity
        for r in range(3):
            q = qty[rf == r]
            self.sd[r][0] += int(q.size)
            self.sd[r][1] += int(q.sum())
            self.sd[r][2] += int((q * q).sum())
        self.qty_hist += np.bincount(qty, minlength=5100)

    def q1_rows(self):
        out = []
        for (r, s) in sorted(self.q1):
            qty, price, dprice, charge, disc, n = self.q1[(r, s)]
            out.append((str(RETURNFLAGS[r]), str(LINESTATUSES[s]),
                        dec(qty, 2), dec(price, 2), dec(dprice, 4),
                        dec(charge, 6), avg_dec(qty, n, 2),
                        avg_dec(price, n, 2), avg_dec(disc, n, 2), n))
        return out

    def direct_rows(self):
        return {EPOCH + datetime.timedelta(days=SHIP_LO + int(i)):
                (int(self.d_n[i]), dec(self.d_qty[i], 2),
                 dec(self.d_max[i], 2))
                for i in np.nonzero(self.d_n)[0]}

    def hash_rows(self, with_min):
        out = {}
        for g in np.nonzero(self.h_n)[0]:
            d, rest = divmod(int(g), 99)
            disc, tax = divmod(rest, 9)
            row = (int(self.h_n[g]), dec(self.h_qty[g], 2))
            if with_min:
                row += (dec(self.h_min[g], 2),)
            out[(EPOCH + datetime.timedelta(days=SHIP_LO + d),
                 dec(disc, 2), dec(tax, 2))] = row
        return out

    def order_rows(self):
        """-> (threshold in cents, {order key: sum}) of the
        ``ORDERS_KEPT`` largest quantity sums (ties at the threshold
        left out, as HAVING ... > threshold leaves them)."""
        threshold = int(np.sort(self.o_qty)[-ORDERS_KEPT - 1])
        return threshold, {int(k): dec(self.o_qty[k], 2)
                           for k in np.nonzero(self.o_qty > threshold)[0]}

    def stddev_rows(self):
        out = []
        for r in range(3):
            n, s, ss = self.sd[r]
            # sample variance of cents, exactly, then to the column's unit
            var_c = (ss * n - s * s) / (n * (n - 1))
            out.append((str(RETURNFLAGS[r]), (var_c ** 0.5) / 100.0))
        return out

    def median_qty(self):
        cum = np.cumsum(self.qty_hist)
        return int(np.searchsorted(cum, (cum[-1] + 1) // 2)) / 100.0


def make_chunk(rng, n, n_orders):
    return {
        "okey": rng.integers(0, n_orders, n),
        "qty": rng.integers(100, 5100, n),
        "price": rng.integers(90_000, 10_500_000, n),
        "disc": rng.integers(0, 11, n),
        "tax": rng.integers(0, 9, n),
        "rf": rng.integers(0, 3, n),
        "ls": rng.integers(0, 2, n),
        "ship": (rng.integers(0, SHIP_DAYS, n) + SHIP_LO).astype(np.int32),
    }


def load_lineitem(cl, ref, rng, rows, n_orders):
    """Generate, reference and ingest lineitem chunk by chunk."""
    done = 0
    while done < rows:
        n = min(CHUNK_ROWS, rows - done)
        c = make_chunk(rng, n, n_orders)
        ref.add(c)
        cl.copy_from("lineitem", columns={
            "l_orderkey": c["okey"],
            "l_quantity": c["qty"] / 100.0,
            "l_extendedprice": c["price"] / 100.0,
            "l_discount": c["disc"] / 100.0,
            "l_tax": c["tax"] / 100.0,
            "l_returnflag": RETURNFLAGS[c["rf"]].tolist(),
            "l_linestatus": LINESTATUSES[c["ls"]].tolist(),
            "l_shipdate": c["ship"],
        })
        done += n


def load_orders(cl, n_orders, table="orders"):
    for start in range(0, n_orders, CHUNK_ROWS):
        key = np.arange(start, min(start + CHUNK_ROWS, n_orders),
                        dtype=np.int64)
        cl.copy_from(table, columns={
            "o_orderkey": key,
            "o_custkey": order_custkey(key, n_orders),
            "o_totalprice": order_totalprice_cents(key) / 100.0,
        })


def load_customer(cl, n_orders):
    key = np.arange(n_orders // 10 + 1, dtype=np.int64)
    cl.copy_from("customer", columns={
        "c_custkey": key,
        "c_mktsegment": [SEGMENTS[k % 5] for k in key.tolist()]})


# ------------------------------------------------------------------- legs


class Runner:
    """Runs one statement through ``cl.execute`` and hands back what the
    engine says about how it ran: the result, counter deltas, the wall
    time to rows on the host, and the spans of its exported trace."""

    def __init__(self, cl, trace_dir):
        self.cl = cl
        self.trace_dir = trace_dir
        self.legs = []

    def traces(self):
        return {n for n in os.listdir(self.trace_dir) if n.endswith(".json")}

    def run_many(self, statements):
        """Run ``[(sql, params)]`` concurrently, one client thread each
        (a single statement runs on the calling thread).  -> (results,
        counter deltas, wall time to the last row, trace events)."""
        before = self.traces()
        c0 = self.cl.counters.snapshot()
        results = [None] * len(statements)

        def client(i):
            sql, params = statements[i]
            results[i] = self.cl.execute(sql, params=params)

        t0 = time.perf_counter()
        if len(statements) == 1:
            client(0)
        else:
            threads = [threading.Thread(target=client, args=(i,))
                       for i in range(len(statements))]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
        elapsed = time.perf_counter() - t0
        c1 = self.cl.counters.snapshot()
        check(all(r is not None for r in results),
              "a client thread died (its traceback is above)")
        events = []
        for name in sorted(self.traces() - before):
            with open(os.path.join(self.trace_dir, name)) as fh:
                events += json.load(fh)["traceEvents"]
        delta = {k: c1[k] - c0.get(k, 0) for k in c1 if c1[k] != c0.get(k, 0)}
        return results, delta, elapsed, events

    def run(self, sql, params=None):
        (r,), delta, elapsed, events = self.run_many([(sql, params)])
        return r, delta, elapsed, events

    def record(self, name, slot, elapsed, delta, **extra):
        leg = {"leg": name, "ok": True, "kernel_slot": slot,
               "elapsed_s_not_a_metric": round(elapsed, 3),
               "compile_s": round(delta.get("kernel_compile_ms", 0) / 1e3, 3)}
        leg.update(extra)
        self.legs.append(leg)
        print("leg " + json.dumps(leg), flush=True)


def kernel_slots(events):
    return sorted({e["args"]["slot"] for e in events
                   if e.get("name") == "kernel"})


def scan_slot(n_dev):
    """The slot a scalar/direct aggregate scan compiles under."""
    return "mesh_run" if n_dev > 1 else "jit_fused"


def check_groups(leg, got, want, n_rows):
    diff = next(((k, got.get(k), want[k]) for k in want
                 if got.get(k) != want[k]), None)
    check(n_rows == len(want) and got == want,
          f"{leg}: {n_rows} groups vs {len(want)}; first diff {diff}")


def leg_q1_cold(run, ref, n_dev):
    r, d, el, ev = run.run(Q1)
    slot = scan_slot(n_dev)
    check(r.explain["strategy"] == "direct", f"Q1 strategy {r.explain}")
    check(slot in kernel_slots(ev), f"Q1 cold: kernel slots {kernel_slots(ev)}")
    check(d.get("device_cache_hits", 0) == 0, "Q1 cold hit the device cache")
    h2d = r.explain["pipeline"].get("h2d_bytes", 0)
    check(h2d > 0, f"Q1 cold: no H2D bytes {r.explain['pipeline']}")
    # every loop folds its rounds into an accumulator on the device
    check(d.get("fused_dispatches", 0) > 0
          and r.explain["pipeline"].get("fused_dispatches", 0) > 0,
          f"Q1 cold: no fused dispatch {d}")
    check(r.rows == ref.q1_rows(),
          f"Q1 cold answer\n got {r.rows}\nwant {ref.q1_rows()}")
    run.record("1 cold Q1 (streaming)", slot, el, d, h2d_bytes=h2d,
               dispatches=len([e for e in ev if e["name"] == "device_round"]))
    return h2d


def leg_q1_warm(run, ref, n_dev):
    r, d, el, _ = run.run(Q1)
    check(d.get("device_cache_hits", 0) > 0,
          f"Q1 warm: no device cache hit {d}")
    check(d.get("kernel_compile_ms", 0) == 0,
          f"Q1 warm compiled: {d.get('kernel_compile_ms')} ms")
    check(d.get("fused_dispatches", 0) > 0, "Q1 warm: no fused dispatch")
    check(r.rows == ref.q1_rows(), f"Q1 warm answer {r.rows}")
    run.record("2 warm Q1 (HBM-resident)", scan_slot(n_dev), el, d)


def leg_q6(run, ref, n_dev):
    r, d, el, ev = run.run(q6_sql(Q6_VARIANTS[0]))
    slot = scan_slot(n_dev)
    check(r.explain["strategy"] == "scalar", f"Q6 strategy {r.explain}")
    check(slot in kernel_slots(ev), f"Q6: kernel slots {kernel_slots(ev)}")
    check(ref.q6_hits[0] > 0, "Q6 reference matched no row")
    check(r.rows == [(dec(ref.q6[0], 4),)],
          f"Q6 answer {r.rows} want {dec(ref.q6[0], 4)}")
    run.record("3 Q6 (scalar)", slot, el, d)


def leg_q6_coalesced(run, ref):
    """Two literal variants of Q6 from two threads inside one window:
    one vmap-lifted dispatch serves both (single-device by design)."""
    cl = run.cl
    cl.execute("SET citus.megabatch_max_size = 2")
    cl.execute("SET citus.megabatch_window_ms = 10000")
    results, delta, elapsed, events = run.run_many(
        [(q6_sql(Q6_VARIANTS[i]), None) for i in (1, 2)])
    cl.execute("SET citus.megabatch_window_ms = 0")
    cl.execute("SET citus.megabatch_max_size = 32")
    check(delta.get("megabatch_batches", 0) == 1
          and delta.get("megabatch_queries", 0) == 2,
          f"Q6 pair did not coalesce: {delta}")
    check("batched:jit_fused" in kernel_slots(events),
          f"coalesced pair: kernel slots {kernel_slots(events)}")
    for i, r in zip((1, 2), results):
        check(ref.q6_hits[i] > 0, "Q6 variant reference matched no row")
        check(r.rows == [(dec(ref.q6[i], 4),)],
              f"coalesced Q6 variant {i}: {r.rows} want {dec(ref.q6[i], 4)}")
    run.record("3b Q6 literal pair (coalesced)", "batched:jit_fused",
               elapsed, delta)


def leg_direct(run, ref, n_dev):
    r, d, el, ev = run.run(Q_DIRECT)
    slot = scan_slot(n_dev)
    check(r.explain["strategy"] == "direct", f"direct leg: {r.explain}")
    check(slot in kernel_slots(ev), f"direct leg: slots {kernel_slots(ev)}")
    want = ref.direct_rows()
    check_groups("direct leg", {row[0]: tuple(row[1:]) for row in r.rows},
                 want, len(r.rows))
    run.record("4 GROUP BY l_shipdate (direct, one-hot)", slot, el, d,
               groups=len(want))


@contextlib.contextmanager
def memory_growth(devices):
    """Which devices the body allocates on: every device's
    ``bytes_in_use`` is polled from a thread while the body runs, and
    ``["per_device"]`` of what is yielded becomes each device's highest
    reading above the one taken before the body."""
    out = {"per_device": "memory_stats unavailable"}
    if any(dv.memory_stats() is None for dv in devices):   # the CPU backend
        yield out
        return
    base = [int(dv.memory_stats()["bytes_in_use"]) for dv in devices]
    peak = list(base)
    stop = threading.Event()

    def poll():
        while not stop.wait(0.05):
            for i, dv in enumerate(devices):
                peak[i] = max(peak[i], int(dv.memory_stats()["bytes_in_use"]))

    t = threading.Thread(target=poll)
    t.start()
    try:
        yield out
    finally:
        stop.set()
        t.join()
    out["per_device"] = [p - b for p, b in zip(peak, base)]


def leg_hash(run, ref, devices, rows):
    cl = run.cl
    cl.execute("SET citus.hash_agg_slots = auto")
    with memory_growth(devices) as grew:
        r, d, el, ev = run.run(Q_HASH)
    check(r.explain["strategy"] == "hash_host", f"hash leg: {r.explain}")
    check("jit_hash_fused" in kernel_slots(ev),
          f"hash leg: slots {kernel_slots(ev)}")
    check(d.get("hash_fused_dispatches", 0) > 0, f"hash leg: no dispatch {d}")
    want = ref.hash_rows(with_min=True)
    check_groups("hash leg", {tuple(row[:3]): tuple(row[3:]) for row in r.rows},
                 want, len(r.rows))
    pl = r.explain["pipeline"]
    # the groups cannot outnumber the rows nor the keys' domain: the
    # table takes the smaller bound (executor.py _hash_slots)
    # -- a table a device, each bounded by its fullest device's rows
    tables = pl.get("hash_tables", 1)
    rows = pl.get("hash_rows_in_max_device", rows)
    by_rows = max(1024, 1 << (rows - 1).bit_length())
    by_domain = 1 << (2 * HASH_DOMAIN_SLOTS - 1).bit_length()
    bound = ((tables * by_domain, "key domain") if by_domain < by_rows
             else (tables * by_rows, "row count"))
    check(tables == len(devices), f"hash leg: {tables} tables on "
          f"{len(devices)} devices")
    check((pl.get("hash_slots"), pl.get("hash_slots_from")) == bound,
          f"hash leg: slots {pl.get('hash_slots')} from "
          f"{pl.get('hash_slots_from')}, want {bound}")
    run.record("5 GROUP BY l_shipdate, l_discount, l_tax with a min (hash)",
               "jit_hash_fused", el, d, groups=len(want),
               hash_slots=pl.get("hash_slots"),
               hash_slots_from=pl.get("hash_slots_from"),
               hash_occupancy_pct=pl.get("hash_occupancy_pct"),
               hash_spill_rows=d.get("hash_spill_rows", 0),
               bytes_in_use_growth_per_device=grew["per_device"])


def leg_hash_every_device(run, ref, devices):
    """Q18's block on every visible device: one hash table a device
    (the counter says so, and so does each device's memory), apart on
    the distribution column, every kept order's sum equal."""
    cl = run.cl
    threshold, want = ref.order_rows()
    # the smoke's order keys are dense: keep the statement off the
    # direct table, as the sparse keys of a real lineitem do
    cl.execute("SET citus.direct_gid_limit = 16")
    try:
        with memory_growth(devices) as grew:
            r, d, el, ev = run.run(Q_ORDERS.format(
                threshold=dec(threshold, 2)))
    finally:
        cl.execute("SET citus.direct_gid_limit = auto")
    pl = r.explain["pipeline"]
    n_dev = len(devices)
    check(r.explain["strategy"] == "hash_host", f"orders leg: {r.explain}")
    check("jit_hash_fused" in kernel_slots(ev),
          f"orders leg: slots {kernel_slots(ev)}")
    check(d.get("hash_tables") == pl.get("hash_tables") == n_dev,
          f"orders leg: {d.get('hash_tables')} tables on {n_dev} devices")
    check(d.get("hash_tables_merged", 0) == 0 and (
        n_dev == 1 or pl.get("hash_disjoint_on") == "l_orderkey"),
        f"orders leg: tables not apart: {pl}")
    if isinstance(grew["per_device"], list):
        check(all(g > 0 for g in grew["per_device"]),
              f"orders leg: bytes_in_use did not grow on every device: "
              f"{grew['per_device']}")
    got = {row[0]: row[1] for row in r.rows}
    check_groups("orders leg", got, want, len(r.rows))
    run.record("5f Q18's block, a hash table on every device",
               "jit_hash_fused", el, d, groups=len(want),
               hash_tables=pl.get("hash_tables"),
               hash_slots=pl.get("hash_slots"),
               hash_slots_from=pl.get("hash_slots_from"),
               hash_disjoint_on=pl.get("hash_disjoint_on"),
               hash_rows_in_max_device=pl.get("hash_rows_in_max_device"),
               hash_having_on_device=bool(pl.get("hash_having_on_device")),
               hash_entries_fetched=d.get("hash_entries_fetched", 0),
               hash_spill_rows=d.get("hash_spill_rows", 0),
               bytes_in_use_growth_per_device=grew["per_device"])


def leg_product(run, ref, n_dev, rows):
    """The same keys over a count and an int64 sum: the route follows
    from the row count, and ``citus.direct_gid_limit`` bounds it.  Both
    routes answer the statement, each a second time on warm kernels, so
    the report holds the product beside the hash kernel at these slots."""
    from citus_tpu.planner.physical import DENSE_ROWS_PER_SLOT
    cl = run.cl
    want = ref.hash_rows(with_min=False)
    for leg, limit, name in (("5d", "auto", "the plan's route"),
                             ("5e", "65536", "direct_gid_limit 65536")):
        cl.execute(f"SET citus.direct_gid_limit = {limit}")
        try:
            _, cold, _, ev = run.run(Q_PRODUCT)     # builds the kernel
            r, d, el, _ = run.run(Q_PRODUCT)
        finally:
            cl.execute("SET citus.direct_gid_limit = auto")
        pl = r.explain["pipeline"]
        direct = (limit == "auto"
                  and rows >= DENSE_ROWS_PER_SLOT * HASH_DOMAIN_SLOTS)
        slot = scan_slot(n_dev) if direct else "jit_hash_fused"
        check(r.explain["strategy"] == ("direct" if direct else "hash_host"),
              f"product leg, {name}: {r.explain}")
        check(slot in kernel_slots(ev),
              f"product leg, {name}: slots {kernel_slots(ev)}")
        if direct:
            check(pl.get("direct_groups") == HASH_DOMAIN_SLOTS,
                  f"product leg: {pl}")
        check_groups(f"product leg, {name}",
                     {tuple(row[:3]): tuple(row[3:]) for row in r.rows},
                     want, len(r.rows))
        run.record(f"{leg} the same keys, count and sum, warm "
                   f"({'direct, product' if direct else 'hash'}; {name})",
                   slot, el, dict(d, kernel_compile_ms=cold.get(
                       "kernel_compile_ms", 0)), groups=len(want),
                   direct_groups=pl.get("direct_groups") if direct else None,
                   hash_slots=None if direct else pl.get("hash_slots"),
                   group_rows_in=pl.get("group_rows_in"))


def leg_float_lanes(run, rng, shards, n_dev, rows):
    """GROUP BY a float64 key (hash mode: floats never take the direct
    path) and an HLL over the same column, on a small side table.  The
    values are halves, which a float32 pair holds exactly, so the keys
    must come back equal; NaN and -0.0 ride along as SQL groups them."""
    cl = run.cl
    cl.execute(MEASURES_DDL)
    cl.execute(f"SELECT create_distributed_table('measures', 'm_key', {shards})")
    x = rng.integers(0, 1000, rows) / 2.0
    x[::1000] = np.nan
    x[1::1000] = -0.0
    cl.copy_from("measures", columns={
        "m_key": np.arange(rows, dtype=np.int64), "x": x})
    want = {}
    vals, counts = np.unique(x[~np.isnan(x)] + 0.0, return_counts=True)
    for v, n in zip(vals, counts):
        want[float(v)] = (int(n), float(v) * int(n))
    want["nan"] = (int(np.isnan(x).sum()), None)

    r, d, el, ev = run.run(Q_FLOAT_KEYS)
    check(r.explain["strategy"] == "hash_host", f"float keys: {r.explain}")
    check("jit_hash_fused" in kernel_slots(ev),
          f"float keys: slots {kernel_slots(ev)}")
    got = {("nan" if row[0] != row[0] else row[0]): row[1:] for row in r.rows}
    check(sorted(got, key=str) == sorted(want, key=str),
          f"float keys: {len(got)} groups vs {len(want)}")
    for k, (n, total) in want.items():
        check(got[k][0] == n, f"float key {k}: count {got[k][0]} want {n}")
        if total is not None:
            check(abs(got[k][1] - total) <= F64_RTOL * abs(total),
                  f"float key {k}: sum {got[k][1]} want {total}")
    run.record("5b GROUP BY a float64 key (hash, float lanes)",
               "jit_hash_fused", el, d, groups=len(want), rtol=F64_RTOL)

    r, d, el, ev = run.run(Q_FLOAT_HLL)
    slot = scan_slot(n_dev)
    check(slot in kernel_slots(ev), f"float HLL: slots {kernel_slots(ev)}")
    check(close(r.rows[0][0], len(vals) + 1, HLL_RTOL),
          f"approx_count_distinct(x) {r.rows} want about {len(vals) + 1}")
    run.record("5c approx_count_distinct over float64 (HLL, float lanes)",
               slot, el, d, rtol=HLL_RTOL, got=int(r.rows[0][0]),
               want=len(vals) + 1)


def leg_router(run, ref):
    r, d, el, ev = run.run(Q_ROUTER, params=[ref.router_key])
    check(r.explain["strategy"] == "projection" and r.explain["router"]
          and r.explain["shards"] == 1, f"router leg: {r.explain}")
    check("jit_filter" in kernel_slots(ev),
          f"router leg: slots {kernel_slots(ev)}")
    check(ref.router_rows and sorted(r.rows) == sorted(ref.router_rows),
          f"router leg: {sorted(r.rows)} want {sorted(ref.router_rows)}")
    run.record("6 projection WHERE l_orderkey = $1 (router)", "jit_filter",
               el, d, rows=len(r.rows))


def close(got, want, rtol):
    return got is not None and abs(float(got) - want) <= rtol * abs(want)


def leg_join_colocated(run, ref):
    """The colocated many-to-one join on the device, on one chip and on
    several alike: customer built once, orders once a shard pair, every
    lineitem batch probed, the survivors aggregated in the device hash
    table; no statement falls back to the host path."""
    r, d, el, ev = run.run(Q_JOIN_COLOCATED)
    check(r.explain["strategy"] == "join:colocated"
          and r.explain.get("join", {}).get("on") == "device",
          f"colocated join: {r.explain}")
    check(d.get("join_host_fallbacks", 0) == 0
          and d.get("join_rows_probed", 0) > 0, f"colocated join: {d}")
    check({"jit_join_probe", "jit_hash_fused"} <= set(kernel_slots(ev)),
          f"colocated join: slots {kernel_slots(ev)}")
    want = [(SEGMENTS[s], int(n), dec(q, 2), dec(t, 2))
            for s, (n, q, t) in enumerate(ref.join_seg.tolist())
            if SEGMENTS[s] != "MACHINERY" and n]
    check(r.rows == want, f"colocated join answer {r.rows} want {want}")
    j = r.explain["join"]
    # the probe looks up the rows lineitem's own filter keeps, not the
    # batch: a chunk loop that looked everything up would read equal
    check(0 < j["rows_looked_up"] < j["rows_probed"]
          and j["rows_out"] <= j["rows_looked_up"],
          f"colocated join: looked up {j['rows_looked_up']} of "
          f"{j['rows_probed']} probed, {j['rows_out']} out")
    # ... and the whole bucket where the probe relation has no filter
    whole = run.cl.execute(Q_JOIN_UNFILTERED)
    w = whole.explain.get("join", {})
    check(w.get("on") == "device"
          and w["rows_looked_up"] == w["rows_probed"] > 0
          and whole.rows == [(int(ref.d_n.sum()),)],
          f"unfiltered join: {whole.rows} {w}")
    run.record("7c colocated join on the device (customer, orders_k, "
               "lineitem: build, probe, packed aggregate)",
               "jit_join_probe", el, d, rows_probed=j["rows_probed"],
               rows_looked_up=j["rows_looked_up"],
               rows_matched=j["rows_matched"], rows_out=j["rows_out"],
               rows_built=j["rows_built"], table_bytes=j["table_bytes"],
               overflow_rounds=j["overflow_rounds"],
               unfiltered_rows_looked_up=w["rows_looked_up"])


def leg_join_q10(run, rng, shards, n_orders):
    """TPC-H Q10's shape on the device: nation and customer built once,
    orders once a shard pair, the groups on the ONE key lane the join
    proves decides them (1 of 7), ORDER BY ... LIMIT 20 cut on the chip
    (``jit_hash_top``) and the six other keys looked up in customer's
    resident table for the groups that come home (``jit_join_lookup``);
    the answer is the numpy arm's and a plain numpy join's."""
    cl = run.cl
    orders = min(Q10_ORDERS, max(n_orders, 20_000))
    customers = max(orders // 10, 5000)
    for table, column, ddl in Q10_DDL:
        cl.execute(ddl)
        cl.execute(f"SELECT create_distributed_table('{table}', '{column}', "
                   f"{shards})" if column
                   else f"SELECT create_reference_table('{table}')")
    nations = ["ALGERIA", "ARGENTINA", "BRAZIL", "CANADA", "EGYPT"]
    c_key = np.arange(1, customers + 1)
    c_nation = rng.integers(0, len(nations), customers)
    c_acctbal = rng.integers(-99_999, 1_000_000, customers)
    word = lambda k, salt: f"{salt}{(k * 2654435761) % 4294967291:010d}"
    text = {c: [word(k, c[2]) for k in c_key.tolist()]
            for c in ("c_address", "c_phone", "c_comment")}
    cl.copy_from("q10_nation", columns={
        "n_nationkey": np.arange(len(nations), dtype=np.int32),
        "n_name": nations})
    cl.copy_from("q10_customer", columns=dict(
        text, c_custkey=c_key,
        c_name=[f"Customer#{k:09d}" for k in c_key.tolist()],
        c_nationkey=c_nation.astype(np.int32), c_acctbal=c_acctbal / 100.0))
    o_key = np.arange(orders, dtype=np.int64) * 4 + 1
    o_cust = rng.integers(1, customers + customers // 3, orders)
    o_date = days(Q10_DATE) + rng.integers(-200, 300, orders)
    cl.copy_from("q10_orders", columns={
        "o_orderkey": o_key, "o_custkey": o_cust,
        "o_orderdate": o_date.astype(np.int32)})
    at = np.repeat(np.arange(orders), rng.integers(1, 8, orders))
    price = rng.integers(100, 10_000_000, at.size)
    disc = rng.integers(0, 11, at.size)
    flag = rng.integers(0, 3, at.size)
    cl.copy_from("q10_lineitem", columns={
        "l_orderkey": o_key[at], "l_extendedprice": price / 100.0,
        "l_discount": disc / 100.0,
        "l_returnflag": RETURNFLAGS[flag].tolist()})

    r, d, el, ev = run.run(Q_Q10)
    j = r.explain.get("join", {})
    check(r.explain["strategy"] == "join:colocated" and j.get("on") == "device"
          and d.get("join_host_fallbacks", 0) == 0, f"Q10: {r.explain}")
    check((j["group_keys"], j["group_key_lanes"],
           j["group_keys_dependent"]) == (7, 1, 6),
          f"Q10 groups on {j['group_key_lanes']} of {j['group_keys']} keys, "
          f"{j['group_keys_dependent']} dependent")
    check(isinstance(j["top"], dict) and j["top"]["rows"] == 20
          and j["top"]["entries"] < j["agg_slots"]
          and d.get("group_top_cuts") == 1, f"Q10 was not cut: {j['top']}")
    check({"jit_join_probe", "jit_hash_fused", "jit_hash_top"}
          <= set(kernel_slots(ev)), f"Q10: slots {kernel_slots(ev)}")
    # the plain join, in numpy: the R lines of the quarter's orders of a
    # customer that exists
    end = days(datetime.date(Q10_DATE.year, Q10_DATE.month + 3, 1))
    keep = (flag == 2) & (o_date[at] >= days(Q10_DATE)) \
        & (o_date[at] < end) & (o_cust[at] <= customers)
    revenue = np.zeros(customers + 1, np.int64)
    np.add.at(revenue, o_cust[at][keep], (price * (100 - disc))[keep])
    groups = np.unique(o_cust[at][keep])
    first = groups[np.lexsort((groups, -revenue[groups]))][:21]
    check(len(set(revenue[first].tolist())) == first.size,
          "Q10: two of the first 21 rows tie on revenue; take another seed")
    want = [(int(k), f"Customer#{k:09d}", dec(revenue[k], 4),
             dec(c_acctbal[k - 1], 2), nations[c_nation[k - 1]],
             text["c_address"][k - 1], text["c_phone"][k - 1],
             text["c_comment"][k - 1]) for k in first[:20].tolist()]
    check(r.rows == want, f"Q10 answer {r.rows[:2]} want {want[:2]}")
    check(j["groups"] == groups.size, f"Q10 groups {j['groups']}")
    cl.execute("SET citus.task_executor_backend = 'cpu'")
    try:
        oracle = cl.execute(Q_Q10)
    finally:
        cl.execute("SET citus.task_executor_backend = 'tpu'")
    check(oracle.rows == r.rows, "Q10: the numpy arm answers otherwise")
    run.record("7f Q10 on the device (nation, customer, orders, lineitem: "
               "1 of 7 keys grouped, top 20 cut on the chip, 6 keys looked "
               "up)", "jit_hash_top", el, d, groups=j["groups"],
               agg_slots=j["agg_slots"], entries_fetched=j["top"]["entries"],
               groups_looked_up=j["groups_looked_up"],
               rows_probed=j["rows_probed"], rows_out=j["rows_out"])


def leg_join_q5(run, rng, shards, n_orders):
    """TPC-H Q5's shape on the device: the join GRAPH planned as a tree
    rooted at the lineitems (orders a table a shard pair with the
    customers' nation riding through it; supplier, under it nation and
    region, a table a query) and ONE cycle filter, ``c_nationkey =
    s_nationkey``, decided over the probe's blocks; the probe relation
    has no filter, so every row is looked up, in two tables; the answer
    is the numpy arm's and a plain numpy join's."""
    cl = run.cl
    orders = min(Q5_ORDERS, max(n_orders, 20_000))
    customers, suppliers = max(orders // 10, 2000), max(orders // 150, 200)
    for table, column, ddl in Q5_DDL:
        cl.execute(ddl)
        cl.execute(f"SELECT create_distributed_table('{table}', '{column}', "
                   f"{shards})" if column
                   else f"SELECT create_reference_table('{table}')")
    n_region = np.arange(25) % 5
    c_nation = rng.integers(0, 25, customers)
    s_nation = rng.integers(0, 25, suppliers)
    cl.copy_from("q5_region", columns={
        "r_regionkey": np.arange(5, dtype=np.int32),
        "r_name": list(Q5_REGIONS)})
    cl.copy_from("q5_nation", columns={
        "n_nationkey": np.arange(25, dtype=np.int32),
        "n_name": [f"NATION{n:02d}" for n in range(25)],
        "n_regionkey": n_region.astype(np.int32)})
    cl.copy_from("q5_customer", columns={
        "c_custkey": np.arange(1, customers + 1),
        "c_nationkey": c_nation.astype(np.int32)})
    cl.copy_from("q5_supplier", columns={
        "s_suppkey": np.arange(1, suppliers + 1),
        "s_nationkey": s_nation.astype(np.int32)})
    o_key = np.arange(orders, dtype=np.int64) * 4 + 1
    o_cust = rng.integers(1, customers + 1, orders)
    o_date = days(Q5_DATE) + rng.integers(-400, 800, orders)
    cl.copy_from("q5_orders", columns={
        "o_orderkey": o_key, "o_custkey": o_cust,
        "o_orderdate": o_date.astype(np.int32)})
    at = np.repeat(np.arange(orders), rng.integers(1, 8, orders))
    supp = rng.integers(1, suppliers + 1, at.size)
    price = rng.integers(100, 10_000_000, at.size)
    disc = rng.integers(0, 11, at.size)
    cl.copy_from("q5_lineitem", columns={
        "l_orderkey": o_key[at], "l_suppkey": supp,
        "l_extendedprice": price / 100.0, "l_discount": disc / 100.0})

    r, d, el, ev = run.run(Q_Q5)
    j = r.explain.get("join", {})
    check(r.explain["strategy"] == "join:colocated" and j.get("on") == "device"
          and d.get("join_host_fallbacks", 0) == 0, f"Q5: {r.explain}")
    check(j["tree"] == {
        "q5_orders": "q5_lineitem", "q5_supplier": "q5_lineitem",
        "q5_customer": "q5_orders", "q5_nation": "q5_supplier",
        "q5_region": "q5_nation"}, f"Q5 tree: {j['tree']}")
    check(len(j["cycle_filters"]) == 1 and j["probe_children"] == 2
          and d.get("join_cycle_filters") == 1,
          f"Q5 cycle filters: {j['cycle_filters']}")
    check(j["rows_looked_up"] == j["rows_probed"],
          f"Q5: an unfiltered probe looked up {j['rows_looked_up']} of "
          f"{j['rows_probed']} rows")
    check({"jit_join_probe", "jit_hash_fused"} <= set(kernel_slots(ev)),
          f"Q5: slots {kernel_slots(ev)}")
    # the plain join, in numpy
    year = (o_date[at] >= days(Q5_DATE)) & (o_date[at] < days(
        datetime.date(Q5_DATE.year + 1, 1, 1)))
    of_supp = s_nation[supp - 1]
    both = year & (n_region[of_supp] == Q5_REGIONS.index("ASIA"))
    keep = both & (c_nation[o_cust[at] - 1] == of_supp)
    revenue = np.zeros(25, np.int64)
    np.add.at(revenue, of_supp[keep], (price * (100 - disc))[keep])
    nations = np.flatnonzero(np.bincount(of_supp[keep], minlength=25))
    nations = nations[np.argsort(-revenue[nations])]
    check(len(set(revenue[nations].tolist())) == nations.size,
          "Q5: two nations tie on revenue; take another seed")
    want = [(f"NATION{n:02d}", dec(revenue[n], 4)) for n in nations.tolist()]
    check(r.rows == want, f"Q5 answer {r.rows[:2]} want {want[:2]}")
    check((j["cycle_rows_in"], j["cycle_rows_kept"])
          == (int(both.sum()), int(keep.sum())),
          f"Q5 cycle filter saw {j['cycle_rows_in']}, kept "
          f"{j['cycle_rows_kept']}; the plain join {int(both.sum())}, "
          f"{int(keep.sum())}")
    cl.execute("SET citus.task_executor_backend = 'cpu'")
    try:
        oracle = cl.execute(Q_Q5)
    finally:
        cl.execute("SET citus.task_executor_backend = 'tpu'")
    check(oracle.rows == r.rows, "Q5: the numpy arm answers otherwise")
    run.record("7g Q5 on the device (a join graph with a cycle: a tree of "
               "five builds and one cycle filter; an unfiltered probe, two "
               "lookups a row)", "jit_join_probe", el, d,
               groups=j["groups"], rows_probed=j["rows_probed"],
               rows_matched=j["rows_matched"],
               overflow_rounds=j["overflow_rounds"],
               cycle_rows_in=j["cycle_rows_in"],
               cycle_rows_kept=j["cycle_rows_kept"])


def leg_join_repartition(run, ref, devices):
    """The single-hash repartition join on the device, on every device
    the machine has (TPC-H Q12's shape: ``orders`` is distributed on the
    customer key, the join is on the order key, ``lineitem``'s filter
    keeps a few rows): every order is exchanged to the device that owns
    its key's ``lineitem`` shard (one ``all_to_all`` a lane; skipped on
    one device) and built into that device's one lookup table, then each
    device probes its own ``lineitem`` shards.  The answer is the
    reference's and the host path's."""
    n_dev = len(devices)
    with memory_growth(devices) as grew:
        r, d, el, ev = run.run(Q_JOIN)
    j = r.explain.get("join", {})
    check(r.explain["strategy"] == "join:repartition"
          and j.get("on") == "device", f"repartition join: {r.explain}")
    check(r.explain.get("shuffle") == (
        "all_to_all:device" if n_dev > 1 else "local"),
        f"repartition join: shuffle {r.explain.get('shuffle')}")
    check(d.get("join_host_fallbacks", 0) == 0, f"repartition join: {d}")
    built = j["rows_built"]
    check(built == ref.n_orders, f"repartition join: built {built} of "
                                 f"{ref.n_orders} orders")
    check(d.get("join_rows_exchanged", 0) == (built if n_dev > 1 else 0),
          f"repartition join: exchanged {d.get('join_rows_exchanged')} "
          f"of {built} build rows")
    slots = set(kernel_slots(ev))
    check({"jit_join_probe", "jit_hash_fused"} <= slots and (
        n_dev == 1 or "jit_join_exchange" in slots),
        f"repartition join: slots {sorted(slots)}")
    if isinstance(grew["per_device"], list):
        check(all(g > 0 for g in grew["per_device"]),
              f"repartition join: bytes_in_use did not grow on every "
              f"device: {grew['per_device']}")
    want = [(ref.join[0], dec(ref.join[1], 2), dec(ref.join[2], 2))]
    check(r.rows == want, f"repartition join answer {r.rows} want {want}")
    run.cl.execute("SET citus.task_executor_backend = 'cpu'")
    try:
        host = run.cl.execute(Q_JOIN)
    finally:
        run.cl.execute("SET citus.task_executor_backend = 'tpu'")
    check(host.rows == r.rows, f"repartition join: host path {host.rows}")
    line = next(l for (l,) in run.cl.execute(
        "EXPLAIN ANALYZE " + Q_JOIN).rows if l.lstrip().startswith("Join:"))
    print("explain " + line.strip(), flush=True)
    x = j["exchange"]
    run.record("7a single-hash repartition join on the device (orders "
               "exchanged on o_orderkey, build and probe per device)",
               "jit_join_exchange" if n_dev > 1 else "jit_join_probe", el, d,
               shuffle=r.explain["shuffle"], pairs=ref.join[0],
               rows_exchanged=x["rows"], bytes_exchanged=x["bytes"],
               rows_received_max_device=x["rows_received_max_device"],
               exchange_overflow_rounds=x["overflow_rounds"],
               rows_built=built, rows_probed=j["rows_probed"],
               rows_looked_up=j["rows_looked_up"], rows_out=j["rows_out"],
               table_bytes=j["table_bytes"],
               bytes_in_use_growth_per_device=grew["per_device"])


def leg_mesh(run, ref, devices, q1_h2d_bytes):
    """More than one device: where the cached Q1 stack lives, then the
    mesh aggregates the single-device legs do not reach."""
    report = {}
    stats = [dv.memory_stats() for dv in devices]
    if all(s is not None for s in stats):
        in_use = [int(s["bytes_in_use"]) for s in stats]
        share = q1_h2d_bytes // len(devices)
        check(all(b >= share // 2 for b in in_use),
              f"cached stack is not spread over the devices: {in_use} "
              f"bytes in use, expected about {share} each")
        report["bytes_in_use_per_device"] = in_use
    else:
        report["bytes_in_use_per_device"] = "memory_stats unavailable"

    r, d, el, ev = run.run(Q_STDDEV)
    want = ref.stddev_rows()
    check(len(r.rows) == 3 and all(
        g[0] == w[0] and close(g[1], w[1], F64_RTOL)
        for g, w in zip(r.rows, want)), f"stddev {r.rows} want {want}")
    check("mesh_run" in kernel_slots(ev), f"stddev: {kernel_slots(ev)}")
    run.record("7b stddev(l_quantity) (f64 partials over psum)", "mesh_run",
               el, d, rtol=F64_RTOL)

    r, d, el, ev = run.run(Q_MEDIAN)
    want = ref.median_qty()
    check(close(r.rows[0][0], want, SKETCH_RTOL),
          f"approx_percentile {r.rows} want {want} within {SKETCH_RTOL}")
    check("mesh_run" in kernel_slots(ev), f"sketch: {kernel_slots(ev)}")
    run.record("7d approx_percentile (DDSketch buckets over psum)",
               "mesh_run", el, d, rtol=SKETCH_RTOL, got=float(r.rows[0][0]),
               want=want)

    r, d, el, ev = run.run(Q_ROUTER_COUNT, params=[ref.router_key])
    check(r.explain["router"] is True
          and r.rows == [(len(ref.router_rows),)],
          f"routed count {r.rows} {r.explain}")
    check("jit_fused" in kernel_slots(ev), f"routed count: {kernel_slots(ev)}")
    run.record("7e routed count(*) WHERE l_orderkey = $1", "jit_fused", el, d)
    return report


# ------------------------------------------------------------------- main


def build_native():
    """Build the native columnar IO library from its tracked sources
    before JAX is imported, so this process starts no child once it can
    hold the chip."""
    subprocess.run(["make", "-C", os.path.join(HERE, "citus_tpu", "native")],
                   check=True, stdout=subprocess.DEVNULL)


def cache_entries(path):
    return len(os.listdir(path)) if os.path.isdir(path) else 0


def versions():
    import importlib.metadata as md
    out = {}
    for pkg in ("jax", "jaxlib", "libtpu", "numpy"):
        try:
            out[pkg] = md.version(pkg)
        except md.PackageNotFoundError:
            out[pkg] = "not installed"
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rows", type=int, default=SF10_LINEITEM_ROWS,
                    help="lineitem rows (default: TPC-H SF10)")
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--out", default=os.path.join(HERE, "chiprun_out",
                                                  "chip_smoke"))
    ap.add_argument("--rehearse-on-cpu", action="store_true",
                    help="debug the script on the CPU backend; the report "
                         "is stamped as a rehearsal and proves nothing "
                         "about the chip")
    args = ap.parse_args()

    if args.rehearse_on_cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
    build_native()
    t_start = time.perf_counter()

    import jax
    import citus_tpu as ct
    from citus_tpu.native import get_lib

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu" and not args.rehearse_on_cpu:
        print(f"chip_smoke: no TPU found (jax.devices() -> {devices}); "
              "refusing to run on another platform", file=sys.stderr)
        return 2
    n_dev = len(devices)
    shards = SHARDS_PER_DEVICE * n_dev
    n_orders = max(args.rows // 4, 1)

    trace_dir = os.path.join(args.out, "traces")
    shutil.rmtree(args.out, ignore_errors=True)
    os.makedirs(trace_dir)
    data_dir = tempfile.mkdtemp(prefix="citus_chip_smoke_")
    try:
        cl = ct.Cluster(data_dir)
        native_io = get_lib() is not None
        check(native_io, "native columnar IO library did not load")
        from citus_tpu.executor.device_cache import GLOBAL_CACHE
        cache_dir = jax.config.jax_compilation_cache_dir
        header = {
            "platform": platform, "device_kind": devices[0].device_kind,
            "device_count": n_dev, "rehearsal": args.rehearse_on_cpu,
            "versions": versions(), "native_io": native_io,
            "rows": args.rows, "shards": shards, "seed": args.seed,
            "device_cache_capacity_bytes": GLOBAL_CACHE.capacity,
            "compile_cache_dir": cache_dir,
            "compile_cache_entries_at_start": cache_entries(cache_dir),
            "assumed": [
                "lineitem carries 8 of TPC-H's 16 columns",
                "keys and values are uniform draws (numpy default_rng), "
                "not dbgen's distributions",
                "orders (the repartition join's, distributed on the "
                "customer key) and orders_k (the colocated join's) carry "
                "3 columns, customer 2",
            ],
            "reduced": ([] if args.rows == SF10_LINEITEM_ROWS else
                        [f"rows cut from {SF10_LINEITEM_ROWS} to {args.rows}"]),
        }
        print("header " + json.dumps(header), flush=True)

        cl.execute(LINEITEM_DDL)
        cl.execute("SELECT create_distributed_table('lineitem', "
                   f"'l_orderkey', {shards})")
        rng = np.random.default_rng(args.seed)
        ref = Reference(n_orders)
        t0 = time.perf_counter()
        load_lineitem(cl, ref, rng, args.rows, n_orders)
        cl.execute(ORDERS_DDL)
        cl.execute("SELECT create_distributed_table('orders', "
                   f"'o_custkey', {shards})")
        load_orders(cl, n_orders)
        cl.execute(ORDERS_K_DDL)
        cl.execute("SELECT create_distributed_table('orders_k', "
                   f"'o_orderkey', {shards})")
        load_orders(cl, n_orders, "orders_k")
        cl.execute(CUSTOMER_DDL)
        cl.execute("SELECT create_reference_table('customer')")
        load_customer(cl, n_orders)
        load_s = time.perf_counter() - t0
        print(f"setup: generated, referenced and ingested {args.rows} rows "
              f"in {load_s:.1f} s (set-up, not a metric)", flush=True)

        cl.execute(f"SET citus.trace_export_dir = '{trace_dir}'")
        cl.execute("SET citus.trace_sample_rate = 1")
        run = Runner(cl, trace_dir)
        h2d = leg_q1_cold(run, ref, n_dev)
        leg_q1_warm(run, ref, n_dev)
        mesh_report = leg_mesh(run, ref, devices, h2d) if n_dev > 1 else None
        leg_q6(run, ref, n_dev)
        leg_q6_coalesced(run, ref)
        leg_direct(run, ref, n_dev)
        leg_hash(run, ref, devices, args.rows)
        leg_product(run, ref, n_dev, args.rows)
        leg_hash_every_device(run, ref, devices)
        leg_float_lanes(run, rng, shards, n_dev,
                        min(MEASURES_ROWS, args.rows))
        leg_router(run, ref)
        leg_join_colocated(run, ref)
        leg_join_q10(run, rng, shards, n_orders)
        leg_join_q5(run, rng, shards, n_orders)
        leg_join_repartition(run, ref, devices)

        memory = []
        for dv in devices:
            s = dv.memory_stats() or {}
            memory.append({"id": dv.id,
                           "bytes_limit": s.get("bytes_limit"),
                           "peak_bytes_in_use": s.get("peak_bytes_in_use")})
        report = dict(header, legs=run.legs, mesh=mesh_report, memory=memory,
                      setup_load_s=round(load_s, 1),
                      setup_compile_s=round(
                          sum(l["compile_s"] for l in run.legs), 3),
                      compile_cache_entries_at_end=cache_entries(cache_dir),
                      wall_s_not_a_metric=round(
                          time.perf_counter() - t_start, 1))
        with open(os.path.join(args.out, "report.json"), "w") as fh:
            json.dump(report, fh, indent=1, default=str)
        print("report " + json.dumps(report, default=str), flush=True)
        cl.close()
    finally:
        shutil.rmtree(data_dir, ignore_errors=True)

    device = {"platform": platform, "kind": devices[0].device_kind,
              "count": n_dev}
    final = {"ok": True, "device": device}
    if args.rehearse_on_cpu:
        final["rehearsal"] = True
    print(json.dumps(final), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
