"""What a trip of ``jit_hash_fused``'s offer loop costs, by table size
and by chunk (ISSUE 51, step 0).  A builder's tool for the chip:

    chiprun -- python3 tools/hash_offer_probe.py [--tag parent]

It compiles the module itself (``ops/hash_agg.py``
``build_fused_hash_worker``) for two plans -- Q18's block (one bigint
key, sum + count) over a batch of 1,048,576 rows with 187,500 distinct
keys, and Q3's aggregate shape (three keys: bigint, date, int; one sum)
over a block of 65,536 rows of which 18,689 are live -- at S slots and a
chunk of C entries, C set through the module's constants before the
trace (both chunks of its rule, so that every table runs THIS chunk).  Each variant is timed on an EMPTY table with D entries and with
none (every row masked out): the difference over the trips is a trip,
the rest is what a dispatch costs whatever it offers (the sort and the
gathers into sorted order: it moves by tens of ms with the variant
compiled, so compare ``ms_offered`` too).  Run from the
root of the checkout it measures (the parent's copy: ``cd`` there and
give this file's path); writes ``chiprun_out/hash_offer_probe.<tag>.json``.
"""

import argparse
import json
import os
import sys
import tempfile
import time

import numpy as np

sys.path.insert(0, os.getcwd())

SHAPES = {
    # name: (DDL, SQL, batch rows, live rows, distinct keys)
    "q18": ("CREATE TABLE li (l_orderkey bigint NOT NULL, "
            "l_quantity decimal(15,2) NOT NULL)",
            "SELECT l_orderkey, sum(l_quantity) FROM li GROUP BY l_orderkey",
            1 << 20, 750_000, 187_500),
    "q3": ("CREATE TABLE li (l_orderkey bigint NOT NULL, d date NOT NULL, "
           "p int NOT NULL, v decimal(15,2) NOT NULL)",
           "SELECT l_orderkey, d, p, sum(v) FROM li GROUP BY l_orderkey, d, p",
           1 << 16, 18_689, 18_689),
}
SLOTS = (1 << 20, 1 << 22, 1 << 23, 1 << 24)
CHUNKS = (4096, 8192, 16384, 65536)
REPS = 5


def variants(shape: str):
    """(S, C) pairs, the same for a parent and a change: every chunk at
    the tables the cells have (2^23 and 2^24 slots under Q18's block,
    one chip and four; 2^20 under Q3's aggregate) and every table size
    at the widest chunk and at the expected one."""
    own = (1 << 23, 1 << 24) if shape == "q18" else (1 << 20,)
    return [(s, c) for s in SLOTS for c in CHUNKS
            if s in own or c in (8192, 65536)]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--tag", default="change")
    ap.add_argument("--shapes", default="q18,q3")
    ap.add_argument("--small", action="store_true",
                    help="a rehearsal: tiny shapes, proves nothing")
    args = ap.parse_args()

    import citus_tpu as ct
    import jax
    import jax.numpy as jnp
    from citus_tpu.executor.executor import _hash_key_dtypes
    from citus_tpu.ops import hash_agg
    from citus_tpu.planner import parse_sql
    from citus_tpu.planner.bind import bind_select
    from citus_tpu.planner.physical import plan_select

    out = {"device": jax.devices()[0].device_kind, "tag": args.tag,
           "rows": []}
    for shape in args.shapes.split(","):
        ddl, sql, N, live, D = SHAPES[shape]
        grid = variants(shape)
        if args.small:
            N, live, D = 4096, 3000, 1500
            grid = [(1 << 12, 512), (1 << 13, 4096)]
        cl = ct.Cluster(tempfile.mkdtemp())
        cl.execute(ddl)
        cl.execute("SELECT create_distributed_table('li', 'l_orderkey', 1)")
        # two rows far apart: no key domain, so the plan takes the hash table
        rows = {"l_orderkey": np.array([1, 10**13])}
        if shape == "q18":
            rows["l_quantity"] = np.array([1.0, 2.0])
        else:
            rows.update(d=np.array([9000, 9001], np.int32),
                        p=np.array([0, 1], np.int32), v=np.array([1.0, 2.0]))
        cl.copy_from("li", columns=rows)
        plan = plan_select(cl.catalog,
                           bind_select(cl.catalog, parse_sql(sql)[0]))
        assert plan.group_mode.kind == "hash_host", plan.group_mode
        key_dtypes = _hash_key_dtypes(plan, {})
        lanes = plan.lanes
        rng = np.random.default_rng(51)
        keys = rng.choice(10**12, D, replace=False)
        at = rng.permutation(live) % D
        cols = []
        for c, dt in zip(plan.scan_columns, lanes):
            v = np.zeros(N, dt)
            if c == "l_orderkey":
                v[:live] = keys[at]
            elif c in ("d", "p"):
                v[:live] = (keys[at] % 2000).astype(dt)
            else:
                v[:live] = rng.integers(1, 5000, live)
            cols.append(jax.device_put(v))
        cols = tuple(cols)
        valids = tuple(jax.device_put(np.ones(N, bool)) for _ in cols)
        masks = {D: jax.device_put(np.arange(N) < live),
                 0: jax.device_put(np.zeros(N, bool))}
        for S, C in grid:
            # every table size at THIS chunk, whatever the module's rule
            hash_agg.ENTRY_CHUNK = hash_agg.WIDE_CHUNK = C
            kernel = jax.jit(
                hash_agg.build_fused_hash_worker(plan, jnp, key_dtypes),
                donate_argnums=0)
            t0 = time.perf_counter()
            ms = {}
            for d, mask in masks.items():
                ts = []
                for rep in range(REPS + 1):
                    state = jax.device_put(
                        hash_agg.empty_hash_state(plan, S, key_dtypes))
                    jax.block_until_ready(state)
                    t = time.perf_counter()
                    state, spill = kernel(state, cols, valids, mask)
                    jax.block_until_ready((state, spill))
                    ts.append((time.perf_counter() - t) * 1e3)
                    if rep == 0:
                        got = (int(spill[0]), int(spill[1]))
                # a key whose rows another key's interleave in the sorted
                # order is offered as several entries: a few in 10,000
                assert d <= got[0] <= d + d // 500, (got, d)
                ms[d] = sorted(ts[1:])[REPS // 2]
                if d:
                    offered, spilled = got
            c = min(C, N)
            trips = -(-offered // c)
            row = {"shape": shape, "K": len(key_dtypes),
                   "P": len(plan.partial_ops), "N": N, "D": D, "S": S,
                   "C": c, "offered": offered, "trips": trips, "ms_offered": ms[D],
                   "ms_nothing": ms[0],
                   "ms_a_trip": (ms[D] - ms[0]) / trips,
                   "spilled": spilled,
                   "compile_and_run_s": time.perf_counter() - t0}
            out["rows"].append(row)
            print(json.dumps(row), flush=True)
            os.makedirs("chiprun_out", exist_ok=True)
            with open(f"chiprun_out/hash_offer_probe.{args.tag}.json",
                      "w") as fh:
                json.dump(out, fh, indent=1)


if __name__ == "__main__":
    main()
