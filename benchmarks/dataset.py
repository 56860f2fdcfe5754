"""The table a configuration runs on: generated from the configuration's
seed, ingested through ``Cluster.copy_from`` once per checkout, and
reopened by every later run.

The directory is keyed by (configuration, data seed, generator version,
rows) under ``benchmarks/.data/``, which git ignores: two cells of one
configuration share it, and it never enters a tree that is committed or
copied.  The reference's statistics are saved beside it.
"""

import concurrent.futures
import os
import shutil
import time

import numpy as np

from .spec import HERE, plugin

DATA_ROOT = os.path.join(HERE, ".data")


def data_dir(config, generator, orders: int) -> str:
    g = config["generator"]
    return os.path.join(
        DATA_ROOT, f"{config['name']}-seed{g['data_seed']}"
                   f"-g{generator.GENERATOR_VERSION}-orders{orders}")


def tree_bytes(path: str) -> int:
    return sum(os.path.getsize(os.path.join(d, f))
               for d, _, files in os.walk(path) for f in files)


def prepare(config, n_devices: int, open_cluster, orders=None, log=print):
    """-> (cluster, statistics dict, info).  Ingests on a checkout's
    first run of this configuration; ``info["ingested"]`` says which."""
    generator = plugin("generators", config["generator"]["name"])
    gparams = dict(config["generator"])
    if orders is not None:
        gparams["orders"] = orders
        gparams["chunk_orders"] = min(gparams["chunk_orders"], orders)
    root = data_dir(config, generator, gparams["orders"])
    db, stats_path = os.path.join(root, "db"), os.path.join(root, "stats.npz")
    ready = os.path.join(root, "READY")
    info = {"data_dir": os.path.relpath(root, HERE), "ingested": False}
    if not os.path.exists(ready):
        # a half-written directory (a run cut during ingest) is not reopened
        shutil.rmtree(root, ignore_errors=True)
        os.makedirs(root)
        t0 = time.perf_counter()
        cl = open_cluster(db)
        shards = config["shards_per_device"] * n_devices
        cl.execute(config["ddl"])
        cl.execute(f"SELECT create_distributed_table('{config['table']}', "
                   f"'{config['distribution_column']}', {shards})")
        stats = generator.Statistics(gparams)

        def make(i):
            chunk = generator.generate_chunk(gparams, gparams["data_seed"], i)
            stats.add(chunk)
            return generator.copy_columns(chunk)

        # one thread makes chunk i + 1 while chunk i is ingested
        with concurrent.futures.ThreadPoolExecutor(1) as pool:
            ahead = pool.submit(make, 0)
            for i in range(generator.n_chunks(gparams)):
                columns = ahead.result()
                if i + 1 < generator.n_chunks(gparams):
                    ahead = pool.submit(make, i + 1)
                cl.copy_from(config["table"], columns=columns)
        arrays = stats.arrays()
        np.savez(stats_path, **arrays)
        info.update(ingested=True,
                    ingest_s=time.perf_counter() - t0,
                    data_bytes_on_disk=tree_bytes(db))
        with open(ready, "w") as fh:
            fh.write(f"{int(arrays['rows'])} rows, {shards} shards\n")
        log(f"setup: generated and ingested {int(arrays['rows'])} rows into "
            f"{info['data_dir']} in {info['ingest_s']:.1f} s "
            f"({info['data_bytes_on_disk']} bytes on disk)")
    else:
        cl = open_cluster(db)
        with np.load(stats_path) as z:
            arrays = {k: z[k] for k in z.files}
    info["rows"] = int(arrays["rows"])
    return cl, arrays, info
