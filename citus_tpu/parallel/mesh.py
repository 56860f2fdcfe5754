"""Device mesh + the partial-agg/combine collective.

``sharded_partial_agg`` is the north-star lowering (SURVEY §2.4): each
mesh slot runs the worker kernel on its shard's batch, then the partial
states are combined in-mesh with psum/pmin/pmax so every device (and the
host) sees the merged table after one collective — the reference needs a
coordinator gather plus a combine query for the same step
(multi_logical_optimizer.c MasterExtendedOpNode).
"""

from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from citus_tpu.errors import ExecutionError
from citus_tpu.executor.kernel_cache import jit_compile

SHARD_AXIS = "shard"


def executor_devices() -> list:
    """The devices the JAX executor runs on — the package's one answer
    to that question.  Cluster node count, scan loop, join mesh and
    ``default_mesh`` ask here for the list; ``kernel_cache.jit_compile``
    asks before it builds any kernel, so no slot (projection, hash
    aggregate, megabatch, rollup, a hosted worker's task) can compute
    without having passed this check.

    The CPU platform counts only when it was asked for by name
    (``JAX_PLATFORMS`` / ``jax_platforms`` lists ``cpu``, as the test
    harness does).  When the platform choice was left to JAX and it
    came back with ``cpu``, the accelerator is missing or unreachable:
    raise instead of computing on the host under the accelerator's
    name.  A query under ``task_executor_backend = "cpu"`` (the numpy
    arm) builds no kernel and never calls this."""
    devs = jax.devices()
    if devs[0].platform == "cpu":
        asked = (jax.config.jax_platforms or "").lower().split(",")
        if "cpu" not in asked:
            raise ExecutionError(
                "no accelerator found: JAX fell back to the cpu platform "
                "without being asked to (set JAX_PLATFORMS=cpu to run "
                "the executor on the host deliberately)")
    return devs


def default_mesh(n: Optional[int] = None) -> Mesh:
    devs = executor_devices()
    n = n or len(devs)
    return Mesh(devs[:n], (SHARD_AXIS,))


def shard_axis_size(mesh: Mesh) -> int:
    return mesh.shape[SHARD_AXIS]


def sharded_partial_agg(worker, combine_kinds: list[str], mesh: Mesh) -> Callable:
    """Wrap a worker fn (cols, valids, row_mask) -> partial tuple into a
    shard_map'd program over stacked inputs [n_dev, N]:

      out[i] = combine_over_shards(worker(inputs[shard]))   (replicated)

    combine_kinds[i] in {sum, min, max, none} selects the collective per
    output position; 'none' outputs are returned stacked per-shard.
    """

    def per_shard(cols, valids, row_mask):
        cols = tuple(c[0] for c in cols)      # strip the leading shard dim
        valids = tuple(v[0] for v in valids)
        row_mask = row_mask[0]
        partials = worker(cols, valids, row_mask)
        outs = []
        for p, kind in zip(partials, combine_kinds):
            if kind == "sum":
                outs.append(jax.lax.psum(p, SHARD_AXIS))
            elif kind in ("min", "max"):
                # TPU lowers only Sum all-reduces; min/max combine as an
                # all_gather over ICI followed by a local reduction
                g = jax.lax.all_gather(p, SHARD_AXIS)
                outs.append(jnp.min(g, axis=0) if kind == "min" else jnp.max(g, axis=0))
            else:
                outs.append(p[None])
        return tuple(outs)

    n_in = None  # in_specs built per call from pytree structure

    def run(cols, valids, row_mask):
        in_specs = (
            tuple(P(SHARD_AXIS) for _ in cols),
            tuple(P(SHARD_AXIS) for _ in valids),
            P(SHARD_AXIS),
        )
        out_specs = tuple(
            P(SHARD_AXIS) if kind == "none" else P()
            for kind in combine_kinds
        )
        fn = jax.shard_map(per_shard, mesh=mesh, in_specs=in_specs,
                           out_specs=out_specs, check_vma=False)
        return fn(cols, valids, row_mask)

    return jit_compile(run)
