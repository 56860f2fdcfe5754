"""Repartition shuffle: all_to_all over the mesh.

The reference redistributes rows between workers with MapMergeJob — map
tasks hash-partition each source shard's rows into bucket files, fetch
tasks pull each bucket to its destination
(src/backend/distributed/planner/multi_physical_planner.h MapMergeJob;
executor/partitioned_intermediate_results.c worker_partition_query_result;
directed_acyclic_graph_execution.c).  On a TPU mesh the same exchange is
one ``jax.lax.all_to_all`` over ICI.

Static-shape contract: each device holds ``N`` rows (+validity); rows
are bucketed by a target id in ``[0, n_dev)``; every (src, dst) block is
padded to a fixed capacity ``C``.  If any block overflows C the shuffle
reports it (`overflow` flag) and the caller retries with a larger C or
falls back to the host path — the static-shape equivalent of the
reference's dynamically-sized bucket files.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from citus_tpu.executor.kernel_cache import jit_compile
from citus_tpu.observability.trace import kernel_scope
from citus_tpu.parallel.mesh import SHARD_AXIS


def _pack_blocks(values: tuple, target: jnp.ndarray, mask: jnp.ndarray,
                 n_dev: int, capacity: int, rnd=0):
    """Arrange one device's rows into [n_dev, C] send blocks by target.

    Returns (packed values tuple, packed validity, overflow): block
    ``d`` holds the ``rnd``-th ``capacity`` rows addressed to device
    ``d``, in their order; ``overflow`` counts the rows that no round up
    to this one has taken (the caller answers with a further round, or
    with a larger capacity).  One sort of the row positions and a
    contiguous slice a destination: a scatter is a serial loop on a TPU.
    """
    n = target.shape[0]
    bits = max(1, (n - 1).bit_length())
    pos = jnp.arange(n, dtype=np.int32)
    tgt = jnp.where(mask, target, n_dev).astype(np.int32)  # invalid -> virtual bucket
    if (n_dev + 1) << bits < 2 ** 31:
        # destination above position in one int32 lane: one operand
        order = jax.lax.sort((tgt << bits) | pos) & np.int32((1 << bits) - 1)
    else:
        order = jnp.argsort(tgt, stable=True).astype(np.int32)
    # where each destination's rows start among the sorted ones
    counts = jnp.stack([jnp.sum(tgt == d, dtype=np.int32)
                        for d in range(n_dev)])
    start = jnp.cumsum(counts) - counts
    # room for a whole block after the last row: a slice never clamps
    order = jnp.concatenate([order, jnp.zeros((capacity,), np.int32)])
    first = rnd * capacity
    at = jnp.stack([jax.lax.dynamic_slice(
        order, (jnp.minimum(start[d] + first, n),), (capacity,))
        for d in range(n_dev)])
    lane = first + jnp.arange(capacity, dtype=np.int32)
    packed_valid = lane[None, :] < counts[:, None]
    packed = tuple(jnp.where(packed_valid, v[at], jnp.zeros((), v.dtype))
                   for v in values)
    overflow = jnp.sum(jnp.maximum(counts - (first + capacity), 0))
    return packed, packed_valid, overflow


def exchange_rows(values: tuple, target, mask, n_dev: int, capacity: int,
                  rnd=0):
    """One device's half of an ``all_to_all`` exchange, for a body that
    runs under ``shard_map`` over ``SHARD_AXIS``: its rows ``values``
    (arrays ``[N]``) that ``mask`` keeps go to the devices ``target``
    names, ``capacity`` rows a (source, destination) block, round
    ``rnd`` of them.  -> (the received lanes ``[n_dev * capacity]``,
    their validity, int32 ``[3]``: rows sent this round, rows this
    device received, rows no round has taken yet).  One ``all_to_all`` a
    lane; nothing is dropped: what a block cannot hold waits for the
    next round."""
    with kernel_scope(jnp, "exchange.pack"):
        packed, pvalid, overflow = _pack_blocks(values, target, mask, n_dev,
                                                capacity, rnd)
    with kernel_scope(jnp, "exchange.all_to_all"):
        swap = lambda v: jax.lax.all_to_all(
            v, SHARD_AXIS, split_axis=0, concat_axis=0).reshape(-1)
        received = tuple(swap(v) for v in packed)
        rvalid = swap(pvalid)
        counts = jnp.stack([pvalid.sum(dtype=np.int32),
                            rvalid.sum(dtype=np.int32),
                            overflow.astype(np.int32)])
    return received, rvalid, counts


def build_repartition(mesh: Mesh, n_cols: int, capacity: int):
    """Compile an all_to_all repartition over ``mesh``.

    Input (stacked over devices): values tuple of [n_dev, N] arrays,
    target [n_dev, N] int32 (destination device per row), mask [n_dev, N].
    Output: values tuple of [n_dev, n_dev*C] (rows now living on their
    target device), validity [n_dev, n_dev*C], overflow count (replicated
    scalar — nonzero means retry with larger capacity).
    """
    n_dev = mesh.shape[SHARD_AXIS]

    def per_device(values, target, mask):
        values = tuple(v[0] for v in values)
        target = target[0]
        mask = mask[0]
        packed, pvalid, overflow = _pack_blocks(values, target, mask, n_dev, capacity)
        # exchange: block i goes to device i; after all_to_all, this
        # device holds the blocks addressed to it from every source
        out_vals = tuple(
            jax.lax.all_to_all(v, SHARD_AXIS, split_axis=0, concat_axis=0)
            for v in packed)
        out_valid = jax.lax.all_to_all(pvalid, SHARD_AXIS, split_axis=0, concat_axis=0)
        total_overflow = jax.lax.psum(overflow, SHARD_AXIS)
        flat_vals = tuple(v.reshape(-1)[None] for v in out_vals)
        return flat_vals, out_valid.reshape(-1)[None], total_overflow

    in_specs = (tuple(P(SHARD_AXIS) for _ in range(n_cols)), P(SHARD_AXIS), P(SHARD_AXIS))
    out_specs = (tuple(P(SHARD_AXIS) for _ in range(n_cols)), P(SHARD_AXIS), P())
    fn = jax.shard_map(per_device, mesh=mesh, in_specs=in_specs,
                       out_specs=out_specs, check_vma=False)
    return jit_compile(fn)


def _sorted_join_indexes(lgid, lvalid, rgid, rvalid, join_cap: int):
    """Per-device inner equi-join on dense group ids -> (left_idx,
    right_idx, out_valid, n_pairs).  Sort-based: left sorts by gid,
    each right row binary-searches its run; output slot j maps back to
    its (right row, offset) pair via a searchsorted over run ends.
    Static output size ``join_cap``; the caller sizes it exactly from
    host-side per-gid counts, so overflow is an invariant violation,
    not a retry path."""
    L = lgid.shape[0]
    R = rgid.shape[0]
    big = jnp.iinfo(lgid.dtype).max
    lkey = jnp.where(lvalid, lgid, big)     # gids are dense >= 0: big is free
    order = jnp.argsort(lkey)
    skey = lkey[order]
    lo = jnp.searchsorted(skey, rgid, side="left")
    hi = jnp.searchsorted(skey, rgid, side="right")
    cnt = jnp.where(rvalid, hi - lo, 0)
    ends = jnp.cumsum(cnt)
    total = ends[-1] if R else jnp.zeros((), cnt.dtype)
    start = ends - cnt
    j = jnp.arange(join_cap)
    # first right row whose run end exceeds j (skips cnt==0 rows)
    r_idx = jnp.searchsorted(ends, j, side="right").clip(0, max(R - 1, 0))
    off = j - start[r_idx]
    l_idx = order[(lo[r_idx] + off).clip(0, max(L - 1, 0))]
    out_valid = j < total
    return l_idx, r_idx, out_valid, total


def build_repartition_join(mesh: Mesh, n_lcols: int, n_rcols: int,
                           capacity_l: int, capacity_r: int, join_cap: int):
    """Compile a fused shuffle+join over ``mesh``: both relations
    all_to_all-exchange by join-key bucket, then each device joins its
    bucket with a sort/searchsorted inner join — the map-merge *and* the
    merge-side hash join of the reference's MapMergeJob pipeline
    (multi_physical_planner.h:160), entirely on the mesh; the host sees
    one fetch of the joined columns.

    Inputs (stacked over devices): left values tuple of [n_dev, Nl]
    (column streams incl. validity as bool columns), lgid [n_dev, Nl]
    int64 dense join-group ids, ltgt/lmask likewise; same for the right
    side.  Output: left columns gathered to [n_dev, join_cap], right
    columns likewise, out_valid [n_dev, join_cap], overflow scalar
    (must be 0 when join_cap is sized exactly)."""
    n_dev = mesh.shape[SHARD_AXIS]

    def per_device(lvals, lgid, ltgt, lmask, rvals, rgid, rtgt, rmask):
        lvals = tuple(v[0] for v in lvals)
        rvals = tuple(v[0] for v in rvals)
        lgid, ltgt, lmask = lgid[0], ltgt[0], lmask[0]
        rgid, rtgt, rmask = rgid[0], rtgt[0], rmask[0]

        def exchange(values, gid, tgt, mask, capacity):
            packed, pvalid, overflow = _pack_blocks(
                (gid,) + values, tgt, mask, n_dev, capacity)
            outs = tuple(
                jax.lax.all_to_all(v, SHARD_AXIS, split_axis=0, concat_axis=0)
                for v in packed)
            ovalid = jax.lax.all_to_all(pvalid, SHARD_AXIS,
                                        split_axis=0, concat_axis=0)
            flat = tuple(v.reshape(-1) for v in outs)
            return flat[0], flat[1:], ovalid.reshape(-1), overflow

        lgid_x, lcols_x, lvalid_x, lov = exchange(lvals, lgid, ltgt, lmask,
                                                  capacity_l)
        rgid_x, rcols_x, rvalid_x, rov = exchange(rvals, rgid, rtgt, rmask,
                                                  capacity_r)
        li, ri, ovalid, total = _sorted_join_indexes(
            lgid_x, lvalid_x, rgid_x, rvalid_x, join_cap)
        out_l = tuple(v[li] for v in lcols_x)
        out_r = tuple(v[ri] for v in rcols_x)
        join_overflow = jnp.maximum(total - join_cap, 0)
        overflow = jax.lax.psum(lov + rov + join_overflow, SHARD_AXIS)
        return (tuple(v[None] for v in out_l), tuple(v[None] for v in out_r),
                ovalid[None], overflow)

    cols = lambda k: tuple(P(SHARD_AXIS) for _ in range(k))
    in_specs = (cols(n_lcols), P(SHARD_AXIS), P(SHARD_AXIS), P(SHARD_AXIS),
                cols(n_rcols), P(SHARD_AXIS), P(SHARD_AXIS), P(SHARD_AXIS))
    out_specs = (cols(n_lcols), cols(n_rcols), P(SHARD_AXIS), P())
    fn = jax.shard_map(per_device, mesh=mesh, in_specs=in_specs,
                       out_specs=out_specs, check_vma=False)
    return jit_compile(fn)


def repartition_host(values: tuple, target: np.ndarray, mask: np.ndarray,
                     n_buckets: int):
    """Host reference implementation (oracle + fallback): returns per-
    bucket lists of row arrays."""
    out = []
    for b in range(n_buckets):
        sel = mask & (target == b)
        out.append(tuple(v[sel] for v in values))
    return out
