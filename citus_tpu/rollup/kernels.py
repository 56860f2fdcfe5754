"""Delta-batch partial builders for the rollup refresh loop.

A CDC delta batch is (values, group index) pairs; the refresh needs the
same per-group partial states the scan aggregates compute — count/sum
psum-combinable vectors, HLL register maxes, DDSketch/top-k bucket
histograms — just over a small batch instead of a shard.  The builders
here compile through ``kernel_cache.jit_compile`` (the package's one
``jax.jit`` door) and cache in ``GLOBAL_KERNELS`` keyed by padded batch
shape, so a steady-state refresh loop recompiles only when the batch
size crosses a power-of-two boundary.

Scatter (``.at[]``) accumulation is used instead of the scan kernels'
one-hot trick: a rollup group table is G×M wide (M up to 2048), so the
one-hot product would be [G*M, N] — delta batches are small enough that
the serialized scatter is the cheaper shape.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from citus_tpu.executor.kernel_cache import GLOBAL_KERNELS, jit_compile
from citus_tpu.planner.aggregates import (
    DDSK_M, HLL_M, TOPK_M, TOPK_SENTINEL, ddsk_bucket_indexes,
    hll_rho_buckets, hll_value_bits, topk_buckets,
)


def value_bits(arr: np.ndarray) -> np.ndarray:
    """Values -> the int64 lanes the hash sketches consume — the same
    function the raw scan uses, so rollup and raw-scan estimates
    agree."""
    return hll_value_bits(np, arr)


def _pad_to(n: int) -> int:
    p = 8
    while p < n:
        p *= 2
    return p


def _build(kind: str, gp: int):
    if kind == "count":
        def k_count(gidx, ok):
            return jnp.zeros((gp,), jnp.int64) \
                .at[gidx].add(ok.astype(jnp.int64))
        return k_count
    if kind == "sum_int":
        def k_sum_i(vals, gidx, ok):
            upd = jnp.where(ok, vals, jnp.int64(0))
            return jnp.zeros((gp,), jnp.int64).at[gidx].add(upd)
        return k_sum_i
    if kind == "sum_float":
        def k_sum_f(vals, gidx, ok):
            upd = jnp.where(ok, vals, jnp.float64(0.0))
            return jnp.zeros((gp,), jnp.float64).at[gidx].add(upd)
        return k_sum_f
    if kind == "hll":
        def k_hll(bits, gidx, ok):
            bucket, rho = hll_rho_buckets(jnp, bits, ok)
            flat = gidx.astype(jnp.int32) * HLL_M + bucket
            acc = jnp.zeros((gp * HLL_M,), jnp.int32)
            return acc.at[flat].max(rho).reshape(gp, HLL_M)
        return k_hll
    if kind == "ddsk":
        def k_ddsk(vals, gidx, ok):
            bucket = ddsk_bucket_indexes(jnp, vals)
            flat = gidx.astype(jnp.int32) * DDSK_M + bucket
            acc = jnp.zeros((gp * DDSK_M,), jnp.int64)
            return acc.at[flat].add(ok.astype(jnp.int64)) \
                .reshape(gp, DDSK_M)
        return k_ddsk
    if kind == "topk":
        def k_topk(bits, gidx, ok):
            bucket = topk_buckets(jnp, bits)
            flat = gidx.astype(jnp.int32) * TOPK_M + bucket
            counts = jnp.zeros((gp * TOPK_M,), jnp.int64) \
                .at[flat].add(ok.astype(jnp.int64)).reshape(gp, TOPK_M)
            upd = jnp.where(ok, bits, TOPK_SENTINEL)
            vals = jnp.full((gp * TOPK_M,), TOPK_SENTINEL, jnp.int64) \
                .at[flat].max(upd).reshape(gp, TOPK_M)
            return counts, vals
        return k_topk
    raise AssertionError(f"unknown rollup partial kind {kind!r}")


def delta_partials(kind: str, gidx: np.ndarray, ok: np.ndarray,
                   n_groups: int, values=None):
    """Per-group partials for one delta batch.

    ``kind``   — count | sum_int | sum_float | hll | ddsk | topk
    ``gidx``   — [N] group index per row
    ``ok``     — [N] bool (real row AND value non-null)
    ``values`` — [N] values (sum/ddsk) or int64 hash bits (hll/topk)

    Returns numpy: [G] for count/sum, [G, M] for hll/ddsk, a
    ([G, M], [G, M]) counts/values pair for topk.
    """
    n = int(np.asarray(gidx).shape[0])
    np_pad, gp = _pad_to(max(n, 1)), _pad_to(max(n_groups, 1))
    g = np.zeros(np_pad, np.int32)
    g[:n] = np.asarray(gidx, np.int32)
    m = np.zeros(np_pad, bool)
    m[:n] = np.asarray(ok, bool)
    args = [g, m]
    if values is not None:
        dt = np.float64 if kind in ("sum_float", "ddsk") else np.int64
        v = np.zeros(np_pad, dt)
        v[:n] = np.asarray(values, dt)
        args = [v, g, m]
    key = ("rollup", kind, np_pad, gp)
    kern = GLOBAL_KERNELS.get(key)
    if kern is None:
        kern = jit_compile(_build(kind, gp))
        GLOBAL_KERNELS.put(key, kern)
    out = kern(*args)
    if isinstance(out, tuple):
        return tuple(np.asarray(o)[:n_groups] for o in out)
    return np.asarray(out)[:n_groups]
