"""Device mesh + the partial-agg/combine collective.

``sharded_partial_agg`` is the north-star lowering (SURVEY §2.4): each
mesh slot runs the worker kernel on its shard's batch, then the partial
states are combined in-mesh with psum / all_gather and folded into the
running states of the rounds before, which stay on the chips — the
reference needs a coordinator gather plus a combine query for the same
step (multi_logical_optimizer.c MasterExtendedOpNode).
"""

from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from citus_tpu.errors import ExecutionError
from citus_tpu.executor.kernel_cache import jit_compile
from citus_tpu.observability.trace import kernel_scope
from citus_tpu.ops.scan_agg import fold_partials

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
    """Wrap a worker fn (cols, valids, row_mask) -> partial tuple into
    one mesh ROUND over stacked inputs [n_dev, N]:

      run(acc, cols, valids, row_mask) -> acc'
      acc'[i] = acc[i] (+) combine_over_shards(worker(inputs[shard]))[i]

    ``acc`` holds the running partial states of the rounds before this
    one: replicated (``P()``: every chip holds and folds its own copy),
    donated, shaped like ``zero_partials``' output.  combine_kinds[i]
    in {sum, min, max} selects the collective per position and the
    elementwise fold (+) behind it (``fold_partials``, the rule the
    one-chip ``jit_fused`` loop uses).  The states never leave the
    chips between rounds; the caller fetches the last ``acc`` once.

    The jitted function is called ``run`` and so its XLA module
    ``jit_run``: the device trace is read by that name (kernel time,
    roofline share, dispatches per query)."""
    bad = sorted(set(combine_kinds) - {"sum", "min", "max"})
    if bad:
        # a stacked per-shard output has no elementwise fold: refuse
        # rather than merge it wrongly
        raise ValueError(f"partial states of kind {bad} cannot be folded "
                         "on the mesh")

    def per_shard(acc, cols, valids, row_mask):
        cols = tuple(c[0] for c in cols)      # strip the leading shard dim
        valids = tuple(v[0] for v in valids)
        row_mask = row_mask[0]
        partials = worker(cols, valids, row_mask)
        outs = []
        with kernel_scope(jnp, "scan.fold"):
            for p, kind in zip(partials, combine_kinds):
                if kind == "sum":
                    outs.append(jax.lax.psum(p, SHARD_AXIS))
                else:
                    # TPU lowers only Sum all-reduces; min/max combine as
                    # an all_gather over ICI followed by a local reduction
                    g = jax.lax.all_gather(p, SHARD_AXIS)
                    outs.append(jnp.min(g, axis=0) if kind == "min"
                                else jnp.max(g, axis=0))
            return fold_partials(jnp, combine_kinds, acc, outs)

    def run(acc, cols, valids, row_mask):
        in_specs = (
            tuple(P() for _ in acc),
            tuple(P(SHARD_AXIS) for _ in cols),
            tuple(P(SHARD_AXIS) for _ in valids),
            P(SHARD_AXIS),
        )
        fn = jax.shard_map(per_shard, mesh=mesh, in_specs=in_specs,
                           out_specs=tuple(P() for _ in acc),
                           check_vma=False)
        return fn(acc, cols, valids, row_mask)

    return jit_compile(run, donate_argnums=0)


def zero_partials(empty_partials: Callable[[], tuple], mesh: Mesh) -> Callable:
    """() -> the partial states of zero rows, replicated on the mesh:
    the first round's ``acc``.  ``empty_partials`` builds them with
    jax.numpy, so they are filled on the chips: one small dispatch a
    query instead of a host copy per state and chip.  A step of its own
    (module ``jit_zero_acc``) because ``run`` donates its ``acc``."""

    def zero_acc():
        return empty_partials()

    return jit_compile(zero_acc, out_shardings=NamedSharding(mesh, P()))


def per_device(fn: Callable, mesh: Mesh, replicated: tuple = ()) -> Callable:
    """``fn`` run by every chip of the mesh on its own slice, with no
    collective: each argument and each result is a pytree of arrays
    with a leading device axis, sharded over ``shard`` (a chip sees its
    slice without that axis and returns its results without it) --
    except the arguments at the positions ``replicated``, which every
    chip gets whole.  The per-device hash tables' steps: what one chip
    does to its table is the one-device kernel's body, to the letter.
    The wrapper keeps ``fn``'s name, so the XLA module of the jitted
    wrapper is named as the one-device kernel's is."""
    import functools

    def body(*args):
        args = [a if i in replicated
                else jax.tree_util.tree_map(lambda x: x[0], a)
                for i, a in enumerate(args)]
        return jax.tree_util.tree_map(lambda x: jnp.asarray(x)[None],
                                      fn(*args))

    @functools.wraps(fn)
    def on_each(*args):
        specs = tuple(P() if i in replicated else P(SHARD_AXIS)
                      for i in range(len(args)))
        return jax.shard_map(body, mesh=mesh, in_specs=specs,
                             out_specs=P(SHARD_AXIS), check_vma=False)(*args)

    return on_each
