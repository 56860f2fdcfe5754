"""Scan → filter → partial-aggregate worker kernels.

One worker function is built per physical plan and jit-compiled once per
(plan, batch shape).  Its structure mirrors the per-shard half of the
reference's split aggregation (multi_logical_optimizer.c
WorkerExtendedOpNode): evaluate quals, compute group ids, accumulate
combinable partial states.  All partial states are chosen so that the
cross-shard combine is a pure elementwise sum/min/max — i.e. a single
``psum``/``pmin``/``pmax`` over the mesh axis (the reference needs a
coordinator-side combine query; we need one collective).

Input convention (fixed by the executor):
    cols:     tuple of value arrays [N] in plan.scan_columns order
    valids:   tuple of bool arrays [N] (validity)
    row_mask: bool array [N] marking real (non-padding) rows

Output convention:
    scalar mode:    tuple of 0-d accumulators per partial op
    direct mode:    tuple of [G] accumulators per partial op, plus [G]
                    int64 group-row counts
    hash_host mode: (filter_mask [N], key value/valid arrays, agg-input
                    value/valid arrays) — grouping happens on the host
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Callable

import numpy as np

from citus_tpu.observability.trace import kernel_scope
from citus_tpu.planner.bound import (
    _as_mask, compile_expr, param_env_names, predicate_mask,
)
from citus_tpu.planner.physical import (
    PhysicalPlan, product_planes, shadow_sources,
)


def _sentinel(kind: str, dtype: np.dtype):
    if kind == "min":
        return np.inf if np.issubdtype(dtype, np.floating) else np.iinfo(dtype).max
    if kind == "max":
        return -np.inf if np.issubdtype(dtype, np.floating) else np.iinfo(dtype).min
    return 0


#: group tables up to this many slots reduce by the masked one-hot on the
#: VPU; larger ones by the factored one-hot product on the MXU.  Read on
#: the chip (PERF.md section 6, PR 29): the one-hot costs G x N selects a
#: partial, the product a few hundred selects a row whatever G is
ONEHOT_MAX_GROUPS = 64

#: rows of one exact float32 accumulation of the product: 8-bit limbs
#: (at most 255 each) summed in float32 stay integers below 2**24
_MM_CHUNK = 32768


def direct_reduction(n_groups: int, numpy_arm: bool) -> str:
    """Which group reduction the direct mode runs, from the plan's group
    count alone: ``scatter`` (numpy), ``onehot`` (small tables: G x N
    selects a partial on the VPU) or ``matmul`` (the middle: counts and
    int64 sums as one factored one-hot product on the MXU; float sums,
    min and max keep the one-hot up to 8,192 slots and scatter above)."""
    if numpy_arm:
        return "scatter"
    return "onehot" if n_groups <= ONEHOT_MAX_GROUPS else "matmul"


class _Pending:
    """A slot of the worker's output that the batch's one product fills."""

    def __init__(self, fill):
        self.fill = fill


def _product_split(n_groups: int) -> tuple[int, int]:
    """(H, L) of the group id's split ``hi * L + lo``.  H rows of the
    left operand a plane, L rows of the right one: their sum is the
    selects a row costs, and H a multiple of bfloat16's 16-row tile makes
    the planes' rows one array without a copy.  Read on the chip (PERF.md
    section 6, PR 29; ms a 4,194,304-row batch of 19 planes): 4,345 slots
    8.9 at (16, 272), 11.8 at (17, 256), 10.0 at (9, 512); 8,193 slots
    12.9 at (16, 520), 19.8 at (33, 256); 65,536 slots 59.0 at (32, 2048),
    86.7 at (128, 512); 1,024 slots 3.9 at (8, 128), 5.5 at (4, 256)."""
    if n_groups <= 256:
        H = 1
    elif n_groups <= 2048:
        H = 8
    else:
        H = 16 * -(-n_groups // (16 * 2048))
    return H, -(-n_groups // (8 * H)) * 8


class _MatmulGroupSums:
    """Counts and int64 sums of one batch by group id, exact, as ONE
    matrix product on the MXU, for group tables in the thousands.

    A group id splits into ``hi * L + lo``.  Every value is cut into
    eight 8-bit limbs (the top one signed, so that the limbs of an int64
    add up to it modulo 2**64, which is int64's own arithmetic); a count
    is one plane of 0/1.  With ``A[p, h, n] = plane_p[n] where hi[n] == h
    else 0`` and ``B[l, n] = (lo[n] == l)``, both exact in bfloat16, the
    product ``A @ B.T`` over the rows is the table of per-group plane
    sums: P*H + L selects a row instead of G a partial, and 2*G*P
    multiply-adds a row on the MXU.  The rows are contracted in chunks of
    32,768, inside which a float32 accumulator holds integers below
    2**24 exactly; the chunks add up in integers.  The loop over the
    chunks reads the 32-bit words and the masks; the limbs are cut
    inside it, so they never travel through HBM."""

    def __init__(self, xp, gid, n_groups: int):
        self.xp, self.gid, self.G = xp, gid, n_groups
        self.sources: list = []     # [n] int32 words and bool masks
        self.planes: list = []      # (source, byte | None, signed)
        self._count_plane: dict = {}
        self.sums: dict = {}        # arg index -> its first limb plane

    def count(self, ok) -> _Pending:
        at = self._count_plane.get(id(ok))
        if at is None:
            at = self._count_plane[id(ok)] = len(self.planes)
            # the source list keeps ``ok`` alive: its id is not reused
            self.planes.append((len(self.sources), None, False))
            self.sources.append(ok)
        return _Pending(lambda table: table[at])

    def sum_int64(self, arg_index: int, v, ok) -> _Pending:
        xp = self.xp
        v = xp.where(ok, v, 0).astype(np.int64)
        at = self.sums[arg_index] = len(self.planes)
        for word in (v.astype(np.uint32).astype(np.int32),
                     (v >> 32).astype(np.int32)):
            for b in range(4):
                # the top byte of the value keeps its sign
                self.planes.append((len(self.sources), b,
                                    len(self.planes) == at + 7))
            self.sources.append(word)
        return _Pending(lambda table: sum(
            table[at + b] << (8 * b) for b in range(8)))

    def shadow(self, arg_index: int, divisor: float) -> _Pending:
        """float64 value of the batch's TRUE per-group sum (not wrapped),
        in the cast's logical units: what the overflow guard sums."""
        at = self.sums[arg_index]
        return _Pending(lambda table: sum(
            table[at + b].astype(np.float64) * float(1 << (8 * b))
            for b in range(8)) / divisor)

    def _table(self):
        """-> int64 [P, G]: every plane's sum in every group."""
        import jax
        import jax.numpy as jnp
        G, P = self.G, len(self.planes)
        H, L = _product_split(G)
        n = self.gid.shape[-1]
        chunk = min(_MM_CHUNK, n)
        pad = -n % chunk
        K = (n + pad) // chunk

        def chunks(a):
            return (jnp.pad(a, (0, pad)) if pad else a).reshape(K, chunk)

        # a plane's sum over a whole batch passes int32 from 2**23 rows on
        wide = np.int64 if (n + pad) * 255 >= 2 ** 31 else np.int32
        slots_lo = jnp.arange(L, dtype=np.int32)[:, None]
        slots_hi = jnp.arange(H, dtype=np.int32)[None, :, None]
        specs = self.planes

        def fold(table, xs):
            g, sources = xs                         # [chunk] each
            planes = []
            for at, byte, signed in specs:
                src = sources[at]
                if byte is not None:
                    src = src >> (8 * byte)         # arithmetic: sign kept
                    src = src if signed else src & 0xFF
                planes.append(src.astype(jnp.bfloat16))
            onehot_lo = (g % L == slots_lo).astype(jnp.bfloat16)   # [L, chunk]
            lhs = jnp.where(g // L == slots_hi, jnp.stack(planes)[:, None, :],
                            jnp.bfloat16(0)).reshape(P * H, chunk)
            part = jnp.einsum("cn,ln->cl", lhs, onehot_lo,
                              preferred_element_type=jnp.float32)
            return table + part.astype(wide), None

        table, _ = jax.lax.scan(
            fold, jnp.zeros((P * H, L), wide),
            (chunks(self.gid), tuple(chunks(a) for a in self.sources)))
        return table.astype(np.int64).reshape(P, H * L)[:, :G]

    def resolve(self, outs: list) -> list:
        table = self._table()
        return [o.fill(table) if isinstance(o, _Pending) else o for o in outs]


def _floor_div_small_quotient(xp, d, step: int, q_max: int):
    """``d // step`` for integer ``d``, exact wherever the quotient lies
    in ``[0, q_max]``, without the 64-step shift-and-subtract loop that a
    64-bit division is on a TPU (it was a quarter of the hourly rollup's
    kernel).  A float32 estimate of a quotient below 2**17 is off by at
    most one; one multiply and two compares in ``d``'s own width settle
    it.  Other rows (padding, NULL keys: the caller masks them) get any
    value in ``[-1, q_max + 1]``."""
    q = xp.floor(d.astype(np.float32) / np.float32(step))
    q = xp.clip(q, -1, q_max + 1).astype(np.int32)
    r = d - q.astype(d.dtype) * d.dtype.type(step)
    return q - (r < 0) + (r >= step)


_INT32 = np.dtype(np.int32)


@dataclass(frozen=True)
class IdLane:
    """How the direct mode makes one key's group code: the width the
    key is subtracted in, and which division (if any) follows."""
    sub: np.dtype    # lanes of ``kv - lo``
    divide: str      # none | estimate | floor_div

    @property
    def narrow(self) -> bool:
        """No 64-bit division, and 32-bit lanes from the code on."""
        return self.divide != "floor_div" or self.sub == _INT32


def direct_id_lanes(plan: PhysicalPlan) -> list[IdLane]:
    """One ``IdLane`` a group key of a direct plan, from what the plan
    proves: every row that counts has ``0 <= kv - lo <= step * (size -
    2)``, its code lies below ``size`` and the id below ``n_groups``.

    ``step == 1`` (a plain column, dictionary or boolean key): the code
    IS the difference, below 2**31, so the low 32 bits of ``kv`` and of
    ``lo`` give it exactly whatever the key's width, and nothing
    divides (for a constant divisor of 1 XLA folds the quotient and
    keeps the 64-bit remainder: two thirds of Q1's kernel).  Another
    ``step`` (``date_trunc``): the difference keeps the key's own width
    where 32 bits hold it and the estimate's correction, else int64;
    the quotient is the float32 estimate (``size`` below 2**17) or a
    floor division, narrowed right after."""
    mode = plan.group_mode
    assert mode.n_groups < 1 << 31, mode.n_groups
    lanes = []
    for key, d in zip(plan.bound.group_keys, mode.domains):
        if d.step == 1:
            lanes.append(IdLane(_INT32, "none"))
            continue
        # the difference and the estimate's ``q * step`` stay in int32
        fits32 = (key.type.device_dtype.itemsize <= 4
                  and -(1 << 31) <= d.lo < 1 << 31
                  and d.step * (d.size + 1) < 1 << 31)
        lanes.append(IdLane(_INT32 if fits32 else np.dtype(np.int64),
                            "estimate" if d.size < 1 << 17 else "floor_div"))
    return lanes


def _low32(v: int) -> np.int32:
    """The low 32 bits of ``v`` as an int32 (two's complement)."""
    return np.int32((v + (1 << 31)) % (1 << 32) - (1 << 31))


def direct_group_id_fn(plan: PhysicalPlan, xp) -> Callable:
    """``group_id(env, mask) -> int32 [N]`` of a direct plan: the slot of
    every row in ``[0, n_groups - 1]``, made as ``direct_id_lanes`` says.
    A row inside its keys' domains gets ``sum((code + 1) * stride)``, a
    NULL key code 0; a masked or padding row computes a wild (wrapped)
    id from its zeroed values and gets slot 0, where its updates are
    neutral.  The numpy and the jax arm take the same branches."""
    mode = plan.group_mode
    key_fns = [compile_expr(k, xp) for k in plan.bound.group_keys]
    lanes = direct_id_lanes(plan)
    G = mode.n_groups

    def group_id(env, mask):
        gid = None
        for kf, lane, d, stride in zip(key_fns, lanes, mode.domains,
                                       mode.strides):
            kv, kvalid = kf(env)
            if lane.divide == "none":
                code = kv.astype(np.int32) - _low32(d.lo)
            else:
                diff = kv.astype(lane.sub) - lane.sub.type(d.lo)
                if lane.divide == "estimate":
                    code = _floor_div_small_quotient(xp, diff, d.step, d.size)
                else:
                    code = (diff // lane.sub.type(d.step)).astype(np.int32)
            code = xp.where(_as_mask(xp, kvalid, kv), code + np.int32(1),
                            np.int32(0))
            part = code if stride == 1 else code * np.int32(stride)
            gid = part if gid is None else gid + part
        # the one clip keeps every index in the table whatever a row
        # holds (XLA's scatter would drop it silently, numpy's raises)
        return xp.clip(xp.where(mask, gid, np.int32(0)), np.int32(0),
                       np.int32(G - 1))
    return group_id


def scan_env_fn(plan: PhysicalPlan) -> Callable:
    """The ONE maker of a scan kernel's ``env``: ``make_env(cols,
    valids) -> {name: (values, valid)}`` over the scan columns and the
    trailing parameter "columns".  A scan column whose
    array arrives narrower than its logical device dtype (an int64
    column the table's statistics bound inside int32 rides the device
    as int32: ``PhysicalPlan.scan_lanes``) is widened to it HERE, inside
    the traced body, so every expression, filter, key and aggregate
    computes on the values and at the dtype it always did, and on a
    TPU no 64-bit parameter has to be cut into halves by a pass of its
    own.  A kernel that moves rows first (the hash kernel's gather
    into sorted order) moves the arrays as they arrived and makes its
    env of the moved ones: a narrowed column is one 32-bit lane there."""
    names = plan.scan_columns + param_env_names(plan.bound.param_specs)
    # (the join's block of payload lanes is no table's: nothing to widen)
    table = plan.bound.table
    logical = [] if table is None else [
        np.dtype(table.schema.scan_dtype(c, device=True))
        for c in plan.scan_columns]

    def make_env(cols, valids):
        env = {}
        for i, (n, c, v) in enumerate(zip(names, cols, valids)):
            if i < len(logical) and c.dtype != logical[i]:
                c = c.astype(logical[i])
            env[n] = (c, v)
        return env
    return make_env


def build_worker_fn(plan: PhysicalPlan, xp) -> Callable:
    """Build the per-shard worker function (pure, jittable when xp=jnp)."""
    filter_fn = compile_expr(plan.bound.filter, xp) if plan.bound.filter is not None else None
    arg_fns = [compile_expr(a, xp) for a in plan.agg_args]
    arg_types = [a.type for a in plan.agg_args]
    mode = plan.group_mode
    # $N parameters ride as trailing 0-d "columns": the jitted kernel
    # treats them as traced inputs, so one compile serves every value
    make_env = scan_env_fn(plan)
    partial_ops = plan.partial_ops

    def eval_mask(env, row_mask):
        if filter_fn is None:
            return row_mask
        return row_mask & predicate_mask(xp, filter_fn, env, row_mask)

    def filtered(cols, valids, row_mask):
        """-> (env, the rows that count), each under its scope."""
        with kernel_scope(xp, "scan.env"):
            env = make_env(cols, valids)
        with kernel_scope(xp, "scan.filter"):
            return env, eval_mask(env, row_mask)

    if mode.kind == "scalar":
        def worker_scalar(cols, valids, row_mask):
            env, mask = filtered(cols, valids, row_mask)
            outs = []
            with kernel_scope(xp, "scan.reduce"):
                for op in partial_ops:
                    if op.arg_index < 0:
                        outs.append(xp.sum(mask, dtype=np.int64))
                        continue
                    v, valid = arg_fns[op.arg_index](env)
                    ok = mask & _as_mask(xp, valid, mask)
                    dt = np.dtype(op.dtype)
                    if op.kind == "count":
                        outs.append(xp.sum(ok, dtype=np.int64))
                    elif op.kind == "sum":
                        outs.append(xp.sum(xp.where(ok, v, 0).astype(dt)))
                    elif op.kind == "min":
                        outs.append(xp.min(xp.where(ok, v, dt.type(_sentinel("min", dt))).astype(dt)))
                    elif op.kind == "max":
                        outs.append(xp.max(xp.where(ok, v, dt.type(_sentinel("max", dt))).astype(dt)))
                    elif op.kind == "ddsk":
                        # DDSketch log-bucket histogram: per-row bucket id,
                        # one-hot segment sum into [M] — combinable across
                        # shards with the same psum as plain sum partials.
                        # numpy would materialize the [M, N] one-hot (M=2048
                        # — 16x HLL's), so the host backend bincounts instead
                        from citus_tpu.planner.aggregates import (
                            DDSK_M, ddsk_bucket_indexes,
                        )
                        bucket = ddsk_bucket_indexes(xp, xp.asarray(v))
                        if xp.__name__ == "numpy":
                            outs.append(np.bincount(
                                bucket[np.asarray(ok)],
                                minlength=DDSK_M).astype(np.int64))
                        else:
                            onehot = bucket[None, :] == xp.arange(
                                DDSK_M, dtype=np.int32)[:, None]
                            outs.append(xp.sum(
                                (onehot & ok[None, :]).astype(np.int64), axis=1))
                    elif op.kind == "topk":
                        # heavy-hitter count sketch: hashed bucket per row,
                        # one-hot segment sum into [M] — psum-combinable
                        # like ddsk (numpy bincounts for the same reason)
                        from citus_tpu.planner.aggregates import (
                            TOPK_M, topk_buckets,
                        )
                        bucket = topk_buckets(xp, xp.asarray(v).astype(np.int64))
                        if xp.__name__ == "numpy":
                            outs.append(np.bincount(
                                bucket[np.asarray(ok)],
                                minlength=TOPK_M).astype(np.int64))
                        else:
                            onehot = bucket[None, :] == xp.arange(
                                TOPK_M, dtype=np.int32)[:, None]
                            outs.append(xp.sum(
                                (onehot & ok[None, :]).astype(np.int64), axis=1))
                    elif op.kind == "topkv":
                        # companion value register: max value per hash
                        # bucket (INT64_MIN = empty) — max-combinable
                        from citus_tpu.planner.aggregates import (
                            TOPK_M, TOPK_SENTINEL, topk_buckets,
                        )
                        v64 = xp.asarray(v).astype(np.int64)
                        bucket = topk_buckets(xp, v64)
                        upd = xp.where(ok, v64, TOPK_SENTINEL)
                        if xp.__name__ == "numpy":
                            acc = np.full((TOPK_M,), TOPK_SENTINEL, np.int64)
                            outs.append(_np_scatter_max(acc, bucket, upd))
                        else:
                            onehot = bucket[None, :] == xp.arange(
                                TOPK_M, dtype=np.int32)[:, None]
                            outs.append(xp.max(
                                xp.where(onehot, upd[None, :], TOPK_SENTINEL),
                                axis=1))
                    elif op.kind == "hll":
                        # HyperLogLog registers: per-row (bucket, rho), then a
                        # one-hot segment max into [m] — combinable across
                        # shards with the same elementwise-max collective as
                        # plain max partials
                        from citus_tpu.planner.aggregates import (
                            HLL_M, hll_rho_buckets, hll_value_bits,
                        )
                        bucket, rho = hll_rho_buckets(
                            xp, hll_value_bits(xp, v), ok)
                        onehot = bucket[None, :] == xp.arange(
                            HLL_M, dtype=np.int32)[:, None]
                        outs.append(xp.max(
                            xp.where(onehot, rho[None, :], np.int32(0)), axis=1))
            return tuple(outs)
        return worker_scalar

    if mode.kind == "direct":
        G = mode.n_groups
        group_id = direct_group_id_fn(plan, xp)
        reduction = direct_reduction(G, xp.__name__ == "numpy")
        # XLA lowers scatter with colliding indices to a serial loop on
        # TPU; for small group tables a masked one-hot reduction keeps
        # the whole aggregation on the VPU (the [G, N] product is tiled
        # by XLA, never materialized), at G x N compares and selects a
        # partial.  Above the threshold, fall back to scatter.
        use_onehot = xp.__name__ != "numpy" and G <= 8192
        shadow_of = (shadow_sources(plan.partial_ops, plan.agg_args)
                     if reduction == "matmul" else {})
        # the planes the planner chose this route by (None: some partial
        # reduces beside the product)
        planned = product_planes(plan.partial_ops, plan.agg_args)

        def seg_sum(gid, upd, dt):
            if use_onehot:
                onehot = gid[None, :] == xp.arange(G, dtype=gid.dtype)[:, None]
                return xp.sum(xp.where(onehot, upd[None, :], dt.type(0)), axis=1)
            acc = xp.zeros((G,), dt)
            return (acc.at[gid].add(upd) if xp.__name__ != "numpy"
                    else _np_scatter_add(acc, gid, upd))

        def seg_minmax(gid, upd, dt, kind):
            sent = dt.type(_sentinel(kind, dt))
            if use_onehot:
                onehot = gid[None, :] == xp.arange(G, dtype=gid.dtype)[:, None]
                red = xp.min if kind == "min" else xp.max
                return red(xp.where(onehot, upd[None, :], sent), axis=1)
            acc = xp.full((G,), sent, dt)
            if xp.__name__ != "numpy":
                return acc.at[gid].min(upd) if kind == "min" else acc.at[gid].max(upd)
            return (_np_scatter_min if kind == "min" else _np_scatter_max)(acc, gid, upd)

        def worker_direct(cols, valids, row_mask):
            env, mask = filtered(cols, valids, row_mask)
            with kernel_scope(xp, "scan.group_id"):
                gid = group_id(env, mask)
            with kernel_scope(xp, "scan.reduce"):
                # the middle reduction takes every count and int64 sum of the
                # batch in ONE product: they queue here and fill their slots
                # after the loop; float sums, min and max reduce as above
                mm = _MatmulGroupSums(xp, gid, G) if reduction == "matmul" else None
                outs = []

                def count_of(ok):
                    if mm is not None:
                        return mm.count(ok)
                    return seg_sum(gid, xp.where(ok, 1, 0).astype(np.int64),
                                   np.dtype(np.int64))

                for i, op in enumerate(partial_ops):
                    dt = np.dtype(op.dtype)
                    if op.arg_index < 0:
                        outs.append(count_of(mask))
                        continue
                    if i in shadow_of and shadow_of[i][0] in mm.sums:
                        # the float64 overflow guard of an int64 sum that the
                        # product already holds limb by limb: read from those
                        outs.append(mm.shadow(*shadow_of[i]))
                        continue
                    v, valid = arg_fns[op.arg_index](env)
                    ok = mask & _as_mask(xp, valid, mask)
                    if op.kind == "count":
                        outs.append(count_of(ok))
                    elif op.kind == "sum" and mm is not None and dt == np.int64:
                        outs.append(mm.sum_int64(op.arg_index, v, ok))
                    elif op.kind == "sum":
                        outs.append(seg_sum(gid, xp.where(ok, v, 0).astype(dt), dt))
                    else:
                        sent = dt.type(_sentinel(op.kind, dt))
                        upd = xp.where(ok, v, sent).astype(dt)
                        outs.append(seg_minmax(gid, upd, dt, op.kind))
                outs.append(count_of(mask))
                if mm is not None:
                    assert planned in (None, len(mm.planes)), \
                        f"product of {len(mm.planes)} planes, planned {planned}"
                    outs = mm.resolve(outs)
            return tuple(outs)
        return worker_direct

    # hash_host: device evaluates filter, keys and agg inputs; host groups
    key_fns = [compile_expr(k, xp) for k in plan.bound.group_keys]

    def worker_hash(cols, valids, row_mask):
        env, mask = filtered(cols, valids, row_mask)
        keys = []
        for kf in key_fns:
            kv, kvalid = kf(env)
            keys.append((kv, _as_mask(xp, kvalid, kv)))
        args = []
        for af in arg_fns:
            av, avalid = af(env)
            av = xp.asarray(av)
            if av.ndim == 0:  # constant argument, e.g. count(1)
                av = xp.broadcast_to(av, mask.shape)
            args.append((av, _as_mask(xp, avalid, mask)))
        return mask, tuple(keys), tuple(args)
    return worker_hash


def _np_scatter_add(acc, idx, upd):
    np.add.at(acc, idx, upd)
    return acc


def _np_scatter_min(acc, idx, upd):
    np.minimum.at(acc, idx, upd)
    return acc


def _np_scatter_max(acc, idx, upd):
    np.maximum.at(acc, idx, upd)
    return acc


def combine_kinds(plan: PhysicalPlan) -> list[str]:
    """Elementwise combine op per partial state, in build_worker_fn
    output order (the trailing "sum" is direct mode's group row
    counts).  Shared by the host combine, the mesh collectives, and
    the fused running merge below."""
    kinds = []
    for op in plan.partial_ops:
        kinds.append({"sum": "sum", "count": "sum", "min": "min",
                      "max": "max", "hll": "max", "ddsk": "sum",
                      "topk": "sum", "topkv": "max"}[op.kind])
    if plan.group_mode.kind == "direct":
        kinds.append("sum")
    return kinds


def fold_partials(xp, kinds: list[str], acc, out) -> tuple:
    """acc (+) out, elementwise per combine kind: the running merge of
    the device loops (one chip: inside ``jit_fused``; mesh: inside
    ``jit_run``, behind the round's collective)."""
    fold = {"sum": lambda a, o: a + o, "min": xp.minimum, "max": xp.maximum}
    return tuple(fold[kind](a, o) for a, o, kind in zip(acc, out, kinds))


def build_fused_worker_fn(plan: PhysicalPlan, xp) -> Callable:
    """Fused single-dispatch hot loop: decode→filter→partial-agg AND
    the running cross-batch merge in one kernel.

    ``fused(acc, cols, valids, row_mask) -> acc'`` folds one batch into
    the running partial-agg registers.  The executor jits it with
    ``donate_argnums=0`` so the register buffers are donated back to
    the output and stay device-resident across the whole scan — one
    kernel launch per batch, no separate merge dispatch, no host
    round-trip until the final ``device_get``.  Each accumulator has
    the same shape/dtype as the matching ``_empty_partials`` seed, so
    donation reuses every buffer in place."""
    if plan.group_mode.kind == "hash_host":
        raise ValueError("fused accumulation needs device-combinable "
                         "partials (scalar/direct group modes)")
    worker = build_worker_fn(plan, xp)
    kinds = combine_kinds(plan)

    def fused(acc, cols, valids, row_mask):
        out = worker(cols, valids, row_mask)
        with kernel_scope(xp, "scan.fold"):
            return fold_partials(xp, kinds, acc, out)

    return fused


def combine_partials_host(plan: PhysicalPlan, shard_partials: list[tuple]) -> tuple:
    """Combine per-shard partial tuples on the host (numpy).  Used by the
    local executor and as the coordinator-side merge when shards were
    executed in independent rounds; the in-mesh combine uses
    psum/pmin/pmax instead (citus_tpu.parallel.collectives)."""
    ops = list(plan.partial_ops)
    n = len(ops)
    has_rows = plan.group_mode.kind == "direct"
    out = []
    for i, op in enumerate(ops):
        stack = np.stack([np.asarray(sp[i]) for sp in shard_partials])
        if op.kind in ("sum", "count", "ddsk", "topk"):
            out.append(stack.sum(axis=0))
        elif op.kind == "min":
            out.append(stack.min(axis=0))
        elif op.kind in ("max", "hll", "topkv"):
            out.append(stack.max(axis=0))
        else:
            raise AssertionError(f"uncombinable partial kind {op.kind!r}")
    if has_rows:
        rows = np.stack([np.asarray(sp[n]) for sp in shard_partials]).sum(axis=0)
        return tuple(out) + (rows,)
    return tuple(out)
