"""Jit'd public wrappers around the Pallas kernels (padding, filter encoding,
kernel/reference dispatch).

No wrapper takes an interpret flag: each kernel call resolves its mode from
the backend (``filtered_topk.interpret_mode``) — the Pallas interpreter on
the CPU, Mosaic on a TPU, where a kernel that fails to compile raises.
``tests/test_chip_compile.py`` compiles the main-path kernels for a
described v5e topology.
"""
from __future__ import annotations

import functools
import threading
from collections import OrderedDict
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ..core.filters import (BallFilter, BoxFilter, ComposeFilter, Filter,
                            IntervalFilter)
from ..obs.trace import NULL_TRACE
from . import ref
from .distance import pairwise_dist_kernel_call
from .filtered_topk import filtered_topk_kernel_call
from .quant_topk import quant_filtered_topk_kernel_call

__all__ = ["pairwise_dist", "filtered_topk", "next_pow2", "round_up",
           "sharded_filtered_topk", "sharded_filtered_topk_grouped",
           "sharded_quant_filtered_topk", "rerank_topk", "rerank_width",
           "quant_meta_rows", "warm_sharded_shapes", "dispatch_trace_count",
           "encode_filter", "exact_filtered_search", "place_rows",
           "PAD_META"]

_POS = 1e30
_PAD_META = 2e30
# Metadata sentinel for padding / dead rows: every filter kind (including
# "none") rejects rows whose metadata carries this value, so consumers that
# stack ragged shards can mask rows by overwriting their metadata.
PAD_META = _PAD_META


def _pad_to(a, axis, mult, value):
    pad = (-a.shape[axis]) % mult
    if pad == 0:
        return a
    widths = [(0, 0)] * a.ndim
    widths[axis] = (0, pad)
    return jnp.pad(a, widths, constant_values=value)


def next_pow2(v: int) -> int:
    """Smallest power of two >= v — the shared rounding rule behind the
    kernel's kpad padding and the shard packs' bucket-capacity classes
    (one definition, so the two shape families can't drift apart)."""
    p = 1
    while p < v:
        p *= 2
    return p


def round_up(v: int, mult: int) -> int:
    """Smallest multiple of ``mult`` >= max(v, 1) — the shared
    round-up-to-tile rule for lane/sublane padding, bucket row capacities,
    and warm-compile block shapes (one definition across kernels and the
    shard packs)."""
    return ((max(v, 1) + mult - 1) // mult) * mult


_next_pow2 = next_pow2


def pairwise_dist(q, x, metric: str = "l2", use_kernel: bool = True,
                  tq: int = 128, tn: int = 512):
    """[bq, d] x [n, d] -> [bq, n] distance matrix."""
    if not use_kernel:
        return (ref.pairwise_sq_l2(q, x) if metric == "l2"
                else ref.pairwise_neg_ip(q, x))
    bq, n = q.shape[0], x.shape[0]
    q = _pad_to(_pad_to(jnp.asarray(q), 1, 128, 0.0), 0, tq, 0.0)
    x = _pad_to(_pad_to(jnp.asarray(x), 1, 128, 0.0), 0, tn, 0.0)
    out = pairwise_dist_kernel_call(q, x, metric=metric, tq=tq, tn=tn)
    return out[:bq, :n]


def _flatten_and(filt: Filter):
    """Flatten nested 'and' compositions into a list of leaf filters."""
    if isinstance(filt, ComposeFilter) and filt.op == "and":
        return _flatten_and(filt.a) + _flatten_and(filt.b)
    return [filt]


def encode_filter(filt: Optional[Filter], m: int,
                  mpad: int = 128) -> Optional[Tuple[str, np.ndarray]]:
    """Filter object -> (kind, packed [4, mpad] params) or None if the filter
    has no kernel encoding (the caller falls back to the jnp path).

    Box rows default to (-1e30, +1e30) per dim, so half-open intervals
    (``IntervalFilter`` with an open end) encode without a synthetic bound:
    metadata padding rows carry +2e30 and still fail every box test.
    Conjunctions of boxes/intervals fold into one box; one ball plus any
    boxes/intervals encodes as the fused ``box_ball`` kind.
    """
    params = np.zeros((4, mpad), np.float32)
    params[0, :] = -_POS
    params[1, :] = _POS
    params[3, 0] = _POS          # ball r^2 (pass-all by default)
    params[3, 1] = 0             # ball ndim

    def put_box(lo, hi):
        params[0, :m] = np.maximum(params[0, :m], np.asarray(lo, np.float32))
        params[1, :m] = np.minimum(params[1, :m], np.asarray(hi, np.float32))

    def put_interval(f: IntervalFilter) -> bool:
        if f.dim >= m:
            return False
        if f.lo is not None:
            params[0, f.dim] = max(params[0, f.dim],
                                   float(np.asarray(f.lo)))
        if f.hi is not None:
            params[1, f.dim] = min(params[1, f.dim],
                                   float(np.asarray(f.hi)))
        return True

    def put_ball(f: BallFilter):
        c = np.asarray(f.center, np.float32)
        params[2, : len(c)] = c
        params[3, 0] = float(np.asarray(f.radius)) ** 2
        params[3, 1] = len(c)

    if filt is None:
        return "none", params
    if isinstance(filt, BoxFilter):
        put_box(filt.lo, filt.hi)
        return "box", params
    if isinstance(filt, IntervalFilter):
        return ("box", params) if put_interval(filt) else None
    if isinstance(filt, BallFilter):
        put_ball(filt)
        return "ball", params
    if isinstance(filt, ComposeFilter):
        if filt.op == "andnot":
            # (boxes/intervals) \ ball
            b = filt.b
            parts = _flatten_and(filt.a)
            if isinstance(b, BallFilter) and all(
                    isinstance(p, (BoxFilter, IntervalFilter)) for p in parts):
                for p in parts:
                    if isinstance(p, BoxFilter):
                        put_box(p.lo, p.hi)
                    elif not put_interval(p):
                        return None
                put_ball(b)
                return "box_not_ball", params
            return None
        if filt.op == "and":
            parts = _flatten_and(filt)
            balls = [p for p in parts if isinstance(p, BallFilter)]
            rest = [p for p in parts if not isinstance(p, BallFilter)]
            if len(balls) > 1 or not all(
                    isinstance(p, (BoxFilter, IntervalFilter)) for p in rest):
                return None
            for p in rest:
                if isinstance(p, BoxFilter):
                    put_box(p.lo, p.hi)
                elif not put_interval(p):
                    return None
            if not balls:
                return "box", params
            put_ball(balls[0])
            return "box_ball", params
    return None


def filtered_topk(q, x, s, filt: Optional[Filter], k: int,
                  metric: str = "l2", use_kernel: bool = True,
                  tq: int = 64, tn: int = 256):
    """Fused brute-force filtered top-k (exact): returns (ids [bq, k] int32
    with -1 misses, dists [bq, k] ascending)."""
    q = jnp.asarray(q, jnp.float32)
    x = jnp.asarray(x, jnp.float32)
    s = jnp.asarray(s, jnp.float32)
    bq, n = q.shape[0], x.shape[0]
    enc = encode_filter(filt, s.shape[1]) if use_kernel else None
    if enc is None:
        # jnp fallback (arbitrary Filter objects, incl. polygons)
        d = (ref.pairwise_sq_l2(q, x) if metric == "l2"
             else ref.pairwise_neg_ip(q, x))
        if filt is not None:
            ok = filt.contains(s)
            d = jnp.where(ok[None, :], d, jnp.inf)
        neg, ids = jax.lax.top_k(-d, k)
        dd = -neg
        return jnp.where(jnp.isfinite(dd), ids, -1), dd
    kind, params = enc
    kpad = _next_pow2(max(k, 8))
    tn = max(tn, kpad)
    qp = _pad_to(_pad_to(q, 1, 128, 0.0), 0, tq, 0.0)
    xp = _pad_to(_pad_to(x, 1, 128, 0.0), 0, tn, 0.0)
    sp = _pad_to(_pad_to(s, 1, 128, 0.0), 0, tn, _PAD_META)
    dd, ids = filtered_topk_kernel_call(
        qp, xp, sp, jnp.asarray(params), kind=kind, kpad=kpad, metric=metric,
        tq=tq, tn=tn)
    return ids[:bq, :k], dd[:bq, :k]


# ---------------------------------------------------------------------------
# Shard-stack dispatch: jit caches, trace accounting, compile warming
# ---------------------------------------------------------------------------
_TRACE_COUNT = [0]               # bumped at *trace* time of any dispatch
_WARM_SIGS: "OrderedDict[tuple, None]" = OrderedDict()
_WARM_SIGS_MAX = 16
_WARM_LOCK = threading.Lock()


def dispatch_trace_count() -> int:
    """How many shard-stack dispatch traces have run in this process —
    a test/benchmark observable for the compile-warming path (a warmed
    shape must not trace again when the first real query hits it)."""
    return _TRACE_COUNT[0]


def _note_warm_sig(key: tuple) -> None:
    """Remember a dispatch signature (filter kind, k, tiles, padded query
    block, stack geometry) so :func:`warm_sharded_shapes` can replay it
    against a freshly grown bucket shape.  Bounded LRU: only the most
    recent signatures matter — they are what the next query will use."""
    with _WARM_LOCK:
        _WARM_SIGS[key] = None
        _WARM_SIGS.move_to_end(key)
        while len(_WARM_SIGS) > _WARM_SIGS_MAX:
            _WARM_SIGS.popitem(last=False)


def place_rows(arr, mesh):
    """Partition a pack block's leading (row) axis over the mesh's
    ``"shard"`` axis; ``arr`` as is without a mesh.  The bucketed pack
    places its device blocks with it, and compile warming places its zero
    blocks with it too (jit caches per input *sharding*: warming with
    unsharded zeros would compile an executable a mesh-placed query never
    hits).  A row count the mesh does not divide raises: a block is never
    left whole on one device."""
    if mesh is None:
        return arr
    nd = int(mesh.devices.size)
    if int(arr.shape[0]) % nd:
        raise ValueError(f"{arr.shape[0]} block rows do not divide the "
                         f"{nd}-device shard mesh")
    spec = P("shard", *([None] * (arr.ndim - 1)))
    return jax.device_put(arr, NamedSharding(mesh, spec))


def block_mesh(arr):
    """The mesh a pack block is partitioned over (leading axis on
    ``"shard"``), or None for a block on one device.  The dispatches key
    on it: a Mosaic kernel cannot be partitioned by XLA, so over a mesh
    it runs under ``shard_map`` — each device scans its resident rows."""
    sh = getattr(arr, "sharding", None)
    if isinstance(sh, NamedSharding) and len(sh.spec) \
            and sh.spec[0] == "shard":
        return sh.mesh
    return None


def _on_mesh(fn, mesh, in_specs, out_specs):
    """``fn`` as is without a mesh; else mapped per device over the
    ``"shard"`` axis (kernel calls carry no varying-axis types, hence
    ``check_vma=False``)."""
    if mesh is None:
        return fn
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def warm_sharded_shapes(specs) -> int:
    """Pre-trace the per-bucket kernel dispatch for freshly allocated /
    doubled bucket-block shapes, off the query path.

    ``specs`` is an iterable of dicts describing device blocks the pack
    just created: ``{"mode": "fp32", "rows", "cap", "dpad", "mesh"}`` or
    ``{"mode": "int8", "rows", "cap", "dq", "mq", "mesh", "buckets"}``.
    For every recorded dispatch signature (captured from real queries)
    whose geometry matches, the jitted dispatch is invoked once on zero
    arrays of the new shape, built and placed exactly the way the real
    wrappers build theirs (same padding helpers, same mesh sharding) so
    the jit cache entry is the one the first post-growth query will hit
    (the exp12 residual-spike fix).  An int8 spec's ``buckets`` (the
    pack's bucket count) also warms every recorded rerank signature
    (:func:`rerank_topk`) at each candidate width a view of 1 to
    ``buckets`` buckets asks for.  Returns the number of dispatches
    warmed.
    """
    with _WARM_LOCK:
        sigs = list(_WARM_SIGS)
    warmed = 0
    for spec in specs:
        rows, cap = int(spec["rows"]), int(spec["cap"])
        mesh = spec.get("mesh")
        for sig in sigs:
            if sig[0] == "rerank":
                if spec.get("mode") == "int8":
                    warmed += _warm_rerank(sig, int(spec.get("buckets", 0)))
                continue
            mode, kind, kpad, metric, tq, tn, bq_pad = sig[:7]
            if mode != spec.get("mode", "fp32"):
                continue
            if mode == "fp32":
                dpad = sig[7]
                if dpad != int(spec["dpad"]):
                    continue
                qp = jnp.zeros((bq_pad, dpad), jnp.float32)
                x0 = place_rows(jnp.zeros((rows, cap, dpad), jnp.float32),
                                mesh)
                s0 = place_rows(jnp.full((rows, cap, 128), _PAD_META,
                                         jnp.float32), mesh)
                xp = _pad_to(x0, 1, tn, 0.0)
                sp = _pad_to(s0, 1, tn, _PAD_META)
                pj = jnp.zeros((4, 128), jnp.float32)
                _sharded_kernel_dispatch(kind, kpad, metric, tq, tn,
                                         mesh)(qp, xp, sp, pj)
            else:
                dq, mq = sig[7], sig[8]
                if dq != int(spec["dq"]) or mq != int(spec["mq"]):
                    continue
                sc = place_rows(jnp.zeros((rows, dq), jnp.float32), mesh)
                # reproduce the wrapper's scale-fold so the product array
                # carries the same (propagated) sharding as a real query's
                qs = _pad_to(jnp.zeros((bq_pad, dq), jnp.float32)[None]
                             * sc[:, None, :], 1, tq, 0.0)
                c0 = place_rows(jnp.zeros((rows, dq, cap), jnp.int8), mesh)
                st0 = place_rows(jnp.full((rows, mq, cap), _PAD_META,
                                          jnp.float32), mesh)
                cp = _pad_to(c0, 2, tn, 0)
                stp = _pad_to(st0, 2, tn, _PAD_META)
                pt = jnp.zeros((4, mq), jnp.float32)
                qn = jnp.zeros((bq_pad,), jnp.float32)
                _sharded_quant_dispatch(kind, kpad, metric, tq, tn,
                                        mesh)(qs, cp, stp, pt, qn)
            warmed += 1
    return warmed


@functools.lru_cache(maxsize=None)
def _sharded_kernel_dispatch(kind: str, kpad: int, metric: str, tq: int,
                             tn: int, mesh=None):
    """One jitted shard-stack dispatch per (filter kind, k, tile, mesh)
    config.

    The bucketed pack calls :func:`sharded_filtered_topk` once per
    capacity bucket, so the dispatch must not re-trace per call: this
    returns a single ``jax.jit``-wrapped callable whose internal cache is
    keyed on the stack *shape* — each bucket geometry compiles exactly
    once and every later call (any bucket, any epoch) reuses its
    executable.
    """
    def scan(qp, xp, sp, pj):
        def one(x, s):
            return filtered_topk_kernel_call(qp, x, s, pj, kind=kind,
                                             kpad=kpad, metric=metric,
                                             tq=tq, tn=tn)
        return jax.vmap(one)(xp, sp)

    scan = _on_mesh(scan, mesh, (P(), P("shard"), P("shard"), P()),
                    (P("shard"), P("shard")))

    def call(qp, xp, sp, pj):
        _TRACE_COUNT[0] += 1             # python side-effect: trace time only
        return scan(qp, xp, sp, pj)
    return jax.jit(call)


def sharded_filtered_topk(q, xs, ss, filt: Optional[Filter], k: int,
                          metric: str = "l2", use_kernel: bool = True,
                          tq: int = 64, tn: int = 256,
                          m: Optional[int] = None):
    """Shard-parallel fused filtered top-k: one dispatch over a stacked shard
    axis.

    ``q`` is ``[bq, d]``; ``xs`` / ``ss`` are ``[g, n, d]`` / ``[g, n, m]``
    stacks of ``g`` equal-capacity shards (pad ragged shards with
    ``PAD_META`` metadata rows — they fail every predicate, including
    ``filt=None``).  The fused kernel is ``vmap``-ed over the shard axis, so
    the whole stack is a single jitted dispatch; placed on a mesh with a
    ``"shard"`` axis, XLA partitions that axis across devices and each
    device scans only its resident shards.

    Returns ``(ids [g, bq, k], dists [g, bq, k])`` with *shard-local* ids
    (-1 for misses) and ascending exact distances — shard results merge
    exactly because every shard computes the same per-point distance the
    monolithic kernel would.

    ``m`` is the real metadata dimension when ``ss`` arrives pre-padded to
    the 128-lane layout (filter encoding and the jnp fallback must see only
    the live columns).
    """
    q = jnp.asarray(q, jnp.float32)
    xs = jnp.asarray(xs, jnp.float32)
    ss = jnp.asarray(ss, jnp.float32)
    bq, n = q.shape[0], xs.shape[1]
    m = ss.shape[2] if m is None else int(m)
    enc = encode_filter(filt, m) if use_kernel else None
    if enc is None:
        # jnp fallback mirroring filtered_topk's (arbitrary Filter objects);
        # zero-pad q to the (possibly pre-padded) stack width — padding
        # lanes are zero in xs, so they contribute nothing to distances
        qf = _pad_to(q, 1, xs.shape[2], 0.0)

        def one(x, s):
            d = (ref.pairwise_sq_l2(qf, x) if metric == "l2"
                 else ref.pairwise_neg_ip(qf, x))
            ok = (s[:, 0] < _POS)
            if filt is not None:
                ok &= filt.contains(s[:, :m])
            d = jnp.where(ok[None, :], d, jnp.inf)
            neg, ids = jax.lax.top_k(-d, min(k, n))
            dd = -neg
            return jnp.where(jnp.isfinite(dd), ids, -1), dd
        ids, dd = jax.vmap(one)(xs, ss)
        return ids, dd
    kind, params = enc
    kpad = _next_pow2(max(k, 8))
    tn = max(tn, kpad)
    qp = _pad_to(_pad_to(q, 1, 128, 0.0), 0, tq, 0.0)
    xp = _pad_to(_pad_to(xs, 2, 128, 0.0), 1, tn, 0.0)
    sp = _pad_to(_pad_to(ss, 2, 128, 0.0), 1, tn, _PAD_META)
    pj = jnp.asarray(params)
    _note_warm_sig(("fp32", kind, kpad, metric, tq, tn,
                    int(qp.shape[0]), int(qp.shape[1])))
    dd, ids = _sharded_kernel_dispatch(kind, kpad, metric, tq, tn,
                                       block_mesh(xp))(qp, xp, sp, pj)
    return ids[:, :bq, :k], dd[:, :bq, :k]


_I32_MAX = int(np.iinfo(np.int32).max)


def _flip_negative(bits):
    """Map float32 bit patterns (as int32) to keys whose signed order is
    the floats' total order — the order ``lax.top_k`` ranks by, -0.0
    below +0.0 and NaNs at the ends.  The map is its own inverse."""
    return jnp.where(bits < 0, bits ^ _I32_MAX, bits)


def _merge_lists(ids, dd, valid, gid_stack, k: int):
    """Query rows' shard-local lists -> each row's exact global top-k, by
    ``k`` rounds of least-pair extraction instead of a sort.

    ``ids`` / ``dd`` / ``valid`` are ``[rows, b, w]`` (ids into each row of
    ``gid_stack [rows, cap]``, -1 for misses); invalid candidates count as
    +inf.  Each round takes, per query row, the least (distance, position)
    pair past the previous round's: distances in the total order that
    ``lax.top_k`` ranks by, position ``shard * w + rank`` (the index in the
    shard-major concatenation, so ties go to the lower position, as
    ``top_k``'s do).  The result is therefore bit-for-bit ``top_k`` over
    the concatenated lists, the solo merge's.  A sort costs about 300 KB
    of TPU code in each program that holds one; the rounds cost about a
    tenth of that.  Returns ``(gids [b, k], dists [b, k])``, gid -1
    wherever the distance is not finite."""
    _, b, w = dd.shape
    key = _flip_negative(jax.lax.bitcast_convert_type(
        jnp.where(valid, dd, jnp.inf), jnp.int32))
    pos = (jax.lax.broadcasted_iota(jnp.int32, dd.shape, 0) * w
           + jax.lax.broadcasted_iota(jnp.int32, dd.shape, 2))
    col = jax.lax.broadcasted_iota(jnp.int32, (b, k), 1)

    def extract(j, carry):
        v0, p0, out_k, out_p, out_i = carry
        v0, p0 = v0[None, :, None], p0[None, :, None]
        left = (key > v0) | ((key == v0) & (pos > p0))
        v = jnp.min(jnp.where(left, key, _I32_MAX), axis=(0, 2))
        p = jnp.min(jnp.where(left & (key == v[None, :, None]), pos,
                              _I32_MAX), axis=(0, 2))
        i = jnp.max(jnp.where(pos == p[None, :, None], ids, -1), axis=(0, 2))
        at = col == j
        return (v, p, jnp.where(at, v[:, None], out_k),
                jnp.where(at, p[:, None], out_p),
                jnp.where(at, i[:, None], out_i))

    none = jnp.zeros((b, k), jnp.int32)
    init = (jnp.full((b,), -_I32_MAX - 1, jnp.int32),
            jnp.full((b,), -1, jnp.int32), none, none, none)
    _, _, out_k, out_p, out_i = jax.lax.fori_loop(0, k, extract, init)
    dists = jax.lax.bitcast_convert_type(_flip_negative(out_k), jnp.float32)
    gids = gid_stack[out_p // w, jnp.maximum(out_i, 0)]
    return jnp.where(jnp.isfinite(dists), gids, -1), dists


def _grouped_k_top(kpad: int, rows: int, cap: int) -> int:
    """Width of a grouped dispatch's merged lists: the largest
    ``k_out = min(k, rows * kk)`` (``kk = min(k, cap)``) that a group of
    the ``kpad`` class can ask of a ``[rows, cap]`` block.  Below the
    capacity ``kk = k <= kpad``; a class that reaches it (``cap <= kpad``)
    may hold any ``k >= cap``, so it takes every shard's whole list."""
    return kpad if cap > kpad else rows * cap


@functools.lru_cache(maxsize=None)
def _grouped_kernel_dispatch(kind: str, kpad: int, metric: str, tq: int,
                             tn: int, k_top: int, m: int, mesh=None):
    """One filter class of a grouped bucket dispatch, finished in ONE
    jitted program: the class's query rows, every group's rows stacked in
    one query axis with its own packed filter on each row, go through ONE
    fused kernel call per shard (the per-row predicate over the ``m`` real
    metadata dims, so a 64-row query tile serves up to 64 groups and the
    bucket's block is read once per class), then every row's shard lists
    are merged into its global top-k through the bucket's gid table.

    The merge masks what the row's solo call would not see — shards its
    group's temporal ``active`` mask drops and list ranks at or past its
    group's own ``kk`` (data, not shape) — and takes :func:`_merge_lists`
    at ``k_top``; a group keeps its rows' first ``k_out`` columns,
    bit-for-bit the solo merge of the lists cut to ``kk`` at ``k_out``
    (the masked entries are +inf, and a list past its valid entries holds
    only +inf, whose gid is -1 whichever entry is taken).  Shapes follow
    (kind, kpad, tiles, k_top, m, mesh) and the arguments' shapes — padded
    query rows and bucket geometry — never the group count, the per-group
    k or the filter values."""
    def scan(qp, xp, sp, pr):
        def one(x, s):
            return filtered_topk_kernel_call(qp, x, s, pr, kind=kind,
                                             kpad=kpad, metric=metric,
                                             tq=tq, tn=tn, m=m)
        return jax.vmap(one)(xp, sp)

    scan = _on_mesh(scan, mesh, (P(), P("shard"), P("shard"), P()),
                    (P("shard"), P("shard")))

    def call(qp, pr, active, kk, xs, ss, gids):
        _TRACE_COUNT[0] += 1             # python side-effect: trace time only
        xp = _pad_to(_pad_to(xs, 2, 128, 0.0), 1, tn, 0.0)
        sp = _pad_to(_pad_to(ss, 2, 128, 0.0), 1, tn, _PAD_META)
        dd, ids = scan(qp, xp, sp, pr)               # [rows, r_pad, kpad]
        rank = jax.lax.broadcasted_iota(jnp.int32, ids.shape, 2)
        valid = ((ids >= 0) & jnp.transpose(active)[:, :, None]
                 & (rank < kk[None, :, None]))
        return _merge_lists(ids, dd, valid, gids, k_top)
    return jax.jit(call)


def sharded_filtered_topk_grouped(qs, kind: str, params, ks, active, xs, ss,
                                  gids, m: int, metric: str = "l2",
                                  tq: int = 64, tn: int = 256, trace=None):
    """One filter class of request groups against ONE ``[rows, cap, d]`` /
    ``[rows, cap, 128]`` shard stack, kernel and shard merge in one
    program.

    ``qs`` holds each group's ``[bq_i, d]`` queries, ``params`` its packed
    ``[4, 128]`` filter (:func:`encode_filter`, all of kind ``kind``),
    ``ks`` its kernel width ``kk_i = min(k_i, cap)`` (all of one class:
    the same ``kpad = next_pow2(max(kk_i, 8))``) and ``active`` its
    ``[rows]`` temporal row mask; ``gids [rows, cap]`` maps shard-local ids
    to global ones; ``m`` is the real metadata width.  The groups' rows
    are stacked group-major into one query axis of ``R = sum(bq_i)`` rows,
    zero-padded to a multiple of ``tq`` and to the 128-lane width, each
    row carrying its group's filter, temporal mask and ``kk``; built on
    the host in numpy and uploaded with one ``device_put``.  One kernel
    call per shard then scans the block for every row of the class, and
    each row's lists are what its group's solo
    :func:`sharded_filtered_topk` call computes for it.

    Returns ``(gids [R_pad, k_top], dists [R_pad, k_top], offsets)``
    (device arrays, :func:`_grouped_k_top`; ``offsets`` each group's first
    row): group ``i``'s answer is ``[offsets[i]:offsets[i] + bq_i,
    :k_out_i]`` with ``k_out_i = min(k_i, rows * kk_i)``, bit-for-bit the
    solo ``sharded_filtered_topk`` + exact shard merge at ``k_out_i``.
    Nothing waits for the device.

    ``trace`` (a ``repro.obs.trace.QueryTrace``, default off) times
    ``group_stack`` (the host build and upload) and ``kernel_launch``
    (the jitted call, which returns once the work is enqueued).
    """
    trace = NULL_TRACE if trace is None else trace
    n_groups = len(qs)
    rows, cap = int(gids.shape[0]), int(gids.shape[1])
    kpad = _next_pow2(max(max(ks), 8))
    sizes = np.asarray([int(q.shape[0]) for q in qs])
    offsets = np.cumsum(sizes) - sizes
    n_rows = int(sizes.sum())
    with trace.span("group_stack", groups=n_groups, rows=n_rows):
        dpad = round_up(int(xs.shape[2]), 128)
        r_pad = round_up(n_rows, tq)
        qp = np.zeros((r_pad, dpad), np.float32)
        q_all = np.concatenate(qs)
        qp[:n_rows, :q_all.shape[1]] = q_all
        pr = np.zeros((r_pad,) + np.shape(params[0]), np.float32)
        pr[:n_rows] = np.repeat(np.stack(params), sizes, axis=0)
        act = np.zeros((r_pad, rows), bool)
        act[:n_rows] = np.repeat(np.stack(active), sizes, axis=0)
        kk = np.zeros((r_pad,), np.int32)
        kk[:n_rows] = np.repeat(np.asarray(ks, np.int32), sizes)
        args = jax.device_put((qp, pr, act, kk))
    with trace.span("kernel_launch", groups=n_groups, rows=n_rows):
        out_g, out_d = _grouped_kernel_dispatch(
            kind, kpad, metric, tq, max(tn, kpad),
            _grouped_k_top(kpad, rows, cap), int(m), block_mesh(xs))(
                *args, xs, ss, gids)
    return out_g, out_d, offsets


def quant_meta_rows(m: int) -> int:
    """Transposed-metadata sublane count for ``m`` real metadata dims:
    ``m`` dims plus one sublane for the dequantized squared norm, rounded
    up to the fp32 sublane tile (8) — the shared rule between the quant
    kernel layout and the bucketed pack's quantized blocks."""
    return round_up(int(m) + 1, 8)


@functools.lru_cache(maxsize=None)
def _sharded_quant_dispatch(kind: str, kpad: int, metric: str, tq: int,
                            tn: int, mesh=None):
    """Quantized sibling of :func:`_sharded_kernel_dispatch`: one jitted
    int8 shard-stack dispatch per (filter kind, k, tile, mesh) config,
    vmapped over the shard axis, with the per-query ``||q||^2`` term
    folded back into the L2 distances so they are comparable with exact
    fp32 blocks (up to quantization error)."""
    def scan(qs, cs, sts, pt, qn):
        def one(q1, c1, s1):
            return quant_filtered_topk_kernel_call(
                q1, c1, s1, pt, kind=kind, kpad=kpad, metric=metric,
                tq=tq, tn=tn)
        dd, ids = jax.vmap(one)(qs, cs, sts)
        if metric == "l2":
            dd = jnp.where(jnp.isfinite(dd), dd + qn[None, :, None], dd)
        return dd, ids

    scan = _on_mesh(scan, mesh,
                    (P("shard"), P("shard"), P("shard"), P(), P()),
                    (P("shard"), P("shard")))

    def call(qs, cs, sts, pt, qn):
        _TRACE_COUNT[0] += 1             # python side-effect: trace time only
        return scan(qs, cs, sts, pt, qn)
    return jax.jit(call)


def sharded_quant_filtered_topk(q, codes, st, scales, filt: Optional[Filter],
                                k: int, metric: str = "l2",
                                use_kernel: bool = True, tq: int = 64,
                                tn: int = 256, m: Optional[int] = None):
    """Shard-parallel fused *asymmetric-distance* filtered top-k over int8
    segment codes.

    ``q`` is ``[bq, d]`` fp32; ``codes`` / ``st`` / ``scales`` are
    ``[g, dq, n]`` int8 / ``[g, mq, n]`` fp32 / ``[g, dq]`` fp32 stacks of
    ``g`` equal-capacity shards in the transposed quant layout
    (``dq = ceil(d / 32) * 32`` code sublanes, ``mq = quant_meta_rows(m)``
    metadata sublanes whose last row carries the dequantized squared
    norms; padding columns hold ``PAD_META`` metadata and fail every
    predicate).  Per shard the scale vector is folded into the query
    (``(q * scale) . codes == q . dequantize(codes)``) so the database is
    only ever touched at int8 — 4x fewer HBM bytes on the scan.

    Returns ``(ids [g, bq, k], dists [g, bq, k])`` with shard-local column
    ids (-1 for misses) and ascending distances equal to the exact fp32
    distance against the *dequantized* vectors — an over-fetched candidate
    list for the downstream exact rerank (``repro.quant.rerank``), merged
    exactly like the fp32 shard lists.

    ``m`` is the real metadata dimension and is required (``st`` is always
    padded, so it cannot be inferred): filter encoding and the jnp
    fallback must see only the live sublanes.
    """
    if m is None:
        # st always arrives padded to quant_meta_rows(m) sublanes, so the
        # real metadata dimension cannot be inferred from its shape (the
        # fp32 sibling's ss may be unpadded, hence its optional m)
        raise ValueError("sharded_quant_filtered_topk requires m= (the "
                         "real metadata dimension)")
    q = jnp.asarray(q, jnp.float32)
    codes = jnp.asarray(codes, jnp.int8)
    st = jnp.asarray(st, jnp.float32)
    scales = jnp.asarray(scales, jnp.float32)
    bq, d = q.shape
    g, dq, n = codes.shape
    mq = st.shape[1]
    m = int(m)
    qd = jnp.pad(q, ((0, 0), (0, dq - d))) if dq > d else q[:, :dq]
    qn = jnp.sum(q * q, axis=1)
    qs = qd[None, :, :] * scales[:, None, :]        # scale-folded queries
    enc = encode_filter(filt, m) if use_kernel else None
    if enc is None:
        # jnp fallback mirroring sharded_filtered_topk's (arbitrary Filter
        # objects, incl. polygons) over dequantized distances
        def one(qs_g, c_g, st_g):
            cf = c_g.astype(jnp.float32)
            ip = jnp.matmul(qs_g, cf, precision=jax.lax.Precision.HIGHEST)
            if metric == "l2":
                dmat = st_g[-1, :][None, :] - 2.0 * ip + qn[:, None]
            else:
                dmat = -ip
            ok = st_g[0, :] < _POS
            if filt is not None:
                ok &= filt.contains(st_g[:m, :].T)
            dmat = jnp.where(ok[None, :], dmat, jnp.inf)
            neg, ids = jax.lax.top_k(-dmat, min(k, n))
            dd = -neg
            return jnp.where(jnp.isfinite(dd), ids, -1), dd
        ids, dd = jax.vmap(one)(qs, codes, st)
        return ids, dd
    kind, params = enc
    kpad = _next_pow2(max(k, 8))
    tn = max(tn, kpad)
    qsp = _pad_to(qs, 1, tq, 0.0)
    cp = _pad_to(codes, 2, tn, 0)
    stp = _pad_to(st, 2, tn, _PAD_META)
    qnp = _pad_to(qn, 0, tq, 0.0)
    pt = jnp.asarray(params[:, :mq])
    _note_warm_sig(("int8", kind, kpad, metric, tq, tn,
                    int(qsp.shape[1]), dq, mq))
    dd, ids = _sharded_quant_dispatch(kind, kpad, metric, tq, tn,
                                      block_mesh(cp))(qsp, cp, stp, pt, qnp)
    return ids[:, :bq, :k], dd[:, :bq, :k]


def rerank_width(part_width: int, parts: int) -> int:
    """Candidate slots a rerank of ``parts`` candidate lists of up to
    ``part_width`` each is padded to: their sum rounded up to the lane
    width, so the program's shape follows the view's bucket count and
    never the candidates found."""
    return round_up(int(part_width) * int(parts), 128)


@functools.lru_cache(maxsize=None)
def _rerank_dispatch(k: int, metric: str):
    """One jitted exact rerank per (k, metric): float32 distances of each
    query row's gathered candidate vectors at ``Precision.HIGHEST`` (the
    expansion ``||x||^2 - 2 x.q + ||q||^2`` of the exact scan), invalid
    slots at +inf, then ``lax.top_k``, which breaks distance ties toward
    the lower slot.  Shapes follow the arguments only: padded query rows,
    padded candidate slots and the dimension."""
    def call(q, xs, valid):
        _TRACE_COUNT[0] += 1             # python side-effect: trace time only
        ip = jnp.einsum("bwd,bd->bw", xs, q,
                        precision=jax.lax.Precision.HIGHEST)
        if metric == "l2":
            d = (jnp.sum(xs * xs, axis=2) - 2.0 * ip
                 + jnp.sum(q * q, axis=1)[:, None])
        else:                             # inner product, negated
            d = -ip
        neg, pos = jax.lax.top_k(-jnp.where(valid, d, jnp.inf), k)
        return pos, -neg
    return jax.jit(call)


def rerank_topk(q, xs, valid, k: int, metric: str = "l2",
                part_width: Optional[int] = None):
    """Exact top-``k`` of each query row's candidate slots: ``q [b_pad,
    d]``, ``xs [b_pad, w_pad, d]`` float32 vectors and ``valid [b_pad,
    w_pad]`` (uploaded, or numpy to be uploaded with the call).  Returns
    device ``(slots [b_pad, kk], dists [b_pad, kk])``, ``kk = min(k,
    w_pad)``, nearest first, +inf past the valid slots.

    ``part_width`` (the width of one bucket's candidate list) records the
    signature in the warm registry, so that a pack that gains a bucket
    pre-traces the wider rerank (:func:`warm_sharded_shapes`)."""
    b_pad, w_pad, d = (int(n) for n in xs.shape)
    kk = min(int(k), w_pad)
    if part_width is not None:
        _note_warm_sig(("rerank", metric, int(k), b_pad, int(part_width),
                        d))
    return _rerank_dispatch(kk, metric)(q, xs, valid)


def _warm_rerank(sig: tuple, buckets: int) -> int:
    """Pre-trace a recorded rerank signature at the widths of views of 1
    to ``buckets`` buckets; returns the number of widths warmed."""
    _, metric, k, b_pad, part_width, d = sig
    widths = sorted({rerank_width(part_width, p)
                     for p in range(1, buckets + 1)})
    for w in widths:
        _rerank_dispatch(min(k, w), metric)(
            jnp.zeros((b_pad, d), jnp.float32),
            jnp.zeros((b_pad, w, d), jnp.float32),
            jnp.zeros((b_pad, w), bool))
    return len(widths)


def exact_filtered_search(q, x, s, filt: Optional[Filter], k: int,
                          metric: str = "l2", **kw):
    """Ground-truth generator: exact filtered top-k at kernel speed."""
    return filtered_topk(q, x, s, filt, k, metric=metric, **kw)
