"""Mesh-sharded sealed-segment search: segments × shards, bucketed by size.

Each sealed segment's live point set is partitioned round-robin into
``n_shards`` equal-capacity shards and answered by the fused
filtered-top-k kernel (``kernels.ops.sharded_filtered_topk``) over a
stacked ``[rows, cap, ·]`` device block, followed by an exact merge of the
shard-local ``(gid, dist)`` top-k lists.

Two pack layouts exist:

* :class:`BucketedShardPack` (the default serving structure) groups
  segments into **capacity buckets** — power-of-two multiples of the
  kernel tile (``cap_multiple``) — so a jumbo post-compaction segment pads
  only its own bucket, never the small ones.  The pack is **incrementally
  maintained**: a seal appends one segment's rows into its bucket with a
  ``dynamic_update_slice`` (the block grows geometrically, so uploads are
  amortized O(changed segment)), a compaction publish removes the merged
  inputs and inserts the output into its (likely larger) bucket, an expiry
  tombstones rows without touching device data, and deletes scatter the
  ``PAD_META`` sentinel into the metadata block.  All device updates are
  *functional* (new ``jnp`` arrays, shared buffers): an in-flight query
  holding a :class:`PackView` keeps reading the arrays it captured, which
  is what makes delta application safe against the owner's epoch/lock
  machinery.  A full rebuild happens only on cold start (first sharded
  query, restore from a snapshot) or when delta application fails.

* :class:`ShardPack` — the legacy monolithic layout (one block, every
  shard padded to the single largest shard's capacity), rebuilt whole per
  epoch.  Kept for A/B benchmarking (``StreamConfig(incremental_pack=
  False)``) and as the simplest exactness oracle.

Placed on a mesh with a ``"shard"`` axis (``make_shard_mesh``), the stacked
arrays are partitioned across devices along the shard axis, so each device
scans only its resident shards and only the tiny ``[rows, b, k]`` candidate
lists cross the interconnect for the merge — the TigerVector-style
decoupling of partitioned vector storage from query fan-out.

Exactness: every shard computes the same fp32 distance the monolithic
kernel would for the same point, each true global top-k member is by
definition inside its own shard's top-k, and global ids are disjoint across
shards — so concatenating the per-shard (and per-bucket) lists and taking
the global top-k reproduces the single-device result bit-for-bit.

Quantized read path (``quantize="int8"``): a bucketed pack can instead hold
**int8 segment codes** in a transposed layout (``[rows, dq, cap]`` codes +
``[rows, mq, cap]`` metadata-with-norms, see ``repro.kernels.quant_topk``)
— ~4x fewer vector bytes and ~16x fewer metadata bytes on device than the
fp32 blocks.  The per-segment scales ride the same functional delta
protocol, the scan over-fetches ``rerank_multiple * k`` candidates per
bucket with asymmetric (fp32 query × int8 code) distances, and the caller
reranks the union exactly at fp32 (``repro.quant.rerank``) before the
standard ``(dist, gid)`` merge.  With ``quantize=None`` nothing changes:
the fp32 blocks and kernel path are byte-for-byte the pre-quantization
ones.
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

from ..core import Filter
from ..kernels import (PAD_META, dispatch_trace_count, next_pow2,
                       quant_meta_rows, round_up, sharded_filtered_topk,
                       sharded_filtered_topk_grouped,
                       sharded_quant_filtered_topk)
from ..kernels.ops import encode_filter, place_rows
from ..obs.trace import NULL_TRACE, block_ready

__all__ = ["BucketedShardPack", "PackView", "SegmentShardSource",
           "ShardPack", "bucket_cap_for", "bucket_graph_seeds",
           "build_bucketed_pack", "build_shard_pack", "host_topk",
           "make_shard_mesh", "pack_search", "pack_search_blocks",
           "pack_search_blocks_grouped"]

_MPAD = 128                      # metadata lane padding (kernel layout)


@dataclasses.dataclass(frozen=True)
class SegmentShardSource:
    """One segment's live points, ready to be sharded (plain arrays so this
    module stays import-independent of ``repro.streaming``).

    ``codes`` / ``scales`` / ``xsq`` carry the segment's int8 codec payload
    (rows parallel to ``x``) when the owner runs the quantized read path;
    a quantized pack falls back to encoding on the fly when they are
    absent (e.g. sources rebuilt from a pre-quantization snapshot).
    """

    seg_id: int
    x: np.ndarray                # [n, d] fp32 live vectors
    s: np.ndarray                # [n, m] metadata
    gids: np.ndarray             # [n] int64 global ids
    t_min: float
    t_max: float
    codes: Optional[np.ndarray] = None    # [n, d] int8 segment codes
    scales: Optional[np.ndarray] = None   # [d] fp32 per-dim scales
    xsq: Optional[np.ndarray] = None      # [n] fp32 dequantized sq. norms
    nbrs: Optional[np.ndarray] = None     # [n, deg] int32 local adjacency
    entries: Optional[np.ndarray] = None  # [e] int32 local entry points


def make_shard_mesh(n_devices: Optional[int] = None) -> Mesh:
    """A 1-D device mesh with axis ``"shard"`` over (up to) ``n_devices``.

    On a single-device host this degenerates to a mesh of one — the pack
    code path is identical, which is how the sharded search is exercised in
    CI while production runs hand in a real multi-device mesh.
    """
    devs = jax.devices()
    n = len(devs) if n_devices is None else min(int(n_devices), len(devs))
    return Mesh(np.asarray(devs[:n]).reshape(n), ("shard",),
                axis_types=(AxisType.Auto,))


@dataclasses.dataclass
class ShardPack:
    """Stacked, padded, device-resident shards of a set of sealed segments.

    A pack is immutable in shape: built once per segment-list generation
    (``epoch``) and reused for every query until the segment list changes.
    Deletions between rebuilds are applied with :meth:`mark_dead` (metadata
    sentinel overwrite + lazy re-upload) — no restacking.
    """

    epoch: int
    n_shards: int                    # shards per segment
    m: int                           # real metadata dimension
    seg_ids: np.ndarray              # [g] owning segment id per pack row
    t_min: np.ndarray                # [g] owning segment's time span
    t_max: np.ndarray
    x: jnp.ndarray                   # [g, cap, dpad] device stack
    gids_dev: jnp.ndarray            # [g, cap] int32 (-1 padding)
    _s_host: np.ndarray              # [g, cap, MPAD] host master copy
    _sharding: Optional[NamedSharding]
    _gid_sorted: np.ndarray          # sorted live gids (for mark_dead)
    _gid_flat_pos: np.ndarray        # flat (row*cap + col) per sorted gid
    _s_dev: Optional[jnp.ndarray] = None

    @property
    def n_rows(self) -> int:
        """Pack rows = segments × shards-per-segment."""
        return int(self.x.shape[0])

    @property
    def cap(self) -> int:
        """Padded per-shard point capacity."""
        return int(self.x.shape[1])

    @property
    def nbytes(self) -> int:
        """Device bytes held by the pack (vectors + metadata + gids)."""
        return int(self.x.size * 4 + self._s_host.size * 4
                   + self.gids_dev.size * 4)

    def _put(self, arr: np.ndarray) -> jnp.ndarray:
        if self._sharding is not None:
            return jax.device_put(arr, self._sharding)
        return jnp.asarray(arr)

    @property
    def s_dev(self) -> jnp.ndarray:
        """Device metadata stack, re-uploaded lazily after `mark_dead`."""
        if self._s_dev is None:
            self._s_dev = self._put(self._s_host)
        return self._s_dev

    def mark_dead(self, gids: Sequence[int]) -> int:
        """Mask points by global id: their metadata rows become ``PAD_META``
        so every subsequent query's predicate rejects them.  Returns the
        number of pack rows touched; the device copy refreshes on the next
        query (one upload, not one per delete)."""
        g = np.asarray(gids, np.int64)
        if len(g) == 0 or len(self._gid_sorted) == 0:
            return 0
        pos = np.searchsorted(self._gid_sorted, g)
        pos_c = np.clip(pos, 0, len(self._gid_sorted) - 1)
        ok = self._gid_sorted[pos_c] == g
        flat = self._gid_flat_pos[pos_c[ok]]
        if len(flat) == 0:
            return 0
        rows, cols = np.divmod(flat, self.cap)
        self._s_host[rows, cols, :] = PAD_META
        self._s_dev = None
        return len(flat)

    def sync_alive(self, alive: np.ndarray) -> int:
        """Mask every packed point whose gid is dead in ``alive`` (the
        manager's liveness bitmap).  Used once at pack installation to catch
        deletions that raced the build; later deletions arrive one-by-one
        through :meth:`mark_dead`."""
        dead = self._gid_sorted[~alive[self._gid_sorted]]
        return self.mark_dead(dead)

    def active_rows(self, t_lo: float, t_hi: float) -> np.ndarray:
        """[g] bool — pack rows whose segment span overlaps [t_lo, t_hi]."""
        return (self.t_max >= t_lo) & (self.t_min <= t_hi)


def build_shard_pack(sources: Sequence[SegmentShardSource], n_shards: int,
                     epoch: int = 0, mesh: Optional[Mesh] = None,
                     cap_multiple: int = 256) -> ShardPack:
    """Partition each segment round-robin into ``n_shards`` shards and stack
    all of them into one padded device pack.

    ``cap_multiple`` matches the kernel's candidate-tile size so row padding
    is settled here once instead of on every query.  With ``mesh`` given,
    the stack is placed with the shard axis partitioned across the mesh
    (requires ``g % mesh devices == 0``, which holds whenever ``n_shards``
    is a multiple of the device count).
    """
    n_shards = max(int(n_shards), 1)
    if not sources:
        raise ValueError("build_shard_pack needs at least one segment")
    m = sources[0].s.shape[1]
    d = sources[0].x.shape[1]
    dpad = round_up(d, 128)
    per_row: List[Tuple[int, np.ndarray, SegmentShardSource]] = []
    for src in sources:
        order = np.arange(len(src.gids))
        for sh in range(n_shards):
            per_row.append((src.seg_id, order[sh::n_shards], src))
    g = len(per_row)
    cap = round_up(max(len(idx) for _, idx, _ in per_row), cap_multiple)
    x = np.zeros((g, cap, dpad), np.float32)
    s = np.full((g, cap, _MPAD), PAD_META, np.float32)
    gid = np.full((g, cap), -1, np.int32)
    seg_ids = np.zeros(g, np.int64)
    t_min = np.zeros(g, np.float64)
    t_max = np.zeros(g, np.float64)
    for row, (sid, idx, src) in enumerate(per_row):
        nn = len(idx)
        x[row, :nn, :d] = src.x[idx]
        s[row, :nn, :] = 0.0
        s[row, :nn, :m] = src.s[idx]
        gid[row, :nn] = src.gids[idx]
        seg_ids[row] = sid
        t_min[row], t_max[row] = src.t_min, src.t_max
    sharding = None
    if mesh is not None and g % mesh.devices.size == 0:
        sharding = NamedSharding(mesh, P("shard", None, None))
    flat_gid = gid.reshape(-1).astype(np.int64)
    live = np.nonzero(flat_gid >= 0)[0]
    order = np.argsort(flat_gid[live])
    pack = ShardPack(
        epoch=epoch, n_shards=n_shards, m=m, seg_ids=seg_ids,
        t_min=t_min, t_max=t_max,
        x=jnp.zeros(1), gids_dev=jnp.zeros(1),   # placed below
        _s_host=s, _sharding=sharding,
        _gid_sorted=flat_gid[live][order], _gid_flat_pos=live[order])
    pack.x = pack._put(x)
    gid_sharding = (NamedSharding(mesh, P("shard", None))
                    if sharding is not None else None)
    pack.gids_dev = (jax.device_put(gid, gid_sharding)
                     if gid_sharding is not None else jnp.asarray(gid))
    return pack


# ---------------------------------------------------------------------------
# Size-bucketed, incrementally maintained pack
# ---------------------------------------------------------------------------
def bucket_cap_for(n_points: int, n_shards: int,
                   cap_multiple: int = 256) -> int:
    """Padded per-shard row capacity class for a segment of ``n_points``
    live rows: the smallest power-of-two multiple of ``cap_multiple`` that
    fits the segment's largest round-robin shard.  Power-of-two classes
    bound padding waste at 2× the tile-aligned shard size while keeping the
    number of distinct device-block shapes (= jit cache entries) to
    O(log max-segment)."""
    n_shards = max(int(n_shards), 1)
    shard_rows = -(-max(int(n_points), 1) // n_shards)
    return cap_multiple * next_pow2(-(-shard_rows // cap_multiple))


@jax.jit
def _write_rows(block, rows, row0):
    """Functional row-range write: ``block[row0:row0+len(rows)] = rows``.
    Returns a new array sharing unchanged buffers — in-flight views of the
    old block stay valid."""
    start = (row0,) + (0,) * (block.ndim - 1)
    return jax.lax.dynamic_update_slice(block, rows, start)


@jax.jit
def _mask_meta(s, rows, cols):
    """Functional scatter of the ``PAD_META`` sentinel into metadata rows
    ``(rows[i], cols[i])`` — how deletions reach the device block without a
    re-upload (duplicate indices are fine: every write stores the same
    sentinel)."""
    return s.at[rows, cols, :].set(PAD_META)


@jax.jit
def _mask_meta_t(st, rows, cols):
    """Transposed-layout sibling of :func:`_mask_meta`: sets every metadata
    sublane (including the xsq row) of the quantized block's columns
    ``(rows[i], :, cols[i])`` to ``PAD_META``, so every predicate —
    including ``filt=None`` — rejects the point."""
    return st.at[rows, :, cols].set(PAD_META)


@dataclasses.dataclass
class _SegEntry:
    """Where one segment's points live inside the pack (host bookkeeping
    for deltas and deletions)."""

    seg_id: int
    cap: int                     # owning bucket key
    slot: int                    # slot index inside the bucket
    gid_sorted: np.ndarray       # sorted gids of the segment's packed rows
    rows_sorted: np.ndarray      # bucket row per sorted gid
    cols_sorted: np.ndarray      # bucket column per sorted gid
    entry_pos: Optional[np.ndarray] = None  # flattened graph entry positions


@dataclasses.dataclass
class _Bucket:
    """One capacity class: a padded ``[rows, cap, ·]`` block whose rows are
    allocated in slots of ``n_shards`` consecutive rows.

    Exactly one of the two layouts is populated: the fp32 blocks
    (``x`` / ``s``) or the quantized transposed blocks (``codes`` / ``st``
    / ``scales``) — never both, which is where the quantized pack's device
    bytes go from ~1 KiB/point to ~70 B/point.

    Residency (tiered storage): a **resident** bucket holds its blocks as
    device ``jnp`` arrays; an evicted one holds byte-identical host ``np``
    copies in ``host`` instead (and ``gids_h`` doubles as its gid block).
    Cold mutations are copy-on-write — the touched host array is replaced,
    never edited in place — so a :class:`BucketView` captured before the
    mutation keeps reading the pre-mutation bytes, exactly like the
    functional device updates.  ``gen`` counts mutations/transitions so an
    off-lock admission upload can detect it went stale before installing.
    """

    cap: int
    seg_ids: np.ndarray          # [rows] int64 owning segment (-1 = free)
    t_min: np.ndarray            # [rows] owning segment's span (+inf free)
    t_max: np.ndarray            # [rows] (-inf free)
    free_slots: List[int]
    gids_h: np.ndarray           # [rows, cap] int32 host mirror (-1 padding)
    gids: Optional[jnp.ndarray] = None    # [rows, cap] int32 (resident only)
    x: Optional[jnp.ndarray] = None       # [rows, cap, dpad] fp32
    s: Optional[jnp.ndarray] = None       # [rows, cap, MPAD] fp32
    codes: Optional[jnp.ndarray] = None   # [rows, dq, cap] int8
    st: Optional[jnp.ndarray] = None      # [rows, mq, cap] fp32 (+xsq row)
    scales: Optional[jnp.ndarray] = None  # [rows, dq] fp32 per-dim scales
    nbrs: Optional[jnp.ndarray] = None    # [rows, cap, degp] int32 adjacency
    resident: bool = True
    host: Optional[Dict[str, np.ndarray]] = None  # cold block arrays
    gen: int = 0                 # bumps on every mutation / tier transition

    @property
    def n_rows(self) -> int:
        """Allocated rows (live + free) in this bucket's block."""
        return int(self.gids_h.shape[0])

    def _arrs(self) -> Dict[str, object]:
        """The populated block arrays (device when resident, host when
        cold), keyed by field name; the gid block rides under ``gids``."""
        if not self.resident:
            return dict(self.host, gids=self.gids_h)
        names = ("codes", "st", "scales") if self.codes is not None \
            else ("x", "s")
        out = {name: getattr(self, name) for name in names}
        if self.nbrs is not None:
            out["nbrs"] = self.nbrs
        out["gids"] = self.gids
        return out

    @property
    def full_nbytes(self) -> int:
        """Bytes this bucket's blocks occupy (on whichever tier they
        live) — also the upload size of admitting it."""
        return sum(int(a.size) * a.dtype.itemsize
                   for a in self._arrs().values())

    @property
    def nbytes(self) -> int:
        """Device bytes held by this bucket (0 when evicted)."""
        return self.full_nbytes if self.resident else 0

    @property
    def host_nbytes(self) -> int:
        """Host bytes held by this bucket's cold copy (0 when resident)."""
        return 0 if self.resident else self.full_nbytes


@dataclasses.dataclass(frozen=True)
class BucketView:
    """Immutable per-bucket snapshot handed to the lock-free query path.

    The ``jnp`` arrays are captured by reference (functional updates never
    mutate them); the host-side row metadata is copied because delta
    application edits it in place.  Quantized buckets expose
    ``codes`` / ``st`` / ``scales`` instead of ``x`` / ``s``.

    A **cold** bucket (``resident=False`` — its block was evicted under the
    device budget, see ``streaming/tiering.py``) exposes the same fields as
    host ``np`` arrays holding byte-identical content; dispatching them
    through the same kernels streams the block to the device transiently,
    so cold answers are bit-for-bit the resident ones.  ``stage_bytes`` is
    what admitting the block would upload (the planner's staging cost) and
    ``fill`` counts filled slots per row (the planner's live-point
    estimate)."""

    cap: int
    gids: jnp.ndarray
    seg_ids: np.ndarray
    t_min: np.ndarray
    t_max: np.ndarray
    x: Optional[jnp.ndarray] = None
    s: Optional[jnp.ndarray] = None
    codes: Optional[jnp.ndarray] = None
    st: Optional[jnp.ndarray] = None
    scales: Optional[jnp.ndarray] = None
    nbrs: Optional[jnp.ndarray] = None    # [rows, cap, degp] int32 adjacency
    # per-packed-segment graph entry points for the stitched traversal:
    # ((row0, flattened positions), ...) — row0 identifies the owning slot's
    # first bucket row, so the temporal active mask decides seed inclusion
    entries: Tuple[Tuple[int, np.ndarray], ...] = ()
    resident: bool = True
    stage_bytes: int = 0                  # device bytes if admitted
    fill: Optional[np.ndarray] = None     # [rows] filled slots per row

    @property
    def quantized(self) -> bool:
        """Whether this bucket holds int8 codes instead of fp32 blocks."""
        return self.codes is not None

    @property
    def graph_ready(self) -> bool:
        """Whether this bucket carries a stitched graph block with at least
        one segment exposing entry points (the graph read path's gate)."""
        return self.nbrs is not None and any(
            len(pos) for _, pos in self.entries)

    def active_rows(self, t_lo: float, t_hi: float) -> np.ndarray:
        """[rows] bool — allocated rows whose segment span overlaps the
        query window.  All-False means the whole device block is pruned
        (no kernel dispatch for this bucket)."""
        return ((self.seg_ids >= 0) & (self.t_max >= t_lo)
                & (self.t_min <= t_hi))


@dataclasses.dataclass(frozen=True)
class PackView:
    """Consistent snapshot of a :class:`BucketedShardPack` at one epoch —
    what queries actually search while deltas keep mutating the pack."""

    epoch: int
    n_shards: int
    m: int
    buckets: Tuple[BucketView, ...]
    nbytes: int                           # device-resident bytes
    quantize: Optional[str] = None
    host_nbytes: int = 0                  # cold (evicted) bucket bytes

    @property
    def n_rows(self) -> int:
        """Total allocated pack rows across buckets."""
        return sum(b.gids.shape[0] for b in self.buckets)


class BucketedShardPack:
    """Size-bucketed, delta-maintained device pack of sealed segments.

    Segments land in capacity buckets (:func:`bucket_cap_for`); each bucket
    owns one padded ``[rows, cap, ·]`` device block that grows
    geometrically in slots of ``n_shards`` rows.  Mutations —
    :meth:`add_segment` (seal), :meth:`remove_segment` (compaction victim /
    expiry), :meth:`mark_dead` (deletes) — are **functional** on the device
    arrays, so a :class:`PackView` captured before a mutation keeps
    answering from the pre-mutation state.  The owner (``SegmentManager``)
    serializes mutations and view capture under its lock and stamps
    ``epoch`` after each applied delta.
    """

    def __init__(self, n_shards: int, d: int, m: int, epoch: int = 0,
                 mesh: Optional[Mesh] = None, cap_multiple: int = 256,
                 quantize: Optional[str] = None, metrics=None,
                 graph_degree: Optional[int] = None,
                 resident_default: bool = True):
        from ..obs.metrics import NULL_REGISTRY
        self.metrics = NULL_REGISTRY if metrics is None else metrics
        # tiered storage: buckets created while False start cold (host
        # arrays, no device upload) — how a budgeted cold build / restore
        # avoids staging the whole corpus before the first query
        self.resident_default = bool(resident_default)
        self.n_shards = max(int(n_shards), 1)
        self.d = int(d)
        self.m = int(m)
        self.dpad = round_up(d, 128)
        self.dq = round_up(d, 32)           # int8 code sublane padding
        self.mq = quant_meta_rows(m)         # meta sublanes (+1 xsq row)
        # graph read path: when set, every bucket also carries a
        # [rows, cap, degp] adjacency block of flattened bucket positions
        # (row * cap + col), staged from each segment's sealed CubeGraph
        # layer at add time; None keeps the pack byte-for-byte scan-only
        self.graph_degree = None if not graph_degree else int(graph_degree)
        self.degp = (round_up(max(self.graph_degree, 1), 8)
                     if self.graph_degree else 0)
        self.epoch = int(epoch)
        self.mesh = mesh
        self.cap_multiple = max(int(cap_multiple), 8)
        self.quantize = quantize
        self.buckets: Dict[int, _Bucket] = {}
        self._entries: Dict[int, _SegEntry] = {}
        # resilience: when the manager installs a FaultInjector it is
        # threaded here so the admission trio's named fault points fire
        # (streaming/resilience.py); None — the default — costs nothing
        self.fault_hook = None
        # block shapes created since the last drain — the manager hands
        # them to kernels.ops.warm_sharded_shapes so a grown bucket's
        # dispatch is pre-traced off the query path
        self._new_shapes: List[dict] = []

    # -- geometry ------------------------------------------------------
    @property
    def n_segments(self) -> int:
        """Segments currently packed."""
        return len(self._entries)

    @property
    def n_rows(self) -> int:
        """Total allocated pack rows (live + free) across buckets."""
        return sum(b.n_rows for b in self.buckets.values())

    @property
    def nbytes(self) -> int:
        """Device bytes held by all resident bucket blocks."""
        return sum(b.nbytes for b in self.buckets.values())

    @property
    def host_nbytes(self) -> int:
        """Host bytes held by all evicted (cold) bucket blocks."""
        return sum(b.host_nbytes for b in self.buckets.values())

    def bucket_stats(self) -> Dict[int, Dict[str, int]]:
        """Per-bucket occupancy:
        ``{cap: {rows, live_rows, segments, resident}}``."""
        out = {}
        for cap, b in sorted(self.buckets.items()):
            out[cap] = {"rows": b.n_rows,
                        "live_rows": int((b.seg_ids >= 0).sum()),
                        "segments": int(len({int(s) for s in b.seg_ids
                                             if s >= 0})),
                        "resident": int(b.resident)}
        return out

    # -- placement -----------------------------------------------------
    def _place(self, arr: jnp.ndarray) -> jnp.ndarray:
        """(Re-)pin a bucket block's sharding after a functional update:
        shard-axis partitioned when a mesh is attached.  :meth:`_init_slots`
        makes every block's row count divide the device count; a count
        that does not raises rather than leave the block on one device."""
        return place_rows(arr, self.mesh)

    def _new_block(self, rows: int, cap: int):
        """Fresh zero/PAD device arrays for ``rows`` bucket rows, in the
        layout the pack's mode needs (fp32 blocks or int8 code blocks),
        plus the adjacency block when the graph read path is on."""
        g = self._place(jnp.full((rows, cap), -1, jnp.int32))
        if self.quantize:
            c = self._place(jnp.zeros((rows, self.dq, cap), jnp.int8))
            st = self._place(jnp.full((rows, self.mq, cap), PAD_META,
                                      jnp.float32))
            sc = self._place(jnp.zeros((rows, self.dq), jnp.float32))
            out = dict(codes=c, st=st, scales=sc, gids=g)
        else:
            x = self._place(jnp.zeros((rows, cap, self.dpad), jnp.float32))
            s = self._place(jnp.full((rows, cap, _MPAD), PAD_META,
                                     jnp.float32))
            out = dict(x=x, s=s, gids=g)
        if self.graph_degree:
            out["nbrs"] = self._place(jnp.full((rows, cap, self.degp), -1,
                                               jnp.int32))
        return out

    def _new_block_host(self, rows: int, cap: int) -> Dict[str, np.ndarray]:
        """Host (``np``) twin of :meth:`_new_block` for cold buckets —
        byte-identical zero/PAD content, no device upload, and no ``gids``
        entry (the always-maintained ``gids_h`` mirror plays that role)."""
        if self.quantize:
            out = dict(codes=np.zeros((rows, self.dq, cap), np.int8),
                       st=np.full((rows, self.mq, cap), PAD_META,
                                  np.float32),
                       scales=np.zeros((rows, self.dq), np.float32))
        else:
            out = dict(x=np.zeros((rows, cap, self.dpad), np.float32),
                       s=np.full((rows, cap, _MPAD), PAD_META, np.float32))
        if self.graph_degree:
            out["nbrs"] = np.full((rows, cap, self.degp), -1, np.int32)
        return out

    def _note_shape(self, rows: int, cap: int) -> None:
        """Record a freshly created block geometry for compile warming.
        The mesh rides along so the warm-up's zero blocks are placed with
        the same sharding as the real blocks — jit caches per input
        sharding, so an unsharded warm would not pre-compile the
        mesh-placed dispatch."""
        if self.quantize:
            self._new_shapes.append({"mode": "int8", "rows": rows,
                                     "cap": cap, "dq": self.dq,
                                     "mq": self.mq, "mesh": self.mesh})
        else:
            self._new_shapes.append({"mode": "fp32", "rows": rows,
                                     "cap": cap, "dpad": self.dpad,
                                     "mesh": self.mesh})

    def drain_warm_shapes(self) -> List[dict]:
        """Pop the block geometries created since the last drain (call
        under the owner's lock; feed to
        ``kernels.ops.warm_sharded_shapes`` off the query path).  Quantized
        ones carry the pack's bucket count, which sets the rerank's
        candidate width."""
        out, self._new_shapes = self._new_shapes, []
        for spec in out:
            if spec["mode"] == "int8":
                spec["buckets"] = len(self.buckets)
        return out

    def _init_slots(self) -> int:
        """Slot count for a fresh bucket block: the smallest number whose
        row total divides the mesh device count, so every bucket block is
        shard-axis partitionable for *any* ``n_shards`` (doubling growth
        preserves divisibility).  1 without a mesh."""
        if self.mesh is None:
            return 1
        nd = int(self.mesh.devices.size)
        return nd // math.gcd(self.n_shards, nd)

    def _bucket_for(self, cap: int) -> _Bucket:
        b = self.buckets.get(cap)
        if b is None:
            slots = self._init_slots()
            rows = slots * self.n_shards
            kw = dict(seg_ids=np.full(rows, -1, np.int64),
                      t_min=np.full(rows, np.inf, np.float64),
                      t_max=np.full(rows, -np.inf, np.float64),
                      free_slots=list(range(slots)),
                      gids_h=np.full((rows, cap), -1, np.int32))
            if self.resident_default:
                b = _Bucket(cap, **kw, **self._new_block(rows, cap))
                self._note_shape(rows, cap)
            else:
                b = _Bucket(cap, **kw, resident=False,
                            host=self._new_block_host(rows, cap))
            self.buckets[cap] = b
        return b

    def _alloc_slot(self, b: _Bucket) -> int:
        """Pop the lowest free slot, doubling the block when none is left
        (geometric growth keeps appends amortized O(changed segment))."""
        if not b.free_slots:
            old_slots = b.n_rows // self.n_shards
            add_slots = max(old_slots, 1)
            add_rows = add_slots * self.n_shards
            if b.resident:
                add = self._new_block(add_rows, b.cap)
                for name, arr in add.items():
                    grown = jnp.concatenate([getattr(b, name), arr])
                    setattr(b, name, self._place(grown))
            else:
                add = self._new_block_host(add_rows, b.cap)
                host = dict(b.host)
                for name, arr in add.items():
                    host[name] = np.concatenate([host[name], arr])
                b.host = host
            b.gids_h = np.concatenate(
                [b.gids_h, np.full((add_rows, b.cap), -1, np.int32)])
            b.seg_ids = np.concatenate(
                [b.seg_ids, np.full(add_rows, -1, np.int64)])
            b.t_min = np.concatenate(
                [b.t_min, np.full(add_rows, np.inf, np.float64)])
            b.t_max = np.concatenate(
                [b.t_max, np.full(add_rows, -np.inf, np.float64)])
            b.free_slots.extend(range(old_slots, old_slots + add_slots))
            b.gen += 1
            if b.resident:
                self._note_shape(b.n_rows, b.cap)
        b.free_slots.sort()
        return b.free_slots.pop(0)

    # -- delta protocol ------------------------------------------------
    def _stage_fp32(self, src: SegmentShardSource, cap: int):
        """Host-stage one segment's fp32 rows as ``[n_shards, cap, ·]``
        blocks ready for the delta write."""
        n = len(src.gids)
        d = src.x.shape[1]
        xb = np.zeros((self.n_shards, cap, self.dpad), np.float32)
        sb = np.full((self.n_shards, cap, _MPAD), PAD_META, np.float32)
        for sh in range(self.n_shards):
            idx = np.arange(sh, n, self.n_shards)
            nn = len(idx)
            xb[sh, :nn, :d] = src.x[idx]
            sb[sh, :nn, :] = 0.0
            sb[sh, :nn, : self.m] = src.s[idx]
        return dict(x=xb, s=sb)

    def _stage_quant(self, src: SegmentShardSource, cap: int):
        """Host-stage one segment's int8 codes in the transposed quant
        layout (codes ``[n_shards, dq, cap]``, metadata+norms
        ``[n_shards, mq, cap]``, per-row scales).  Uses the segment's
        sealed codec payload when present; otherwise encodes on the fly
        (pre-quantization snapshot restored into a quantized config)."""
        from ..quant import encode_segment
        n = len(src.gids)
        d = src.x.shape[1]
        if src.codes is not None:
            codes, scales, xsq = src.codes, src.scales, src.xsq
        else:
            q = encode_segment(src.x, self.quantize)
            codes, scales, xsq = q.codes, q.scales, q.xsq
        cb = np.zeros((self.n_shards, self.dq, cap), np.int8)
        stb = np.full((self.n_shards, self.mq, cap), PAD_META, np.float32)
        scb = np.zeros((self.n_shards, self.dq), np.float32)
        scb[:, :d] = np.asarray(scales, np.float32)[None, :]
        for sh in range(self.n_shards):
            idx = np.arange(sh, n, self.n_shards)
            nn = len(idx)
            cb[sh, :d, :nn] = codes[idx].T
            stb[sh, :, :nn] = 0.0
            stb[sh, : self.m, :nn] = src.s[idx].T
            stb[sh, self.mq - 1, :nn] = xsq[idx]
        return dict(codes=cb, st=stb, scales=scb)

    def _stage_graph(self, src: SegmentShardSource, cap: int, row0: int):
        """Host-stage one segment's adjacency as a ``[n_shards, cap, degp]``
        block of *flattened bucket positions* (``row * cap + col``), plus
        the segment's entry points in the same coordinate space.

        Positions bake in the slot's ``row0``, so they survive later block
        doubling (cap is fixed per bucket; growth only appends rows).
        Segments packed without a graph payload (e.g. sources rebuilt from
        an old snapshot) stage an all ``-1`` block and no entries — the
        planner then keeps that bucket on the scan path."""
        n = len(src.gids)
        nb = np.full((self.n_shards, cap, self.degp), -1, np.int32)
        entry_pos = np.empty(0, np.int64)
        if src.nbrs is not None and n:
            l = np.arange(n)
            pos_of = ((row0 + l % self.n_shards) * cap
                      + l // self.n_shards).astype(np.int64)
            deg = min(src.nbrs.shape[1], self.degp)
            nbr = np.asarray(src.nbrs[:, :deg], np.int64)
            npos = np.where(nbr >= 0, pos_of[np.minimum(np.maximum(nbr, 0),
                                                        n - 1)],
                            -1).astype(np.int32)
            for sh in range(self.n_shards):
                idx = np.arange(sh, n, self.n_shards)
                nb[sh, : len(idx), :deg] = npos[idx]
            if src.entries is not None and len(src.entries):
                e = np.asarray(src.entries, np.int64)
                e = e[(e >= 0) & (e < n)]
                entry_pos = pos_of[e]
        return nb, entry_pos

    def add_segment(self, src: SegmentShardSource) -> None:
        """Append one segment's live points into its capacity bucket:
        O(segment) host staging + one ``dynamic_update_slice`` per device
        array — never touches other segments' rows."""
        n = len(src.gids)
        if n == 0:
            return
        if src.seg_id in self._entries:
            raise ValueError(f"segment {src.seg_id} is already packed")
        cap = bucket_cap_for(n, self.n_shards, self.cap_multiple)
        b = self._bucket_for(cap)
        slot = self._alloc_slot(b)
        row0 = slot * self.n_shards
        staged = (self._stage_quant(src, cap) if self.quantize
                  else self._stage_fp32(src, cap))
        entry_pos = None
        if self.graph_degree:
            staged["nbrs"], entry_pos = self._stage_graph(src, cap, row0)
        gb = np.full((self.n_shards, cap), -1, np.int32)
        for sh in range(self.n_shards):
            idx = np.arange(sh, n, self.n_shards)
            gb[sh, : len(idx)] = src.gids[idx]
        staged["gids"] = gb
        if b.resident:
            # delta upload volume: what this seal/publish actually shipped
            # to the device (the occupancy gauges are the owner's job — it
            # knows when a transition is complete)
            self.metrics.counter("pack_delta_bytes_total").inc(
                sum(arr.nbytes for arr in staged.values()))
            r0 = jnp.int32(row0)
            for name, block in staged.items():
                written = _write_rows(getattr(b, name), jnp.asarray(block),
                                      r0)
                setattr(b, name, self._place(written))
        else:
            # cold bucket: the delta lands in the host copy without forcing
            # an admission — copy-on-write so in-flight views of a reused
            # slot keep reading the pre-mutation bytes, mirroring the
            # functional device updates
            host = dict(b.host)
            for name, block in staged.items():
                if name == "gids":
                    continue
                arr = host[name].copy()
                arr[row0: row0 + self.n_shards] = block
                host[name] = arr
            b.host = host
        b.gids_h = b.gids_h.copy()
        b.gids_h[row0: row0 + self.n_shards] = gb
        b.gen += 1
        b.seg_ids[row0: row0 + self.n_shards] = src.seg_id
        b.t_min[row0: row0 + self.n_shards] = src.t_min
        b.t_max[row0: row0 + self.n_shards] = src.t_max
        order = np.argsort(src.gids, kind="stable")
        self._entries[src.seg_id] = _SegEntry(
            int(src.seg_id), cap, slot,
            np.asarray(src.gids, np.int64)[order],
            (row0 + order % self.n_shards).astype(np.int64),
            (order // self.n_shards).astype(np.int64),
            entry_pos=entry_pos)

    def remove_segment(self, seg_id: int) -> bool:
        """Tombstone one segment (compaction victim or expiry): host-only —
        the slot is freed and its rows drop out of every later view's
        active mask, so the stale device rows are never merged and get
        overwritten when the slot is reused."""
        e = self._entries.pop(int(seg_id), None)
        if e is None:
            return False
        b = self.buckets[e.cap]
        row0 = e.slot * self.n_shards
        b.seg_ids[row0: row0 + self.n_shards] = -1
        b.t_min[row0: row0 + self.n_shards] = np.inf
        b.t_max[row0: row0 + self.n_shards] = -np.inf
        b.free_slots.append(e.slot)
        b.gen += 1
        if not (b.seg_ids >= 0).any():
            # last live slot gone: release the whole capacity class, so a
            # retired jumbo bucket doesn't pin device memory at its
            # historical peak (in-flight views keep their own references;
            # a later segment of this class re-creates the bucket at one
            # slot and regrows geometrically)
            del self.buckets[e.cap]
        return True

    def mark_dead(self, gids: Sequence[int]) -> int:
        """Mask points by global id: their metadata rows become
        ``PAD_META`` (scattered functionally into each touched bucket's
        device block), so every subsequent view's predicate rejects them.
        Returns the number of pack positions masked."""
        g = np.asarray(gids, np.int64)
        if len(g) == 0:
            return 0
        g_lo, g_hi = int(g.min()), int(g.max())
        per_bucket: Dict[int, List[Tuple[np.ndarray, np.ndarray]]] = {}
        total = 0
        # per-segment lookup keeps the index maintainable in O(changed
        # segment) at add/remove time; the segment count itself is bounded
        # by the compaction policy, and the gid-range prefilter makes
        # non-overlapping segments (the common case — gids are
        # ingestion-ordered) an O(1) skip
        for e in self._entries.values():
            if len(e.gid_sorted) == 0 or e.gid_sorted[-1] < g_lo \
                    or e.gid_sorted[0] > g_hi:
                continue
            pos = np.searchsorted(e.gid_sorted, g)
            pos_c = np.clip(pos, 0, len(e.gid_sorted) - 1)
            ok = e.gid_sorted[pos_c] == g
            if not ok.any():
                continue
            sel = pos_c[ok]
            per_bucket.setdefault(e.cap, []).append(
                (e.rows_sorted[sel], e.cols_sorted[sel]))
            total += int(sel.size)
        for cap, hits in per_bucket.items():
            b = self.buckets[cap]
            rows = np.concatenate([r for r, _ in hits]).astype(np.int32)
            cols = np.concatenate([c for _, c in hits]).astype(np.int32)
            # pad the index vectors to a power of two (repeating the first
            # hit — the scatter is idempotent) so the jit cache sees
            # O(log n) distinct scatter shapes, not one per delete batch
            want = next_pow2(len(rows))
            pad = want - len(rows)
            if pad:
                rows = np.concatenate([rows, np.full(pad, rows[0], np.int32)])
                cols = np.concatenate([cols, np.full(pad, cols[0], np.int32)])
            if not b.resident:
                # same sentinel scatter, applied copy-on-write to the cold
                # host copy — a later admission uploads bytes identical to
                # what the device scatter would have produced
                key = "st" if self.quantize else "s"
                host = dict(b.host)
                arr = host[key].copy()
                if self.quantize:
                    arr[rows, :, cols] = PAD_META
                else:
                    arr[rows, cols, :] = PAD_META
                host[key] = arr
                b.host = host
            elif self.quantize:
                b.st = self._place(_mask_meta_t(b.st, jnp.asarray(rows),
                                                jnp.asarray(cols)))
            else:
                b.s = self._place(_mask_meta(b.s, jnp.asarray(rows),
                                             jnp.asarray(cols)))
            b.gen += 1
        return total

    def sync_alive(self, alive: np.ndarray) -> int:
        """Mask every packed point whose gid is dead in ``alive`` (the
        manager's liveness bitmap) — used once at cold-build installation
        to catch deletions that raced the build."""
        dead = [e.gid_sorted[~alive[e.gid_sorted]]
                for e in self._entries.values()]
        dead = np.concatenate(dead) if dead else np.empty(0, np.int64)
        return self.mark_dead(dead) if len(dead) else 0

    # -- tier transitions (tiered storage, streaming/tiering.py) -------
    def evict_bucket(self, cap: int) -> int:
        """Demote one resident bucket's device block to host ``np`` copies
        (call under the owner's lock).  In-flight views keep the device
        arrays they captured alive; new views of this bucket read the
        byte-identical host copy.  Returns the device bytes released."""
        b = self.buckets.get(cap)
        if b is None or not b.resident:
            return 0
        freed = b.nbytes
        host = {}
        names = ("codes", "st", "scales") if self.quantize else ("x", "s")
        for name in names + (("nbrs",) if self.graph_degree else ()):
            host[name] = np.asarray(getattr(b, name))
            setattr(b, name, None)
        b.gids = None
        b.host = host
        b.resident = False
        b.gen += 1
        return freed

    def _fault(self, point: str) -> None:
        """Fire the named fault point when an injector is attached (the
        manager threads its ``FaultInjector`` here via
        ``install_fault_injector``; None — the default — is free)."""
        if self.fault_hook is not None:
            self.fault_hook(point)

    def stage_admission(self, cap: int):
        """Host half of an admission: snapshot a cold bucket's host arrays
        (call under the owner's lock).  Returns ``(gen, arrays)`` or None
        when the bucket is missing / already resident.  Fault point
        ``admission.stage`` fires before the snapshot — a crash here
        mutates nothing."""
        self._fault("admission.stage")
        b = self.buckets.get(cap)
        if b is None or b.resident:
            return None
        arrs = dict(b.host)
        arrs["gids"] = b.gids_h
        return b.gen, arrs

    def upload_admission(self, staged):
        """Device half of an admission: place the staged host arrays
        (lock-free — the expensive upload happens here, off the owner's
        lock, mirroring ``compact_async``'s execute step).  Fault point
        ``admission.upload`` fires before the upload — a crash strands
        nothing (the staged host copy still lives in the bucket)."""
        self._fault("admission.upload")
        gen, arrs = staged
        return gen, {name: self._place(jnp.asarray(a))
                     for name, a in arrs.items()}

    def install_admission(self, cap: int, gen: int, dev) -> int:
        """Publish an uploaded admission iff the bucket is still cold and
        unchanged since :meth:`stage_admission` (call under the owner's
        lock).  Returns admitted device bytes; 0 means the upload went
        stale (a delta landed mid-upload) and was discarded.  Fault point
        ``admission.install`` fires before the gen check — a crash leaves
        the bucket cold, consistent, and re-admittable."""
        self._fault("admission.install")
        b = self.buckets.get(cap)
        if b is None or b.resident or b.gen != gen:
            return 0
        for name, arr in dev.items():
            setattr(b, name, arr)
        b.host = None
        b.resident = True
        b.gen += 1
        self._note_shape(b.n_rows, cap)
        return b.nbytes

    def admit_bucket(self, cap: int) -> int:
        """Synchronous admission (owner's lock held throughout): upload a
        cold bucket's host copy back to the device.  Returns admitted
        device bytes (0 = missing or already resident)."""
        staged = self.stage_admission(cap)
        if staged is None:
            return 0
        return self.install_admission(cap, *self.upload_admission(staged))

    # -- read side -----------------------------------------------------
    def _bucket_view(self, cap: int, b: _Bucket) -> BucketView:
        """One bucket's immutable snapshot (caller holds the owner's
        lock); cold buckets expose their host arrays in the same fields."""
        entries = tuple(
            (e.slot * self.n_shards, e.entry_pos)
            for e in self._entries.values()
            if e.cap == cap and e.entry_pos is not None
            and len(e.entry_pos))
        fill = (b.gids_h >= 0).sum(axis=1).astype(np.int64)
        common = dict(seg_ids=b.seg_ids.copy(), t_min=b.t_min.copy(),
                      t_max=b.t_max.copy(), entries=entries, fill=fill,
                      stage_bytes=b.full_nbytes)
        if b.resident:
            return BucketView(cap, b.gids, x=b.x, s=b.s, codes=b.codes,
                              st=b.st, scales=b.scales, nbrs=b.nbrs,
                              **common)
        h = b.host
        return BucketView(cap, b.gids_h, x=h.get("x"), s=h.get("s"),
                          codes=h.get("codes"), st=h.get("st"),
                          scales=h.get("scales"), nbrs=h.get("nbrs"),
                          resident=False, **common)

    def bucket_view(self, cap: int) -> Optional[BucketView]:
        """Fresh snapshot of one bucket (e.g. right after an admission so
        the in-flight query dispatches the resident block)."""
        b = self.buckets.get(cap)
        if b is None or not (b.seg_ids >= 0).any():
            return None
        return self._bucket_view(cap, b)

    def view(self) -> PackView:
        """Immutable snapshot for one query (capture under the owner's
        lock).  Buckets with no live slot are dropped, so an all-free
        bucket costs queries nothing.  Cold buckets are included — their
        host arrays dispatch through the same kernels (streamed to the
        device transiently), keeping answers bit-for-bit resident."""
        views = []
        for cap in sorted(self.buckets):
            b = self.buckets[cap]
            if (b.seg_ids >= 0).any():
                views.append(self._bucket_view(cap, b))
        return PackView(self.epoch, self.n_shards, self.m, tuple(views),
                        self.nbytes, quantize=self.quantize,
                        host_nbytes=self.host_nbytes)


def build_bucketed_pack(sources: Sequence[SegmentShardSource], n_shards: int,
                        epoch: int = 0, mesh: Optional[Mesh] = None,
                        cap_multiple: int = 256,
                        quantize: Optional[str] = None,
                        metrics=None,
                        graph_degree: Optional[int] = None,
                        resident_default: bool = True
                        ) -> BucketedShardPack:
    """Cold-build a :class:`BucketedShardPack` (restore / first query /
    bucket-geometry change): the same :meth:`~BucketedShardPack.add_segment`
    delta applied once per segment, so an incrementally maintained pack and
    a from-scratch build of the same segments answer identically.

    ``resident_default=False`` builds every bucket host-side (no device
    uploads) — the budgeted-tier path then admits only the buckets that fit
    ``StreamConfig.device_budget_bytes`` instead of staging the whole
    corpus before the first restored query."""
    if not sources:
        raise ValueError("build_bucketed_pack needs at least one segment")
    pack = BucketedShardPack(n_shards, sources[0].x.shape[1],
                             sources[0].s.shape[1], epoch=epoch, mesh=mesh,
                             cap_multiple=cap_multiple, quantize=quantize,
                             metrics=metrics, graph_degree=graph_degree,
                             resident_default=resident_default)
    for src in sources:
        pack.add_segment(src)
    return pack


def bucket_graph_seeds(bv: BucketView, t_lo: float, t_hi: float
                       ) -> np.ndarray:
    """Flattened seed positions for one bucket's stitched traversal: the
    union of graph entry points of every temporally active packed segment
    (this is the stitching rule — one beam, seeded in every unpruned
    segment's component, instead of per-segment sub-searches)."""
    if bv.nbrs is None or not bv.entries:
        return np.empty(0, np.int64)
    active = bv.active_rows(t_lo, t_hi)
    parts = [pos for row0, pos in bv.entries
             if row0 < len(active) and active[row0]]
    return np.concatenate(parts) if parts else np.empty(0, np.int64)


def host_topk(g: np.ndarray, d: np.ndarray, k: int
              ) -> Tuple[np.ndarray, np.ndarray]:
    """Exact host-side top-k over concatenated ``(gid, dist)`` candidate
    rows: ``argpartition`` narrows each row to ``k`` candidates, then one
    ``lexsort`` orders the slice by ``(dist, gid)``.  The order is total —
    rows where a *finite* distance tie straddles the k-th position (where
    argpartition's selection would be input-order-dependent) are
    re-selected by the full ``(dist, gid)`` order — so the result is
    deterministic regardless of block concatenation order.  Returns
    ``(gids [b, k] int64, dists [b, k] fp32)`` padded with
    ``-1`` / ``+inf``."""
    d = np.where(g >= 0, np.asarray(d, np.float32), np.inf)
    g = np.asarray(g, np.int64)
    if d.shape[1] > k:
        part = np.argpartition(d, k - 1, axis=1)
        g_sel = np.take_along_axis(g, part[:, :k], axis=1)
        d_sel = np.take_along_axis(d, part[:, :k], axis=1)
        kth = d_sel.max(axis=1)
        d_rest = np.take_along_axis(d, part[:, k:], axis=1)
        # +inf boundary ties are harmless (every +inf selection emits
        # gid -1 below); finite ones get the rare full-sort path
        amb = np.isfinite(kth) & (d_rest == kth[:, None]).any(axis=1)
        if amb.any():
            full = np.lexsort((g[amb], d[amb]))[:, :k]
            g_sel[amb] = np.take_along_axis(g[amb], full, axis=1)
            d_sel[amb] = np.take_along_axis(d[amb], full, axis=1)
        g, d = g_sel, d_sel
    order = np.lexsort((g, d))           # per-row: dist, then gid
    out_g = np.take_along_axis(g, order, axis=1)
    out_d = np.take_along_axis(d, order, axis=1)
    out_g = np.where(np.isfinite(out_d), out_g, -1)
    b, w = out_g.shape
    if w < k:
        out_g = np.concatenate(
            [out_g, np.full((b, k - w), -1, np.int64)], axis=1)
        out_d = np.concatenate(
            [out_d, np.full((b, k - w), np.inf, np.float32)], axis=1)
    return out_g, out_d


@partial(jax.jit, static_argnames=("k",))
def _merge_shard_topk(ids, dd, gid_stack, active, k):
    """Shard-local (ids, dists) [g, b, k'] -> exact global (gids, dists)
    [b, k].  Inactive rows and misses are masked to +inf before one
    ``top_k`` over the concatenated shard axis."""
    g = jax.vmap(lambda gr, im: gr[jnp.maximum(im, 0)])(gid_stack, ids)
    valid = (ids >= 0) & active[:, None, None]
    dd = jnp.where(valid, dd, jnp.inf)
    b = dd.shape[1]
    alld = dd.transpose(1, 0, 2).reshape(b, -1)
    allg = g.transpose(1, 0, 2).reshape(b, -1)
    neg, sel = jax.lax.top_k(-alld, k)
    out_d = -neg
    out_g = jnp.take_along_axis(allg, sel, axis=1)
    return jnp.where(jnp.isfinite(out_d), out_g, -1), out_d


def pack_search_blocks(view: PackView, queries: np.ndarray,
                       filt: Optional[Filter], k: int,
                       t_lo: float = -np.inf, t_hi: float = np.inf,
                       metric: str = "l2", trace=None, observe=None,
                       on_cold=None
                       ) -> List[Tuple[np.ndarray, np.ndarray]]:
    """One fused-kernel dispatch per non-empty, temporally unpruned bucket.

    A bucket whose segment spans all miss ``[t_lo, t_hi]`` is skipped
    entirely — temporal pruning drops whole device blocks, not just rows.
    Each dispatched fp32 bucket contributes one exact ``(gids [b, k_b],
    dists [b, k_b])`` candidate block, ready for the caller's exact
    ``(gid, dist)`` merge (``streaming.query.merge_topk`` /
    :func:`host_topk`).  Quantized buckets dispatch the asymmetric int8
    kernel instead and their blocks carry *approximate* distances — the
    caller over-fetches (``k = rerank_multiple * final_k``) and must
    rerank the union exactly at fp32 (``repro.quant.rerank.rerank_exact``)
    before merging with exact blocks.

    ``trace`` (a ``repro.obs.trace.QueryTrace``) opens one span per
    dispatched bucket, stopping its timer only after the bucket's device
    results are ready; ``observe`` (``BucketStats.observe``-compatible
    callable) receives one per-bucket observation per call — rows seen,
    rows temporally pruned, candidate fill, and whether the dispatch hit
    the jit cache.  Both default to off with zero overhead.

    Cold (non-resident) buckets dispatch the *same* kernels over their
    host-held block copies — jax stages the arrays to the device for the
    dispatch and drops them after — so their answers are bit-for-bit what
    the resident block would return.  ``on_cold`` (``f(cap, stage_bytes)``)
    fires once per dispatched cold bucket for tier-miss accounting.
    """
    queries = np.atleast_2d(np.asarray(queries, np.float32))
    trace = NULL_TRACE if trace is None else trace
    want_obs = observe is not None or trace.enabled
    blocks: List[Tuple[np.ndarray, np.ndarray]] = []
    for bv in view.buckets:
        active = bv.active_rows(t_lo, t_hi)
        rows = int(bv.gids.shape[0])
        n_active = int(active.sum())
        if n_active == 0:
            if observe is not None:       # whole-block temporal prune
                observe(bv.cap, rows=rows, active_rows=0)
            continue
        if not bv.resident and on_cold is not None:
            on_cold(bv.cap, bv.stage_bytes)
        kk = min(k, bv.cap)               # per-shard list length
        # merged width: for k > cap the per-shard lists (= whole shards)
        # still hold up to rows * kk candidates, so the top-k stays exact
        k_out = min(k, rows * kk)
        traces0 = dispatch_trace_count() if want_obs else 0
        with trace.span("bucket_dispatch", cap=bv.cap, rows=rows,
                        active_rows=n_active, k_out=k_out,
                        quantized=bv.quantized,
                        resident=bv.resident) as sp:
            if bv.quantized:
                ids, dd = sharded_quant_filtered_topk(
                    queries, bv.codes, bv.st, bv.scales, filt, kk,
                    metric=metric, m=view.m)
            else:
                ids, dd = sharded_filtered_topk(queries, bv.x, bv.s, filt,
                                                kk, metric=metric, m=view.m)
            out_g, out_d = _merge_shard_topk(ids, dd, bv.gids,
                                             jnp.asarray(active), k_out)
            block_ready((out_g, out_d))
        out_g = np.asarray(out_g, np.int64)
        out_d = np.asarray(out_d, np.float32)
        if want_obs:
            cache_hit = dispatch_trace_count() == traces0
            n_cand = int((out_g >= 0).sum())
            sp.annotate(candidates=n_cand, cache_hit=cache_hit)
            if observe is not None:
                observe(bv.cap, rows=rows, active_rows=n_active,
                        candidates=n_cand,
                        candidate_slots=queries.shape[0] * k_out,
                        cache_hit=cache_hit)
        blocks.append((out_g, out_d))
    return blocks


def pack_search_blocks_grouped(view: PackView, groups,
                               metric: str = "l2", trace=None,
                               observe=None, on_cold=None,
                               deadlines=None, on_expired=None,
                               fault=None, observe_group=None,
                               on_device_merge=None, on_kernel_rows=None
                               ) -> List[List[Tuple[np.ndarray, np.ndarray]]]:
    """Heterogeneous-request sibling of :func:`pack_search_blocks`: several
    ``(queries, filt, k, t_lo, t_hi)`` request groups scan the pack's fp32
    buckets in ONE pass, sharing each bucket's device block across every
    group that is temporally active there.

    Per bucket, the groups whose temporal window intersects the bucket
    (exactly the groups for which a solo :func:`pack_search_blocks` call
    would dispatch it) are classed by filter kind and kernel ``kpad``, and
    each class is finished on the device by one
    :func:`repro.kernels.sharded_filtered_topk_grouped` program — the
    class's query rows stacked in one query axis, each row with its
    group's filter, so one kernel call reads the bucket's ``[rows, cap,
    ·]`` block once per class rather than once per filter, then every
    row's shard merge with its group's temporal ``active`` mask and
    ``k``.  The bucket then waits once and copies every class's results to
    the host once; each group's block is a numpy view of its rows.  Every group's candidate block is **bit-for-bit**
    what its solo call would have produced, so callers merge the returned
    blocks exactly as if each group had scanned alone.  A group whose
    filter has no kernel encoding takes the solo dispatch and merge.

    ``deadlines`` (parallel to ``groups``, entries with an ``expired()``
    method or ``None``) drops a group from all remaining buckets once its
    deadline passes, reporting via ``on_expired(group_idx,
    buckets_remaining)`` exactly once; ``fault()`` fires before each
    bucket's dispatch (the owner's ``query.bucket`` fault point);
    ``observe`` gets one union observation per bucket (cache accounting),
    while ``observe_group(group_idx, cap, rows=, active_rows=,
    candidates=, candidate_slots=, cache_hit=)`` attributes the same
    dispatch per group — the per-tenant ``BucketStats`` hook;
    ``on_device_merge(n)`` is told once how many groups the on-device
    finish answered, and ``on_kernel_rows(rows, slots)`` once per class
    program how many real query rows it scanned and how many padded rows
    its query tiles computed.  Returns one candidate-block list per group (a
    dropped group keeps the blocks gathered before its deadline expired).

    ``trace`` opens one ``bucket_dispatch_grouped`` span per dispatched
    bucket holding the host's steps: per class ``group_stack`` (host
    build and upload) and ``kernel_launch`` (the class's program; with
    ``solo=True`` a group without a kernel encoding), then
    ``device_wait`` (the bucket's one wait for the device, which the
    untraced path makes too) and ``readback`` (the bucket's one
    device-to-host copy, the per-group views and candidate counts).
    """
    trace = NULL_TRACE if trace is None else trace
    groups = [(np.atleast_2d(np.asarray(q, np.float32)), f, int(k),
               float(t_lo), float(t_hi)) for q, f, k, t_lo, t_hi in groups]
    encs = [encode_filter(f, view.m) for _, f, _, _, _ in groups]
    want_obs = (observe is not None or observe_group is not None
                or trace.enabled)
    blocks: List[List[Tuple[np.ndarray, np.ndarray]]] = \
        [[] for _ in groups]
    expired = [False] * len(groups)
    on_device = set()
    buckets = list(view.buckets)
    for bi, bv in enumerate(buckets):
        if deadlines is not None:
            for gi, dl in enumerate(deadlines):
                if not expired[gi] and dl is not None and dl.expired():
                    expired[gi] = True
                    if on_expired is not None:
                        on_expired(gi, len(buckets) - bi)
        rows = int(bv.gids.shape[0])
        actives = {}
        live: List[int] = []
        for gi, (_, _, _, t_lo, t_hi) in enumerate(groups):
            if expired[gi]:
                continue
            act = bv.active_rows(t_lo, t_hi)
            if act.any():
                actives[gi] = act
                live.append(gi)
            elif observe_group is not None:   # whole-block temporal prune
                observe_group(gi, bv.cap, rows=rows, active_rows=0)
        if not live:
            if observe is not None:
                observe(bv.cap, rows=rows, active_rows=0)
            continue
        if fault is not None:
            fault()
        if not bv.resident and on_cold is not None:
            on_cold(bv.cap, bv.stage_bytes)
        union_active = int(np.logical_or.reduce(
            [actives[gi] for gi in live]).sum())
        kk = {gi: min(groups[gi][2], bv.cap) for gi in live}
        k_out = {gi: min(groups[gi][2], rows * kk[gi]) for gi in live}
        classes: Dict[tuple, List[int]] = {}
        solo: List[int] = []
        for gi in live:
            if encs[gi] is None:
                solo.append(gi)
            else:
                classes.setdefault((encs[gi][0], next_pow2(max(kk[gi], 8))),
                                   []).append(gi)
        traces0 = dispatch_trace_count() if want_obs else 0
        with trace.span("bucket_dispatch_grouped", cap=bv.cap, rows=rows,
                        active_rows=union_active, n_groups=len(live),
                        resident=bv.resident) as sp:
            # per class (members, their first rows, [R_pad, k_top] device
            # results); a solo group's results are [bq, k_out]
            pending = []
            for (kind, _), members in classes.items():
                out_g, out_d, offs = sharded_filtered_topk_grouped(
                    [groups[gi][0] for gi in members], kind,
                    [encs[gi][1] for gi in members],
                    [kk[gi] for gi in members],
                    [actives[gi] for gi in members], bv.x, bv.s, bv.gids,
                    view.m, metric=metric, trace=trace)
                pending.append((members, offs, (out_g, out_d)))
                if on_kernel_rows is not None:
                    on_kernel_rows(sum(groups[gi][0].shape[0]
                                       for gi in members),
                                   int(out_g.shape[0]))
            for gi in solo:
                with trace.span("kernel_launch", groups=1, solo=True):
                    ids, dd = sharded_filtered_topk(
                        groups[gi][0], bv.x, bv.s, groups[gi][1], kk[gi],
                        metric=metric, m=view.m)
                    pending.append(([gi], [0], _merge_shard_topk(
                        ids, dd, bv.gids, jnp.asarray(actives[gi]),
                        k_out[gi])))
            with trace.span("device_wait"):
                block_ready([res for _, _, res in pending])
            cache_hit = (dispatch_trace_count() == traces0) if want_obs \
                else False
            n_cand_total = 0
            with trace.span("readback"):
                host = jax.device_get([res for _, _, res in pending])
                for (members, offs, _), (hg, hd) in zip(pending, host):
                    hg = hg.astype(np.int64)
                    for gi, off in zip(members, offs):
                        b = groups[gi][0].shape[0]
                        out_g = hg[off:off + b, :k_out[gi]]
                        blocks[gi].append((out_g,
                                           hd[off:off + b, :k_out[gi]]))
                        if want_obs:
                            n_cand = int((out_g >= 0).sum())
                            n_cand_total += n_cand
                            if observe_group is not None:
                                observe_group(
                                    gi, bv.cap, rows=rows,
                                    active_rows=int(actives[gi].sum()),
                                    candidates=n_cand,
                                    candidate_slots=b * k_out[gi],
                                    cache_hit=cache_hit)
        for members in classes.values():
            on_device.update(members)
        if want_obs:
            sp.annotate(candidates=n_cand_total, cache_hit=cache_hit)
            if observe is not None:
                observe(bv.cap, rows=rows, active_rows=union_active,
                        candidates=n_cand_total,
                        candidate_slots=sum(
                            groups[gi][0].shape[0] * k_out[gi]
                            for gi in live),
                        cache_hit=cache_hit)
    if on_device_merge is not None:
        on_device_merge(len(on_device))
    return blocks


def pack_search(pack, queries: np.ndarray, filt: Optional[Filter],
                k: int, t_lo: float = -np.inf, t_hi: float = np.inf,
                metric: str = "l2", lookup=None,
                rerank_multiple: int = 4, trace=None,
                observe=None, on_cold=None, registry=None
                ) -> Tuple[np.ndarray, np.ndarray]:
    """Fan one query batch out over every active shard of the pack and merge
    the shard-local top-k exactly.

    ``pack`` is a legacy :class:`ShardPack`, a :class:`BucketedShardPack`,
    or a :class:`PackView`.  Temporal pruning happens via the ``active``
    mask (host-computed from the per-row segment spans) — and, for the
    bucketed layouts, by skipping whole bucket blocks — so the jit cache
    sees one static shape per pack/bucket.  A quantized pack additionally
    needs ``lookup(gids) -> (x, s, present)`` (the manager's point-store
    getter) for the exact fp32 rerank of its over-fetched
    (``rerank_multiple * k``) candidates, padded to ``rerank_multiple * k``
    slots per bucket of the view whatever the buckets dispatched or the
    candidates found, so its program's shape is fixed per view geometry;
    ``registry`` takes the rerank's counters.  Returns ``(gids [b, k]
    int64, dists [b, k] fp32)`` with ``-1`` / ``+inf`` padding.
    """
    queries = np.atleast_2d(np.asarray(queries, np.float32))
    b = queries.shape[0]
    trace = NULL_TRACE if trace is None else trace
    if isinstance(pack, (BucketedShardPack, PackView)):
        view = pack.view() if isinstance(pack, BucketedShardPack) else pack
        quantized = view.quantize is not None
        k_fetch = max(k * max(int(rerank_multiple), 1), k) if quantized \
            else k
        blocks = pack_search_blocks(view, queries, filt, k_fetch, t_lo=t_lo,
                                    t_hi=t_hi, metric=metric, trace=trace,
                                    observe=observe, on_cold=on_cold)
        if not blocks:
            return (np.full((b, k), -1, np.int64),
                    np.full((b, k), np.inf, np.float32))
        g = np.concatenate([bg for bg, _ in blocks], axis=1)
        if quantized:
            # the approximate distances are never read past this point —
            # the rerank re-scores candidates from their gids alone
            if lookup is None:
                raise ValueError("a quantized pack needs lookup= for the "
                                 "exact fp32 rerank")
            from ..quant import rerank_exact
            with trace.span("rerank_fp32", overfetch=int(g.shape[1]),
                            k=k):
                return rerank_exact(queries, g, k, lookup, metric=metric,
                                    part_width=k_fetch,
                                    parts=len(view.buckets), trace=trace,
                                    registry=registry)
        d = np.concatenate([bd for _, bd in blocks], axis=1)
        return host_topk(g, d, k)
    kk = min(k, pack.cap)                 # per-shard list length
    # merged width: for k > cap the per-shard lists (= whole shards) still
    # hold up to n_rows * kk candidates, so the global top-k stays exact
    k_out = min(k, pack.n_rows * kk)
    with trace.span("pack_dispatch", rows=pack.n_rows, cap=pack.cap,
                    k_out=k_out):
        ids, dd = sharded_filtered_topk(queries, pack.x, pack.s_dev, filt,
                                        kk, metric=metric, m=pack.m)
        active = jnp.asarray(pack.active_rows(t_lo, t_hi))
        out_g, out_d = _merge_shard_topk(ids, dd, pack.gids_dev, active,
                                         k_out)
        block_ready((out_g, out_d))
    gids = np.full((b, k), -1, np.int64)
    dists = np.full((b, k), np.inf, np.float32)
    gids[:, :k_out] = np.asarray(out_g, np.int64)
    dists[:, :k_out] = np.asarray(out_d, np.float32)
    return gids, dists
