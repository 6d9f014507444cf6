"""Portable array redistribution across device meshes (docs/ELASTIC.md).

Elastic topology needs one primitive the collectives layer does not
have: move data living under one logical sharding on mesh A to another
logical sharding on mesh B — different layout (dp<->tp<->pp), different
chip count, or both — WITHOUT ever materializing a full replica of a
large tensor on any single device. "Memory-efficient array
redistribution through portable collective communication"
(arxiv 2112.01075) gives the recipe this module implements: decompose
the transfer into a grid of rectangular piece moves (the intersection
of the source and destination partitions is always a regular grid),
stage the pieces in bounded blocks so peak live memory per device stays
<= destination shard + one staged block, and finish with ONE compiled
SPMD transition program on the destination mesh that both pins the
result layout and cross-checks shard geometry with a collective.

Two levels of API:

``redistribute`` / ``redistribute_tree``
    The general primitive: a jax global array (or pytree of them) under
    any ``NamedSharding`` -> any other ``NamedSharding``, possibly on a
    different device set. Piece moves are derived from the shardings'
    ``devices_indices_map`` so every PartitionSpec jax can express is
    handled, including uneven trailing shards.

``FragLayout`` / ``plan_moves`` / ``reshard_fragments`` / ``place_from_host``
    The flattened-fragment fast path the ZeRO engine (gluon/zero.py,
    arxiv 2004.13336) needs: its state space is a flat per-group
    buffer whose per-device fragment OWNERSHIP is a permutation (the
    dcn x ici owner map) that no PartitionSpec can express. Plans are
    computed host-side in shard-local coordinates with the
    non-dividing/tiny-param clamps explicit — a fragment that is pure
    padding generates no moves and destination padding is explicitly
    zeroed, so a 256->64 resume where some param shrinks below one
    fragment per replica is exact by construction, not by
    pad_to_multiple alignment luck.

Every transition program is compiled through ``compilewatch.watched_jit``
(site="reshard") so it lands in the program inventory and — when
MXNET_STATICCHECK_SPMD is armed — is statically validated by shardcheck
BEFORE first execution. The ``reshard_fail`` faultinject site fires at
LIVE plan execution entry (``reshard_fragments``/``redistribute`` and
``Trainer.reshard_to``) so the degradation path (elastic.py ->
checkpoint-restore) is deterministically testable; the host-side
restore placement (``place_from_host``) deliberately has NO fault site
— degradation must be able to restore while the live fault is armed.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

import numpy as np

from .. import config
from ..base import MXNetError

__all__ = [
    "ReshardError", "FragLayout", "Move", "plan_moves", "stage_blocks",
    "reshard_fragments", "place_from_host", "gather_to_host",
    "redistribute", "redistribute_tree", "owner_permutation",
    "block_bytes", "peak_live_bytes", "sharding_manifest",
    "transition_programs",
]


class ReshardError(MXNetError):
    """A redistribution plan could not be executed (geometry mismatch,
    injected failure, transition-program integrity check). Callers on
    the live path degrade to checkpoint-restore (elastic.py)."""


def block_bytes() -> int:
    """Configured staged-block size in bytes (MXNET_ELASTIC_BLOCK)."""
    b = int(config.get("MXNET_ELASTIC_BLOCK"))
    return b if b > 0 else (4 << 20)


def peak_live_bytes(shard_nbytes: int, blk: Optional[int] = None) -> int:
    """The 2112.01075 bound a staged plan is allowed to reach on any
    one device: the destination shard it is assembling plus one staged
    block in flight (tools/reshard_micro.py gates the measurement
    against exactly this number)."""
    return int(shard_nbytes) + int(blk if blk is not None else block_bytes())


def owner_permutation(n: int, n_dcn: int = 0) -> Tuple[int, ...]:
    """Device position -> global fragment index, the ZeRO dcn x ici
    ownership permutation (gluon/zero.py): position p on a dcn x ici
    hierarchy of ``n_dcn`` slices owns fragment
    ``(p % n_ici) * n_dcn + (p // n_ici)``; flat (n_dcn in {0, 1}) is
    the identity."""
    if n_dcn and n_dcn > 1:
        if n % n_dcn:
            raise ReshardError("n_dcn=%d does not divide n=%d"
                               % (n_dcn, n))
        n_ici = n // n_dcn
        return tuple((p % n_ici) * n_dcn + (p // n_ici) for p in range(n))
    return tuple(range(n))


@dataclass(frozen=True)
class FragLayout:
    """Flattened-fragment layout of ONE logical array of ``size``
    elements sharded over ``n`` devices: fragment length is
    ``ceil(size / n)`` (zero-padded tail), device position ``p`` owns
    global fragment ``owner[p]``, and the fragment lives at
    ``offset`` inside that device's shard buffer (ZeRO packs many
    params into one per-group buffer)."""
    size: int
    n: int
    owner: Tuple[int, ...]
    offset: int = 0

    @property
    def frag(self) -> int:
        return -(-self.size // self.n) if self.size else 0

    @classmethod
    def build(cls, size: int, n: int, n_dcn: int = 0,
              offset: int = 0) -> "FragLayout":
        return cls(int(size), int(n), owner_permutation(n, n_dcn),
                   int(offset))

    def data_extent(self, r: int) -> Tuple[int, int]:
        """Global [lo, hi) of REAL data in fragment ``r`` — the
        explicit non-dividing/tiny-param clamp. A fragment past the
        data (hi == lo) is pure padding and must generate no moves."""
        lo = r * self.frag
        hi = min(self.size, lo + self.frag)
        return (lo, max(lo, hi))

    def pos_of(self, r: int) -> int:
        """Device position holding global fragment ``r``."""
        return self.owner.index(r)


class Move(NamedTuple):
    """One contiguous copy in SHARD-LOCAL element coordinates:
    src shard ``src_pos`` [src_lo, src_hi) -> dst shard ``dst_pos``
    at ``dst_lo`` (offsets already folded in)."""
    src_pos: int
    src_lo: int
    src_hi: int
    dst_pos: int
    dst_lo: int

    @property
    def elems(self) -> int:
        return self.src_hi - self.src_lo


def plan_moves(src: FragLayout, dst: FragLayout) -> List[Move]:
    """Host-side move plan for one logical array between two fragment
    layouts. Every move is the intersection of a source data extent
    with a destination data extent in GLOBAL coordinates, translated
    to shard-local ones; padding never moves. Same-n transitions with
    different owners reduce to a pure permutation (frag identical),
    count changes to the staged split/merge of 2112.01075."""
    if src.size != dst.size:
        raise ReshardError("reshard size mismatch: src=%d dst=%d"
                           % (src.size, dst.size))
    moves: List[Move] = []
    if src.size == 0:
        return moves
    for dp in range(dst.n):
        dr = dst.owner[dp]
        dlo, dhi = dst.data_extent(dr)
        if dhi <= dlo:
            continue                      # destination fragment is padding
        # global data range [dlo, dhi) comes from source fragments
        # floor(dlo/frag_s) .. floor((dhi-1)/frag_s)
        fs = src.frag
        for sr in range(dlo // fs, (dhi - 1) // fs + 1):
            slo, shi = src.data_extent(sr)
            lo, hi = max(dlo, slo), min(dhi, shi)
            if hi <= lo:
                continue
            sp = src.pos_of(sr)
            moves.append(Move(
                sp, src.offset + (lo - sr * fs),
                src.offset + (hi - sr * fs),
                dp, dst.offset + (lo - dr * dst.frag)))
    return moves


def stage_blocks(moves: Sequence[Move],
                 block_elems: int) -> List[List[Move]]:
    """Chunk a move list into staged blocks of <= ``block_elems``
    elements in flight each; a single move larger than the block is
    split so the bound holds even for one giant fragment."""
    block_elems = max(1, int(block_elems))
    split: List[Move] = []
    for m in moves:
        lo = m.src_lo
        dlo = m.dst_lo
        while lo < m.src_hi:
            hi = min(m.src_hi, lo + block_elems)
            split.append(Move(m.src_pos, lo, hi, m.dst_pos, dlo))
            dlo += hi - lo
            lo = hi
    blocks: List[List[Move]] = []
    cur: List[Move] = []
    cur_elems = 0
    for m in split:
        if cur and cur_elems + m.elems > block_elems:
            blocks.append(cur)
            cur, cur_elems = [], 0
        cur.append(m)
        cur_elems += m.elems
    if cur:
        blocks.append(cur)
    return blocks


# ----------------------------------------------------------------------
# transition programs (watched + shardcheck-validated)
# ----------------------------------------------------------------------
_TRANSITIONS: Dict[tuple, object] = {}


def transition_programs() -> int:
    """How many distinct transition programs have been built in this
    process (tests / fleet_report gates)."""
    return len(_TRANSITIONS)


def _flat_transition(n: int, shard_len: int, dtype, devices):
    """One watched SPMD program per (geometry, device set): identity
    passthrough of the freshly assembled (n, shard_len) stack under its
    destination sharding plus a psum'd shard count — a cross-replica
    integrity check that every shard participated (the per-shard
    element geometry is already pinned statically by the in_specs).
    The count is an exact int32 psum — a float32 count would lose
    integer precision past 2^24 elements and fail spuriously at scale.
    The psum is the program's (exempt, explicitly laid out) collective,
    so shardcheck has a real program to validate before first run."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import PartitionSpec as P
    from .. import compilewatch
    from .. import kvstore as kvs_mod
    from .collectives import shard_map

    key = ("flat", n, int(shard_len), np.dtype(dtype).str,
           tuple(id(d) for d in devices))
    prog = _TRANSITIONS.get(key)
    if prog is not None:
        return prog
    mesh = kvs_mod.device_mesh(tuple(devices), ("dp",))

    def body(x):
        total = lax.psum(jnp.asarray(1, jnp.int32), "dp")
        return x, total

    mapped = shard_map(body, mesh=mesh, in_specs=P("dp"),
                       out_specs=(P("dp"), P()), check_vma=False)
    prog = compilewatch.watched_jit(
        mapped, "reshard.transition", site="reshard",
        arg_names=("stack",), instance="n=%d len=%d" % (n, shard_len),
        static_repr="n=%d shard_len=%d dtype=%s"
                    % (n, shard_len, np.dtype(dtype).name))
    _TRANSITIONS[key] = prog
    return prog


_UPDATERS: Dict[tuple, object] = {}


def _shard_updater(dtype, ndim, device):
    """Watched, donated piece-write program: dynamic_update_slice of
    one staged piece into the destination shard buffer being
    assembled. Donating the buffer lets XLA alias it into the output,
    so assembling a shard from many staged pieces keeps exactly ONE
    shard allocation live (plus the piece in flight) — the liveness
    half of the 2112.01075 bound. One program per (dtype, rank,
    device): offsets are traced scalars, so only distinct piece
    shapes recompile."""
    from jax import lax
    from .. import compilewatch

    key = (np.dtype(dtype).str, int(ndim), id(device))
    prog = _UPDATERS.get(key)
    if prog is not None:
        return prog

    def write(buf, piece, *offs):
        return lax.dynamic_update_slice(buf, piece, offs)

    prog = compilewatch.watched_jit(
        write, "reshard.block_write", site="reshard",
        arg_names=("shard", "piece"), instance="dev=%s" % (device,),
        static_repr="dtype=%s ndim=%d"
                    % (np.dtype(dtype).name, int(ndim)),
        donate_argnums=(0,))
    # a plan legitimately stages several distinct piece shapes (full
    # blocks + tails); tell the recompile-storm guard this is planned
    prog.expected_signatures = 8
    _UPDATERS[key] = prog
    return prog


def _run_flat_transition(bufs, n, shard_len, dtype, devices, label):
    """Stack per-device shards zero-copy, run the watched transition,
    hand back the per-device result buffers."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    from .. import kvstore as kvs_mod
    from .. import telemetry

    mesh = kvs_mod.device_mesh(tuple(devices), ("dp",))
    sharding = NamedSharding(mesh, P("dp"))
    stacked = jax.make_array_from_single_device_arrays(
        (n, int(shard_len)), sharding,
        [b.reshape(1, int(shard_len)) for b in bufs])
    out, total = _flat_transition(n, shard_len, dtype, devices)(stacked)
    got = int(jax.device_get(total))
    if got != n:
        raise ReshardError(
            "reshard transition integrity check failed for %r: "
            "psum(shards)=%d expected %d" % (label, got, n))
    telemetry.counter("mx_reshard_transitions_total", kind=label).inc()
    by_dev = {s.device: s.data for s in out.addressable_shards}
    return [by_dev[d].reshape(int(shard_len)) for d in devices]


# ----------------------------------------------------------------------
# fragment-level execution (the ZeRO path)
# ----------------------------------------------------------------------
def _note_peak(dst_shard_nbytes: int, blk_bytes: int, label: str):
    from .. import telemetry
    telemetry.gauge("mx_reshard_planned_peak_bytes", kind=label).set(
        peak_live_bytes(dst_shard_nbytes, blk_bytes))
    telemetry.gauge("mx_reshard_block_bytes", kind=label).set(blk_bytes)


def reshard_fragments(src_bufs, moves: Sequence[Move], n_dst: int,
                      dst_shard_len: int, dst_devices,
                      blk_bytes: Optional[int] = None,
                      label: str = "fragments"):
    """Execute a fragment move plan device-to-device: each destination
    shard is preallocated once (zeros — destination padding and
    unwritten holes are explicitly zero from the start), then staged
    ``device_put`` slices (<= one block in flight) are written into it
    through the donated piece-write program, and the watched
    transition program runs on the destination mesh. Returns the
    per-device (dst_shard_len,) jax buffers in ``dst_devices`` order.

    ``src_bufs`` are per-source-device 1-D jax arrays (committed to
    their devices); any source shard not referenced by a move is never
    read. Because each block's pieces are dropped as soon as they are
    folded into the donated shard buffer, peak live bytes on any
    destination device stay <= dst shard + one staged block
    (peak_live_bytes)."""
    import jax
    import jax.numpy as jnp
    from .. import faultinject
    from .. import telemetry

    faultinject.maybe_fail("reshard_fail", ReshardError)
    dst_devices = tuple(dst_devices)
    if n_dst != len(dst_devices):
        raise ReshardError("n_dst=%d but %d destination devices"
                           % (n_dst, len(dst_devices)))
    dtype = np.dtype(src_bufs[0].dtype) if src_bufs else np.dtype("f4")
    blk = int(blk_bytes if blk_bytes is not None else block_bytes())
    block_elems = max(1, blk // max(1, dtype.itemsize))
    _note_peak(int(dst_shard_len) * dtype.itemsize, blk, label)

    # host-side plan validation before any device work: destination
    # spans must not overlap and must stay inside the shard
    spans: List[List[Tuple[int, int]]] = [[] for _ in range(n_dst)]
    for m in moves:
        spans[m.dst_pos].append((m.dst_lo, m.dst_lo + m.elems))
    for dp, sp in enumerate(spans):
        sp.sort()
        cursor = 0
        for lo, hi in sp:
            if lo < cursor:
                raise ReshardError(
                    "overlapping moves at dst_pos=%d lo=%d" % (dp, lo))
            cursor = hi
        if cursor > int(dst_shard_len):
            raise ReshardError(
                "move past destination shard at dst_pos=%d: hi=%d > "
                "shard_len=%d" % (dp, cursor, int(dst_shard_len)))

    out_bufs = [jax.device_put(jnp.zeros(int(dst_shard_len), dtype), d)
                for d in dst_devices]
    moved = 0
    for block in stage_blocks(moves, block_elems):
        for m in block:
            piece = src_bufs[m.src_pos][m.src_lo:m.src_hi]
            dev = dst_devices[m.dst_pos]
            piece = jax.device_put(piece, dev)
            out_bufs[m.dst_pos] = _shard_updater(dtype, 1, dev)(
                out_bufs[m.dst_pos], piece, np.int32(m.dst_lo))
            moved += m.elems
    telemetry.counter("mx_reshard_moved_bytes_total", kind=label).inc(
        moved * dtype.itemsize)
    return _run_flat_transition(out_bufs, n_dst, dst_shard_len, dtype,
                                dst_devices, label)


def place_from_host(entries, n: int, shard_len: int, dst_devices,
                    dtype, label: str = "restore"):
    """Checkpoint-restore scatter: place canonical host arrays into a
    fresh per-device fragment layout. ``entries`` is a sequence of
    ``(flat_numpy_array, FragLayout)`` pairs all targeting the same
    per-group shard buffer of ``shard_len`` elements on ``n`` devices.
    The shard-local placement uses the same explicit data_extent
    clamps as plan_moves (tiny params land exactly, padding is zeroed),
    then each device receives its full shard in one transfer and the
    watched transition program validates the assembled stack. Returns
    per-device (shard_len,) jax buffers."""
    import jax

    # NO reshard_fail site here: checkpoint-restore placement is the
    # DEGRADATION target of a failed live transition — it must work
    # while the live fault is still armed
    dtype = np.dtype(dtype)
    shards = [np.zeros(int(shard_len), dtype) for _ in range(n)]
    for arr, lay in entries:
        flat = np.asarray(arr, dtype=dtype).reshape(-1)
        if flat.size != lay.size:
            raise ReshardError(
                "restore size mismatch: array=%d layout=%d"
                % (flat.size, lay.size))
        for p in range(lay.n):
            r = lay.owner[p]
            lo, hi = lay.data_extent(r)
            if hi <= lo:
                continue                   # whole fragment is padding
            shards[p][lay.offset:lay.offset + (hi - lo)] = flat[lo:hi]
    bufs = [jax.device_put(s, d) for s, d in zip(shards, dst_devices)]
    return _run_flat_transition(bufs, n, shard_len, dtype,
                                tuple(dst_devices), label)


def gather_to_host(src_bufs, layouts) -> List[np.ndarray]:
    """Inverse of place_from_host: reconstruct each layout's canonical
    flat host array from per-device shard buffers, one bounded
    device->host pull per referenced fragment (never a full stacked
    copy). ``layouts`` is a sequence of FragLayout sharing the shard
    buffers."""
    out = []
    for lay in layouts:
        dtype = np.dtype(src_bufs[0].dtype)
        full = np.zeros(lay.size, dtype)
        for p in range(lay.n):
            r = lay.owner[p]
            lo, hi = lay.data_extent(r)
            if hi <= lo:
                continue
            full[lo:hi] = np.asarray(
                src_bufs[p][lay.offset:lay.offset + (hi - lo)])
        out.append(full)
    return out


# ----------------------------------------------------------------------
# general mesh-to-mesh redistribution (NamedSharding -> NamedSharding)
# ----------------------------------------------------------------------
def _slice_tuple(idx, shape):
    """Normalize a devices_indices_map value to ((start, stop), ...)."""
    out = []
    for sl, dim in zip(idx, shape):
        start = 0 if sl.start is None else int(sl.start)
        stop = dim if sl.stop is None else int(sl.stop)
        out.append((start, stop))
    return tuple(out)


def _general_transition(dst_sharding, shape, dtype):
    """Watched identity+psum transition for an arbitrary NamedSharding
    (the general redistribute path). The psum runs over every mesh
    axis so the participant-count invariant covers the whole device
    set; like the flat path it counts in exact int32 (a float32
    element count loses integer precision past 2^24)."""
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import PartitionSpec as P
    from .. import compilewatch
    from .collectives import shard_map

    mesh = dst_sharding.mesh
    axes = tuple(mesh.axis_names)
    key = ("gen", tuple(shape), np.dtype(dtype).str, axes,
           tuple(str(s) for s in dst_sharding.spec),
           tuple(id(d) for d in mesh.devices.flat))
    prog = _TRANSITIONS.get(key)
    if prog is not None:
        return prog

    def body(x):
        total = lax.psum(jnp.asarray(1, jnp.int32), axes)
        return x, total

    spec = dst_sharding.spec
    mapped = shard_map(body, mesh=mesh, in_specs=spec,
                       out_specs=(spec, P()), check_vma=False)
    prog = compilewatch.watched_jit(
        mapped, "reshard.transition_nd", site="reshard",
        arg_names=("array",),
        instance="shape=%s spec=%s" % (list(shape), str(spec)),
        static_repr="shape=%s dtype=%s axes=%s spec=%s"
                    % (list(shape), np.dtype(dtype).name, list(axes),
                       str(spec)))
    _TRANSITIONS[key] = prog
    return prog


def redistribute(x, dst_sharding, blk_bytes: Optional[int] = None,
                 label: str = "array"):
    """Move a jax global array from its current sharding to
    ``dst_sharding`` (any NamedSharding, possibly on different
    devices) as a staged, memory-bounded transfer: per destination
    shard, pull only the intersecting rectangles from the source's
    addressable shards (each staged ``device_put`` <= one block, big
    rectangles split along their leading axis with ONE row-chunk step
    shared by every intersection of that shard — uneven source widths
    must not skew piece boundaries), write each piece into the
    preallocated shard buffer through the donated piece-write program
    (one shard allocation live, pieces dropped per write), and run the
    watched + shardcheck-validated transition program on the
    destination mesh. Replicated source dims read from the first
    holder; replicated destination specs receive a full copy per
    device (their shard IS the array — the bound is per the
    destination layout, as in 2112.01075)."""
    import jax
    import jax.numpy as jnp
    from .. import faultinject
    from .. import telemetry

    faultinject.maybe_fail("reshard_fail", ReshardError)
    shape = tuple(int(s) for s in x.shape)
    dtype = np.dtype(x.dtype)
    blk = int(blk_bytes if blk_bytes is not None else block_bytes())
    block_elems = max(1, blk // max(1, dtype.itemsize))

    src_map = {}                    # slice-tuple -> shard data (dedup
    for s in x.addressable_shards:  # replicated holders: first wins)
        key = _slice_tuple(s.index, shape)
        src_map.setdefault(key, s.data)

    dst_map = dst_sharding.devices_indices_map(shape)
    max_shard = 0
    out_by_dev = {}
    for dev, idx in dst_map.items():
        dbox = _slice_tuple(idx, shape)
        dshape = tuple(hi - lo for lo, hi in dbox)
        shard_elems = int(np.prod(dshape or (1,)))
        max_shard = max(max_shard, shard_elems * dtype.itemsize)
        if not shape:                       # 0-d array: single piece
            out_by_dev[dev] = jax.device_put(
                next(iter(src_map.values())), dev)
            continue
        if shard_elems == 0:
            out_by_dev[dev] = jax.device_put(
                jnp.zeros(dshape, dtype), dev)
            continue
        inters = [(sbox,
                   tuple((max(dl, sl), min(dh, sh))
                         for (dl, dh), (sl, sh) in zip(dbox, sbox)))
                  for sbox in src_map]
        inters = [(sbox, inter) for sbox, inter in inters
                  if not any(hi <= lo for lo, hi in inter)]
        if not inters:
            raise ReshardError(
                "no source pieces intersect a destination shard of "
                "shape %s — source and destination arrays disagree"
                % (dshape,))
        # one leading-axis chunk step for the WHOLE destination shard
        # (widest intersection decides): intersections in the same row
        # band share their row range, so a common step keeps piece
        # boundaries aligned even when source shards are uneven
        max_row = max(int(np.prod([hi - lo for lo, hi in inter[1:]]
                                  or [1])) for _, inter in inters)
        step = max(1, block_elems // max(1, max_row))
        buf = jax.device_put(jnp.zeros(dshape, dtype), dev)
        upd = _shard_updater(dtype, len(shape), dev)
        covered = 0
        for sbox, inter in inters:
            sdata = src_map[sbox]
            lo0, hi0 = inter[0]
            r = lo0
            while r < hi0:
                r2 = min(hi0, r + step)
                local_src = tuple(
                    slice(r - sbox[0][0], r2 - sbox[0][0])
                    if d == 0 else slice(lo - sbox[d][0], hi - sbox[d][0])
                    for d, (lo, hi) in enumerate(inter))
                piece = jax.device_put(sdata[local_src], dev)
                offs = tuple(
                    np.int32((r if d == 0 else inter[d][0]) - dbox[d][0])
                    for d in range(len(shape)))
                buf = upd(buf, piece, *offs)
                covered += int(piece.size)
                r = r2
        # source boxes are pairwise disjoint (dedup'd), so disjoint
        # piece counts summing to the shard size proves full coverage
        if covered != shard_elems:
            raise ReshardError(
                "source pieces cover %d of %d elements of a "
                "destination shard of shape %s — source and "
                "destination arrays disagree"
                % (covered, shard_elems, dshape))
        out_by_dev[dev] = buf

    _note_peak(max_shard, blk, label)
    stacked = jax.make_array_from_single_device_arrays(
        shape, dst_sharding, [out_by_dev[d] for d in dst_map])
    out, total = _general_transition(dst_sharding, shape, dtype)(stacked)
    got = int(jax.device_get(total))
    want = len(dst_map)
    if got != want:
        raise ReshardError(
            "redistribute integrity check failed for %r: "
            "psum(shards)=%d expected %d" % (label, got, want))
    telemetry.counter("mx_reshard_transitions_total", kind=label).inc()
    return out


def redistribute_tree(tree, dst_shardings, blk_bytes=None,
                      label: str = "tree"):
    """``redistribute`` mapped over a pytree. ``dst_shardings`` is
    either one NamedSharding applied to every leaf or a matching
    pytree of them."""
    import jax

    leaves, treedef = jax.tree_util.tree_flatten(tree)
    if isinstance(dst_shardings, (list, tuple)) or hasattr(
            dst_shardings, "keys"):
        shardings = jax.tree_util.tree_flatten(dst_shardings)[0]
    else:
        shardings = [dst_shardings] * len(leaves)
    if len(shardings) != len(leaves):
        raise ReshardError("dst_shardings does not match tree arity")
    out = [redistribute(x, s, blk_bytes, label)
           for x, s in zip(leaves, shardings)]
    return jax.tree_util.tree_unflatten(treedef, out)


# ----------------------------------------------------------------------
# checkpoint sharding manifest (docs/ELASTIC.md)
# ----------------------------------------------------------------------
def sharding_manifest(trainer) -> dict:
    """Logical-sharding section for the checkpoint manifest
    (model.py manifest version 2): enough layout to reshard the saved
    state onto ANY mesh without unpickling the payload — device count,
    mesh axes, per-param PartitionSpec, and (under ZeRO) the fragment
    geometry + dcn ownership permutation of arxiv 2004.13336."""
    sec = {
        "version": 1,
        "n_devices": len(trainer._contexts),
        "contexts": [str(c) for c in trainer._contexts],
        "mesh_axes": ["dp"],
        "layout": "replicated",
        "partition_spec": None,
        "params": {},
    }
    zero = getattr(trainer, "_zero", None)
    if zero is None or zero is False or isinstance(zero, bool):
        return sec
    sec["layout"] = "zero"
    sec["mesh_axes"] = list(zero._axis_names)
    sec["partition_spec"] = list(zero._axis_names) \
        if zero._dcn_axis else ["dp"]
    sec["owner"] = list(zero._owner)
    sec["n_dcn"] = int(zero._n_dcn)
    sec["quantized"] = bool(zero._quant)
    for it in zero._items:
        sec["params"][it.param.name] = {
            "size": int(it.size), "frag": int(it.frag),
            "offset": int(it.offset), "group": int(it.gi),
        }
    return sec
