"""KVStore — parameter synchronization facade.

Ref: src/kvstore/ (KVStoreLocal, comm.h device rings, kvstore_nccl.h) and
python/mxnet/kvstore/ (KVStoreBase plugin registry, kvstore.py).

TPU-native mapping (SURVEY.md §5.8): the reference needs four transports
(CPU reduce, GPU-direct rings, NCCL, ps-lite RPC) because GPUs + NICs
are separate fabrics. On TPU a single mechanism covers them: XLA
collectives over ICI. ``KVStore('tpu')`` — the north star's peer of
KVStore('nccl') — reduces per-key gradients with one jitted psum-style
program across local devices; multi-host extends the same path over
jax.distributed (round-2 milestone for the process-group transport).
'local'/'device' are kept as API-compatible in-process modes.
"""
from __future__ import annotations

from typing import Any, Callable, Dict, List, Optional

from ..base import MXNetError, Registry
from .. import ndarray as nd
from ..ndarray import NDArray
from .base import KVStoreBase

__all__ = ["KVStore", "KVStoreBase", "create", "device_mesh"]


def _normalize(key):
    return str(key)


def _np_prod(shape) -> int:
    out = 1
    for d in shape:
        out *= int(d)
    return out


def _quantizable_dtype(arr) -> bool:
    """Only float payloads of at most 32 bits ride the quantized wire
    (f64 would silently lose range; integer grads are exact by
    contract)."""
    import numpy as _np2
    try:
        dt = _np2.dtype(arr.dtype)
    except Exception:
        return False
    return dt.kind == "f" and dt.itemsize <= 4


# process-wide device-mesh cache: the grouped kvstore reducer and the
# ZeRO weight-update engine (gluon/zero.py) both build 1-d (or dcn x ici)
# meshes over the SAME replica device sets every step — jax Mesh
# construction is cheap but not free, and sharing one cache keeps the
# two paths' device ordering contract identical.
_MESH_CACHE: Dict = {}

_COMPRESSION_WARNED = False     # one deprecation warning per process


def device_mesh(devices, axis_names=("kv",), shape=None):
    """A cached ``jax.sharding.Mesh`` over `devices` (list order is the
    mesh's flat order). `shape` reshapes the device list for
    multi-axis meshes (e.g. ``(n_dcn, n_ici)`` with
    ``axis_names=("dcn", "dp")``)."""
    import numpy as _np
    from jax.sharding import Mesh
    key = (tuple(id(d) for d in devices), tuple(axis_names),
           tuple(shape) if shape else None)
    m = _MESH_CACHE.get(key)
    if m is None:
        arr = _np.array(devices)
        if shape:
            arr = arr.reshape(shape)
        m = Mesh(arr, tuple(axis_names))
        _MESH_CACHE[key] = m
    return m


class _CollectiveReducer:
    """Grouped allreduce over the local devices that hold the replicas.

    The reference batches keys into one grouped ncclAllReduce launch
    (kvstore_nccl.h :: KVStoreNCCL). TPU equivalent: assemble each
    key's per-device replicas zero-copy into one global jax.Array
    sharded over a 1-d device mesh (make_array_from_single_device_arrays),
    then ONE jitted XLA program sums every key over the mesh axis with
    replicated outputs — XLA lowers each sum to an all-reduce riding
    ICI and its latency-hiding scheduler overlaps them. Replica results
    come back zero-copy via addressable_shards.

    Quantized mode (MXNET_KVSTORE_QUANTIZE, docs/QUANTIZE.md): the
    grouped reduce becomes ONE watched shard_map program per key-group
    signature — every key's local gradient concatenated into a flat
    per-device buffer, error-feedback residual added, then the EQuARX
    int8/fp8 allreduce of parallel/quantize.py (all_to_all of the
    1-byte payload + f32 scale sidecar, dequant-accumulate in f32,
    re-quantized all-gather). The per-device residual rides as a
    program input/output and lives in the caller-owned store (the
    KVStore, so Trainer.save_states can checkpoint it). With the
    config off this path is never entered — the classic reduce is
    byte-for-byte unchanged.
    """

    def __init__(self):
        self._jitted = {}
        self._quant_watched = {}

    def _mesh(self, devices):
        return device_mesh(devices, ("kv",))

    def _sum_fn(self, mesh):
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        key = id(mesh)
        fn = self._jitted.get(key)
        if fn is None:
            def allsum(*xs):
                return tuple(jnp.sum(x, axis=0) for x in xs)
            fn = jax.jit(allsum, out_shardings=NamedSharding(mesh, P()))
            self._jitted[key] = fn
        return fn

    # comm-profile identity (commwatch): the local reducer's grouped
    # allreduce rides the in-process 'kv' mesh axis
    _comm_axis = "kv"

    # ------------------------------------------------------------------
    # quantized grouped reduce (MXNET_KVSTORE_QUANTIZE)
    # ------------------------------------------------------------------
    def _quant_mesh_axis(self, devices):
        """(mesh, axis name) the quantized program runs over. The axis
        name doubles as the commwatch label, so the dist reducer
        overrides this to put cross-process traffic on 'kv.dcn'."""
        return self._mesh(devices), "kv"

    def _quant_fn(self, mesh, axis, cfg, sig):
        """One watched shard_map program per (mesh, config, group
        signature): flat-concat every key's local gradient, apply the
        error-feedback residual, run the EQuARX quantized allreduce,
        split the dequantized result back per key. Residual rides as
        arg 0 / output 0."""
        import jax
        import jax.numpy as jnp
        from .. import compilewatch
        from ..parallel import quantize as qz
        from ..parallel.collectives import shard_map
        from jax.sharding import PartitionSpec as P

        key = (id(mesh), axis, cfg.key(), sig)
        fn = self._quant_watched.get(key)
        if fn is not None:
            return fn
        nkeys = len(sig)

        def body(res, *rest):
            locs = rest[:nkeys]
            qkey = None
            if cfg.stochastic and cfg.mode == "int8":
                qkey = jax.random.PRNGKey(rest[nkeys])
            parts = [a.reshape(-1).astype(jnp.float32) for a in locs]
            g = jnp.concatenate(parts) if len(parts) > 1 else parts[0]
            out, new_res = qz.quantized_allreduce(
                g, axis, None, cfg, residual=res.reshape(-1), key=qkey)
            outs, off = [], 0
            for a in locs:
                size = int(_np_prod(a.shape[1:]))
                outs.append(out[off:off + size]
                            .reshape(a.shape[1:]).astype(a.dtype))
                off += size
            return (new_res[None],) + tuple(outs)

        extra = 1 if cfg.stochastic and cfg.mode == "int8" else 0
        in_specs = (P(axis),) * (1 + nkeys) + (P(),) * extra
        out_specs = (P(axis),) + (P(),) * nkeys
        mapped = shard_map(body, mesh=mesh, in_specs=in_specs,
                           out_specs=out_specs, check_vma=False)
        arg_names = ["residual"] + ["grad%d" % i for i in range(nkeys)] \
            + (["qseed"] if extra else [])
        fn = compilewatch.watched_jit(
            mapped, "kv.quant_reduce", site="kvstore",
            arg_names=arg_names,
            instance="kv.quant/%s/%dkeys" % (axis, nkeys),
            static_repr="mode=%s block=%d tier=%s keys=%d" % (
                cfg.mode, cfg.block, cfg.tier, nkeys))
        self._quant_watched[key] = fn
        return fn

    def quant_reduce_groups(self, groups, keys, cfg, kv):
        """Quantized grouped allreduce (docs/QUANTIZE.md). `groups` as
        in :meth:`reduce_groups`; `keys` names each group's store key
        (the error-feedback residual identity); `kv` is the owning
        KVStore, which holds the residual state (`kv._quant_state`) and
        any checkpoint-restored residuals pending re-injection
        (`kv._quant_restore`). Returns per-key per-device reduced
        replicas like :meth:`reduce_groups`."""
        import jax
        import numpy as _np2
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from .. import commwatch, profiler

        from ..parallel import quantize as qz
        # guard events attribute the mode even when it was switched on
        # env-lessly through the legacy compression route
        qz.note_active(cfg)
        devices = [b.device for b in groups[0]]
        ndev = len(devices)
        mesh, axis = self._quant_mesh_axis(devices)
        nglobal = int(mesh.devices.size)
        if nglobal == 1:
            # truly nothing on the wire. The GLOBAL mesh size decides,
            # not the local replica count: a dist store with one device
            # per process still reduces across processes
            return [[g[0]] for g in groups]
        sizes = [_np_prod(b[0].shape) for b in groups]
        S = int(sum(sizes))
        sig = tuple((tuple(b[0].shape), str(b[0].dtype)) for b in groups)

        rkey = (tuple(keys), axis)
        ent = kv._quant_state.get(rkey)
        if ent is None:
            restore = getattr(kv, "_quant_restore", None) or {}
            base = _np2.zeros(S, _np2.float32)
            off = 0
            for k, size in zip(keys, sizes):
                pend = restore.pop(k, None)
                if pend is not None:
                    # a checkpointed residual is the carried correction
                    # summed over the devices THIS process exported
                    # (quant_residuals_export) — split back over the
                    # same local device count so the export->restore
                    # round trip conserves the sum exactly. In dist
                    # mode residuals are per-process state: each rank
                    # saves/loads its own share (like every per-rank
                    # file), never a global total divided globally.
                    base[off:off + size] = _np2.asarray(
                        pend, _np2.float32).reshape(-1) / ndev
                off += size
            ent = {"res": [jax.device_put(base, d) for d in devices],
                   "keys": tuple(keys), "sizes": tuple(sizes)}
            kv._quant_state[rkey] = ent

        sh = NamedSharding(mesh, P(axis))

        def stack(bufs, shape):
            shards = [b.reshape((1,) + shape) for b in bufs]
            return jax.make_array_from_single_device_arrays(
                (nglobal,) + tuple(shape), sh, shards)

        args = [stack(ent["res"], (S,))]
        for bufs in groups:
            args.append(stack(bufs, tuple(bufs[0].shape)))
        if cfg.stochastic and cfg.mode == "int8":
            kv._quant_step = getattr(kv, "_quant_step", 0) + 1
            args.append(jnp.uint32(kv._quant_step))
        fn = self._quant_fn(mesh, axis, cfg, sig)
        watching = commwatch.enabled() or profiler.state() == "run"
        # the grad sync blocks the step thread here — its wire time is
        # EXPOSED comm, same attribution as the classic comm_span path
        with commwatch.program_watch(("kv.quant", axis, sig),
                                     "kv.quant_reduce", exposed=True):
            outs = fn(*args)
            if watching:
                jax.block_until_ready(outs)
        by_dev = {s.device: s.data for s in outs[0].addressable_shards}
        ent["res"] = [by_dev[d].reshape(-1) for d in devices]
        results = []
        for o in outs[1:]:
            by_dev = {s.device: s.data for s in o.addressable_shards}
            results.append([by_dev[d] for d in devices])
        return results

    @staticmethod
    def _group_bytes(groups) -> int:
        """Logical allreduce payload: one replica buffer per key (the
        reduced size — NCCL-tests' message size convention)."""
        import numpy as _np2
        total = 0
        for bufs in groups:
            b = bufs[0]
            try:
                total += int(b.size) * _np2.dtype(b.dtype).itemsize
            except Exception:
                pass
        return total

    def reduce_groups(self, groups):
        """groups: list of per-key replica lists (jax arrays, one per
        distinct device; same device order for every key). Returns a
        list of per-key lists of per-device reduced replicas."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        devices = [b.device for b in groups[0]]
        ndev = len(devices)
        if ndev == 1:
            return [[g[0]] for g in groups]
        from .. import commwatch, profiler
        # profiler-only runs (telemetry off) still get spans — with
        # real payload bytes, not zeros
        watching = commwatch.enabled() or profiler.state() == "run"
        with commwatch.comm_span(
                "allreduce", self._comm_axis,
                self._group_bytes(groups) if watching else 0,
                ndev, key="%d keys" % len(groups)):
            mesh = self._mesh(devices)
            sh = NamedSharding(mesh, P("kv"))
            gas = []
            for bufs in groups:
                shards = [b.reshape((1,) + b.shape) for b in bufs]
                gas.append(jax.make_array_from_single_device_arrays(
                    (ndev,) + tuple(bufs[0].shape), sh, shards))
            outs = self._sum_fn(mesh)(*gas)
            if watching:
                # the jitted call returns unready arrays; the span must
                # time collective COMPLETION, not host dispatch, or the
                # bandwidth histograms read enqueue time
                jax.block_until_ready(outs)
            results = []
            for o in outs:
                by_dev = {s.device: s.data for s in o.addressable_shards}
                results.append([by_dev[d] for d in devices])
        return results


@KVStoreBase.register("local")
@KVStoreBase.register("device")
@KVStoreBase.register("tpu")
class KVStore(KVStoreBase):
    """In-process key-value store with engine-async reduce.

    ref parity: KVStoreLocal::PushImpl aggregates per-key gradient lists
    (CommCPU/CommDevice); KVStoreNCCL groups keys into one collective.
    Here the reduce for N device replicas is a single XLA program per
    key; cross-device traffic rides ICI via device_put/psum.
    """

    def __init__(self, name: str = "local"):
        self._type = name
        self._store: Dict[str, NDArray] = {}
        self._updater: Optional[Callable] = None
        self._optimizer = None
        self._opt_states: Dict[str, Any] = {}
        self._reducer = _CollectiveReducer()
        self._compression = None          # (type, threshold)
        self._quant_state: Dict = {}      # group key -> EF residual entry
        self._quant_restore: Dict = {}    # key -> np residual (from ckpt)
        self._quant_step = 0              # stochastic-rounding seed clock

    # ------------------------------------------------------------------
    def set_gradient_compression(self, compression_params):
        """MXNet 1.x gradient-compression surface (ref:
        src/kvstore/gradient_compression.cc). The legacy 1-bit/2-bit
        threshold codecs are DEPRECATED here: every compression type is
        served by the int8 quantized collectives with error feedback
        (parallel/quantize.py, docs/QUANTIZE.md) — blockwise-scaled
        int8 preserves gradient magnitudes the fixed +-threshold codec
        destroyed, and the EF residual semantics are the same. The
        ``threshold`` parameter is accepted and ignored (one warning);
        ``MXNET_KVSTORE_QUANTIZE`` is the native spelling."""
        ctype = compression_params.get("type", "2bit")
        if ctype not in ("1bit", "2bit"):
            raise MXNetError("unsupported compression type %r" % ctype)
        global _COMPRESSION_WARNED
        if not _COMPRESSION_WARNED:
            _COMPRESSION_WARNED = True
            import warnings
            warnings.warn(
                "set_gradient_compression(type=%r) now rides the int8 "
                "quantized collectives with error feedback "
                "(MXNET_KVSTORE_QUANTIZE, docs/QUANTIZE.md); the "
                "legacy threshold parameter is ignored" % ctype,
                FutureWarning, stacklevel=2)
        self._compression = (ctype,
                             float(compression_params.get("threshold", 0.5)))

    def _compress(self, key, vals):
        """Legacy hook — compression is applied ON THE WIRE by the
        quantized grouped reduce now (see set_gradient_compression);
        the push-side values are untouched."""
        return vals

    def _quant_cfg(self):
        """The active wire-quantization config: MXNET_KVSTORE_QUANTIZE
        env, or the int8 default when the legacy compression API asked
        for it. None = classic f32 collectives."""
        from ..parallel import quantize as qz
        cfg = qz.from_env()
        if cfg is None and self._compression is not None:
            cfg = qz.QuantConfig()
        return cfg

    # ------------------------------------------------------------------
    # error-feedback residual checkpointing (docs/QUANTIZE.md): the
    # carried correction is real optimizer-adjacent state — dropping it
    # on resume silently loses the accumulated sub-grid gradient mass.
    # ------------------------------------------------------------------
    def quant_residuals_export(self) -> Dict[str, Any]:
        """{store key: total residual (numpy, flat)} — per-key sums of
        the per-device error-feedback residuals (the carry identity
        conserves the SUM, so that is what a checkpoint must hold)."""
        import numpy as _np2
        out: Dict[str, Any] = {}
        for ent in self._quant_state.values():
            total = None
            for dev_res in ent["res"]:
                a = _np2.asarray(dev_res, _np2.float32)
                total = a if total is None else total + a
            off = 0
            for k, size in zip(ent["keys"], ent["sizes"]):
                out[k] = total[off:off + size].copy()
                off += size
        return out

    def quant_residuals_restore(self, residuals: Dict[str, Any]):
        """Queue checkpointed residuals for re-injection at the next
        grouped reduce (the group layout is only known then)."""
        self._quant_state.clear()
        self._quant_restore = dict(residuals or {})

    @property
    def type(self) -> str:
        return self._type

    @property
    def rank(self) -> int:
        return 0

    @property
    def num_workers(self) -> int:
        return 1

    # ------------------------------------------------------------------
    def init(self, key, value):
        keys, values = self._key_value(key, value)
        for k, v in zip(keys, values):
            vv = v[0] if isinstance(v, (list, tuple)) else v
            self._store[k] = vv.copy()

    def push(self, key, value, priority=0):
        keys, values = self._key_value(key, value)
        for k, v in zip(keys, values):
            vals = v if isinstance(v, (list, tuple)) else [v]
            vals = self._compress(k, vals)
            if k not in self._store:
                raise MXNetError("key %s not initialized in kvstore" % k)
            target = self._store[k]
            reduced = self._reduce(vals, target.ctx, key=k)
            if self._updater is not None:
                self._updater(k, reduced, target)
            else:
                target._set_jax(reduced._jax())

    def pull(self, key, out=None, priority=0, ignore_sparse=True):
        keys, outs = self._key_value(key, out)
        for k, o in zip(keys, outs):
            src = self._store.get(k)
            if src is None:
                raise MXNetError("key %s not initialized in kvstore" % k)
            dsts = o if isinstance(o, (list, tuple)) else [o]
            for d in dsts:
                src.copyto(d)

    def pushpull(self, key, value, out=None, priority=0):
        """Fused allreduce (ref: KVStoreBase.pushpull — the Horovod-style
        API). push (sum) then broadcast; one engine-async chain."""
        keys, values = self._key_value(key, value)
        _, outs = self._key_value(key, out if out is not None else value)
        for k, v, o in zip(keys, values, outs):
            vals = v if isinstance(v, (list, tuple)) else [v]
            vals = self._compress(k, vals)
            dsts = o if isinstance(o, (list, tuple)) else [o]
            reduced = self._reduce(vals, vals[0].ctx, key=k)
            for d in dsts:
                reduced.copyto(d)

    def broadcast(self, key, value, out, priority=0):
        self.init(key, value)
        self.pull(key, out=out, priority=priority)

    def row_sparse_pull(self, key, out=None, priority=0, row_ids=None):
        """Pull only the requested rows as RowSparseNDArrays (ref:
        kvstore.py :: row_sparse_pull — the sparse-embedding DP path:
        each device fetches just the rows its batch touches)."""
        if row_ids is None:
            return self.pull(key, out=out, priority=priority)
        from ..ndarray.sparse import RowSparseNDArray
        import numpy as _np
        import jax.numpy as jnp
        keys, outs = self._key_value(key, out)
        _, rids = self._key_value(key, row_ids)
        for k, o, rid in zip(keys, outs, rids):
            src = self._store.get(k)
            if src is None:
                raise MXNetError("key %s not initialized in kvstore" % k)
            dense = src._jax()
            dsts = o if isinstance(o, (list, tuple)) else [o]
            rlist = rid if isinstance(rid, (list, tuple)) else [rid] * len(dsts)
            for d, r in zip(dsts, rlist):
                if not isinstance(d, RowSparseNDArray):
                    # ref raises for non-row_sparse outs; silently
                    # zero-filling unrequested rows would corrupt them
                    raise MXNetError(
                        "row_sparse_pull requires RowSparseNDArray "
                        "outputs (got stype %r)" % d.stype)
                rows = _np.unique(_np.asarray(
                    r.asnumpy() if hasattr(r, "asnumpy") else r)
                    .astype(_np.int64))
                vals = dense[jnp.asarray(rows)]
                d._set_sparse(jnp.asarray(rows.astype(_np.int32)), vals)

    # ------------------------------------------------------------------
    def set_optimizer(self, optimizer):
        from .. import optimizer as opt_mod
        self._optimizer = optimizer
        self._updater = opt_mod.get_updater(optimizer)

    def is_capable(self, capability: str) -> bool:
        return {"optimizer": True}.get(capability, False)

    def _set_updater(self, updater):
        self._updater = updater

    def save_optimizer_states(self, fname, dump_optimizer=False):
        if self._updater is None:
            raise MXNetError("no optimizer on kvstore")
        with open(fname, "wb") as f:
            f.write(self._updater.get_states(dump_optimizer))

    def load_optimizer_states(self, fname):
        if self._updater is None:
            raise MXNetError("no optimizer on kvstore")
        with open(fname, "rb") as f:
            self._updater.set_states(f.read())

    # ------------------------------------------------------------------
    def pushpull_list(self, keys, values, outs=None, priority=0):
        """Batched allreduce of many keys in ONE compiled collective
        program (the KVStoreNCCL grouped-launch analogue). `values` is a
        list of per-key replica lists; results are written into `outs`
        (defaults to `values`) and into the store."""
        keys = [_normalize(k) for k in keys]
        outs = values if outs is None else outs
        vlists = [v if isinstance(v, (list, tuple)) else [v] for v in values]
        if self._compression is not None:
            vlists = [self._compress(k, v) for k, v in zip(keys, vlists)]
        olists = [o if isinstance(o, (list, tuple)) else [o] for o in outs]
        # partition keys by replica-device signature: one grouped
        # collective per distinct device set (reduce_groups requires a
        # uniform device list across its keys)
        from ..ndarray.sparse import RowSparseNDArray

        def _update_store(key, buf, dev2rep=None):
            # commit the reduced value on the STORE entry's device, not
            # wherever the reduce happened (same placement contract as
            # push(): a later pull/compute trusts store.ctx); dev2rep
            # reuses an existing replica on the wanted device when the
            # grouped collective already produced one there
            store = self._store.get(key)
            if store is None:
                return
            import jax
            want = store.ctx.jax_device
            rep = (dev2rep or {}).get(want)
            if rep is None:
                rep = buf if buf.device == want \
                    else jax.device_put(buf, want)
            store._set_jax(rep)

        by_sig: Dict[tuple, list] = {}
        for i, vals in enumerate(vlists):
            if any(isinstance(v, RowSparseNDArray) for v in vals):
                red = self._reduce(vals, vals[0].ctx)
                for d in olists[i]:
                    red.copyto(d)
                _update_store(keys[i], red._jax())
                continue
            devs = [v._jax().device for v in vals]
            if len(vals) > 1 and len(set(devs)) == len(devs):
                by_sig.setdefault(tuple(id(d) for d in devs), []).append(i)
            else:
                red = self._reduce(vals, vals[0].ctx, key=keys[i])
                for d in olists[i]:
                    if d is not red:   # single-replica: grad IS the sum
                        red.copyto(d)
                _update_store(keys[i], red._jax())
        cfg = self._quant_cfg()
        for idx in by_sig.values():
            import jax
            # the quantizable float keys ride the wire-quantized grouped
            # program; anything else (f64, integer grads) stays on the
            # classic f32 collective — one grouped launch each
            q_idx, f_idx = [], []
            for i in idx:
                (q_idx if cfg is not None
                 and _quantizable_dtype(vlists[i][0]) else f_idx).append(i)
            batches = []
            if q_idx:
                batches.append((q_idx, self._reducer.quant_reduce_groups(
                    [[v._jax() for v in vlists[i]] for i in q_idx],
                    [keys[i] for i in q_idx], cfg, self)))
            if f_idx:
                batches.append((f_idx, self._reducer.reduce_groups(
                    [[v._jax() for v in vlists[i]] for i in f_idx])))
            for part, results in batches:
                for i, reps in zip(part, results):
                    dev2rep = {r.device: r for r in reps}
                    for d in olists[i]:
                        want = d.ctx.jax_device
                        rep = dev2rep.get(want)
                        d._set_jax(rep if rep is not None
                                   else jax.device_put(reps[0], want))
                    _update_store(keys[i], reps[0], dev2rep)
        return None

    def _reduce(self, vals: List[NDArray], ctx, key=None) -> NDArray:
        from ..ndarray.sparse import RowSparseNDArray, _SparseCot
        if all(isinstance(v, RowSparseNDArray) for v in vals) and vals:
            if len(vals) == 1:
                v = vals[0]
                if v.ctx == ctx:
                    return v
                from ..ndarray import sparse as sp
                out = sp.zeros("row_sparse", v.shape, ctx, v.dtype)
                return v.copyto(out)
            # COO merge of row-sparse gradients — only touched rows move
            import jax
            import jax.numpy as jnp
            import numpy as _np
            idx = _np.concatenate([_np.asarray(v._sp_indices) for v in vals])
            dat = _np.concatenate([_np.asarray(v._sp_data) for v in vals])
            cot = _SparseCot(jnp.asarray(idx), jnp.asarray(dat),
                             vals[0].shape)
            uniq, merged = cot.merged()
            dev = ctx.jax_device
            return RowSparseNDArray(jax.device_put(merged, dev),
                                    jax.device_put(uniq, dev),
                                    vals[0].shape, ctx)
        if len(vals) == 1:
            return vals[0].as_in_context(ctx)
        devs = [v._jax().device for v in vals]
        if len(set(devs)) == len(devs):
            # true collective: one XLA all-reduce over the replica mesh
            cfg = self._quant_cfg() if key is not None else None
            if cfg is not None and _quantizable_dtype(vals[0]):
                reps = self._reducer.quant_reduce_groups(
                    [[v._jax() for v in vals]], [key], cfg, self)[0]
            else:
                reps = self._reducer.reduce_groups(
                    [[v._jax() for v in vals]])[0]
            want = ctx.jax_device
            for d, rep in zip(devs, reps):
                if d == want:
                    return NDArray(rep, ctx)
            import jax
            return NDArray(jax.device_put(reps[0], want), ctx)
        # replicas share a device (no mesh to reduce over): tree-sum
        acc = vals[0].as_in_context(ctx)
        out = acc
        for v in vals[1:]:
            out = out + v.as_in_context(ctx)
        return out

    @staticmethod
    def _key_value(key, value):
        if isinstance(key, (list, tuple)):
            return [_normalize(k) for k in key], list(value)
        return [_normalize(key)], [value]


def create(name: str = "local") -> KVStoreBase:
    """Ref: kvstore.create / KVStore::Create. local/device/tpu are
    in-process; dist_* joins the multi-process group over
    jax.distributed (DMLC_* env rendezvous, see mxnet_tpu.dist)."""
    if not isinstance(name, str):
        raise TypeError("name must be a string")
    if name.startswith("dist") or name.startswith("p3"):
        from . import dist as _dist  # registers KVStoreDist/P3Store
    elif name == "horovod":
        from . import horovod as _hvd  # registers the plugin (gated)
    kls = KVStoreBase.get(name)
    if kls is None:
        raise MXNetError("unknown kvstore type %r" % name)
    import inspect
    try:
        takes_name = len(inspect.signature(kls).parameters) >= 1
    except (TypeError, ValueError):
        takes_name = False
    return kls(name) if takes_name else kls()
