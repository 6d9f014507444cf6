"""ZeRO-style weight-update sharding for the data-parallel Trainer.

The replicated data-parallel step (``Trainer.step`` with N device
replicas) allreduces gradients and then runs the SAME optimizer update
N times — every replica holds a full copy of the optimizer state
(momentum, Adam m/v) and burns full-model update FLOPs to compute
results identical to its neighbors'. "Automatic Cross-Replica Sharding
of Weight Update in Data-Parallel Training" (arxiv 2004.13336) removes
that redundancy without changing the math:

1. **reduce-scatter** the gradients over the replica set instead of
   allreducing them — each replica receives the fully-reduced values
   for a 1/N shard of the flattened parameter space;
2. **update the shard only** — optimizer state is ALLOCATED sharded
   (one 1/N slice per replica, never materialized whole), so state HBM
   and update FLOPs both drop N x;
3. **all-gather** the updated parameters back so every replica again
   holds the full weights for the next forward.

RS + AG move exactly the bytes one allreduce moves (in bus-traffic
terms: S*(n-1)/n each vs S*2(n-1)/n — tools/zero_micro.py gates this),
so the memory/FLOP win is free on the wire.

Layout: parameters are grouped by dtype; within a group each param is
flattened, zero-padded to a multiple of N (the uneven-shard padding of
``parallel.collectives.pad_to_multiple``) and split into N fragments;
replica r owns fragment r of EVERY param — a contiguous ``(C,)`` slice
of the group's fragment-major space, where the per-param fragments sit
at static offsets. Keeping per-param fragment boundaries uniform across
replicas is what makes the whole RS -> shard-update -> AG step a single
SPMD program (one ``shard_map`` traced once, compiled once, watched by
compilewatch as ``zero.step``): per-fragment hyperparameters (lr, wd —
and Adam's folded bias correction) ride as device tensors, and the
owned weight fragment is dynamically sliced by
``parallel.collectives.shard_owner_index``.

With ``MXNET_ZERO_DCN=k`` the replica set is treated as a k-slice
dcn x ici hierarchy: RS stages as RS(ici) -> RS(dcn) and AG as
AG(dcn) -> AG(ici) (the arxiv 2112.01075 redistribution decomposition),
so the cross-slice tier only ever carries 1/n_ici of the payload. The
resulting shard-ownership permutation is honored by the checkpoint
gather/scatter below.

GradGuard: with a guard active the step splits into two watched
programs — ``zero.reduce`` (RS + per-fragment finiteness/sqnorm flags,
combined across replicas INSIDE the program) and ``zero.update``
(masked/clipped shard update + AG). The host reads one small report
vector per step (the same single extra sync the replicated guard
costs) and applies the shared ``GradGuard.evaluate`` policy; zero/clip
verdicts reach the scattered shards as a per-fragment coefficient
vector.

Checkpoints stay topology-portable: ``gather_states()`` reassembles
the canonical replicated layout ({index: state} exactly as
``optimizer.Updater`` pickles it) on save, ``scatter_states()``
re-slices a canonical checkpoint onto the current shard layout on load
— so a run sharded over 8 replicas restores on 2, on 1 (plain
replicated Trainer), or vice versa.

Observable divergence from the replicated path (documented in
docs/ZERO.md): after ``step()`` the per-replica gradient arrays still
hold their LOCAL pre-reduction values — the reduced gradients only
ever exist scattered inside the step program (writing them back would
cost an extra all-gather and defeat the comm parity).
"""
from __future__ import annotations

import logging
import pickle
from typing import Dict, List, Optional, Tuple

import numpy as np

from ..base import MXNetError
from .. import telemetry

__all__ = ["ZeroEngine", "eligibility", "DONE", "SKIPPED", "BAIL"]

_LOG = logging.getLogger("mxnet_tpu.zero")

DONE = "done"          # sharded step executed (params/states advanced)
SKIPPED = "skipped"    # guard skipped the step (counted, nothing updated)
BAIL = "bail"          # structural mismatch — caller falls back to classic


def _frag_len(size: int, n: int) -> int:
    return -(-size // n)


class _Item:
    __slots__ = ("idx", "param", "shape", "size", "frag", "offset", "fi",
                 "gi", "pos")

    def __init__(self, idx, param, shape, size, frag, offset, fi, gi, pos):
        self.idx = idx          # Trainer parameter index (optimizer key)
        self.param = param
        self.shape = shape
        self.size = size
        self.frag = frag        # per-replica fragment length (padded)
        self.offset = offset    # offset of this fragment in the group shard
        self.fi = fi            # flat fragment index (hyperparam/report row)
        self.gi = gi            # group index
        self.pos = pos          # position in the flat grad/weight arg lists


class _Group:
    __slots__ = ("dtype", "items", "C")

    def __init__(self, dtype):
        self.dtype = dtype
        self.items: List[_Item] = []
        self.C = 0


# ---------------------------------------------------------------------------
# eligibility ladder (docs/ZERO.md) — one reason string per rung
# ---------------------------------------------------------------------------
def eligibility(trainer) -> Tuple[bool, Optional[str]]:
    """(ok, reason-if-not) for sharding this Trainer's update. The
    caller decides whether a False is silent (MXNET_ZERO off) or a
    logged fallback (MXNET_ZERO=1 but the ladder fails)."""
    from .. import config as _cfg
    from .. import kvstore as kvs_mod
    if not _cfg.get("MXNET_ZERO"):
        return False, None
    ctxs = trainer._contexts
    if len(ctxs) < 2:
        return False, "single replica (need >=2 data-parallel devices)"
    devices = [c.jax_device for c in ctxs]
    if len(set(devices)) != len(devices):
        return False, "replica contexts share a device (no mesh to shard " \
            "over)"
    if trainer._update_on_kvstore:
        return False, "update_on_kvstore=True (the kvstore owns the update)"
    kv = trainer._kvstore
    if kv is not None and type(kv) is not kvs_mod.KVStore:
        return False, "kvstore %r is not the in-process store (dist ZeRO " \
            "needs the multi-process reduce-scatter path)" % (
                getattr(kv, "type", type(kv).__name__),)
    if trainer._compression_params:
        return False, "gradient compression rides the kvstore push path"
    if trainer._optimizer.zero_fragment_update() is None:
        return False, "optimizer %s has no elementwise in-graph fragment " \
            "form" % type(trainer._optimizer).__name__
    total = 0
    live = 0
    for p in trainer._params:
        if p.grad_req == "null":
            continue
        if p.grad_req != "write":
            return False, "parameter %s has grad_req=%r (need 'write')" \
                % (p.name, p.grad_req)
        if getattr(p, "_stype", "default") != "default" or \
                getattr(p, "_grad_stype", "default") != "default":
            return False, "parameter %s is sparse" % p.name
        if p._data is not None:
            live += 1
            total += int(np.prod(p.shape))
    if not live:
        return False, "no initialized trainable parameters"
    min_size = _cfg.get("MXNET_ZERO_MIN_SIZE")
    if min_size and total < min_size:
        return False, "model too small (%d < MXNET_ZERO_MIN_SIZE=%d)" \
            % (total, min_size)
    return True, None


# ---------------------------------------------------------------------------
# the engine
# ---------------------------------------------------------------------------
class ZeroEngine:
    """Owns the shard layout, the sharded optimizer state and the
    compiled RS -> shard-update -> AG programs for one Trainer."""

    def __init__(self, trainer):
        from .. import config as _cfg
        from ..parallel import quantize as qz
        self._trainer = trainer
        self._contexts = list(trainer._contexts)
        self._devices = [c.jax_device for c in self._contexts]
        self._n = len(self._devices)
        # wire quantization (MXNET_KVSTORE_QUANTIZE, docs/QUANTIZE.md):
        # resolved once at engine construction — the RS/AG quantize is
        # BAKED into the compiled step programs, and the EF residuals
        # below are allocated to match
        self._quant = qz.from_env()
        qz.note_active(self._quant)
        n_dcn = int(_cfg.get("MXNET_ZERO_DCN") or 0)
        if n_dcn > 1 and self._n % n_dcn == 0:
            self._n_dcn = n_dcn
            self._axis_names = ("dcn", "dp")
            self._mesh_shape = (n_dcn, self._n // n_dcn)
            self._dcn_axis = "dcn"
        else:
            if n_dcn > 1:
                _LOG.warning(
                    "MXNET_ZERO_DCN=%d does not divide the replica count "
                    "%d; using a flat dp mesh", n_dcn, self._n)
            self._n_dcn = 1
            self._axis_names = ("dp",)
            self._mesh_shape = None
            self._dcn_axis = None
        # shard-ownership permutation: device list position p ->
        # owned global fragment index (see collectives.shard_owner_index)
        if self._dcn_axis is None:
            self._owner = list(range(self._n))
        else:
            n_ici = self._n // self._n_dcn
            self._owner = [(p % n_ici) * self._n_dcn + (p // n_ici)
                           for p in range(self._n)]
        self._groups: List[_Group] = []
        self._items: List[_Item] = []
        self._names: List[str] = []
        self._state_nd: List[List[List]] = []   # [group][state kind][device]
        self._nstates = 0
        self._hyper_key = None
        self._structure = None
        self._programs: Dict[str, object] = {}
        # deferred modelwatch report from the previous sampled step:
        # ("full"|"usq", names, device handle, rescale) — read at the
        # next step's single host sync (modelwatch.py)
        self._mw_pending = None
        self._build_layout()

    # ------------------------------------------------------------------
    # layout + sharded state allocation
    # ------------------------------------------------------------------
    def _trainable(self):
        out = []
        for i, p in enumerate(self._trainer._params):
            if p.grad_req == "null" or p._data is None:
                continue
            out.append((i, p))
        return out

    def _signature(self):
        return tuple((i, p.shape, str(p.list_data()[0].dtype))
                     for i, p in self._trainable())

    def _build_layout(self):
        from .. import ndarray as nd
        opt = self._trainer._optimizer
        frag = opt.zero_fragment_update()
        if frag is None:
            raise MXNetError("optimizer %s has no ZeRO fragment form"
                             % type(opt).__name__)
        self._nstates, self._hyper_key, self._frag_fn = frag
        self._structure = self._signature()
        self._groups, self._items, self._names = [], [], []
        by_dtype: Dict[str, _Group] = {}
        for pos, (i, p) in enumerate(self._trainable()):
            dt = str(p.list_data()[0].dtype)
            g = by_dtype.get(dt)
            if g is None:
                g = by_dtype[dt] = _Group(dt)
                self._groups.append(g)
            size = int(np.prod(p.shape)) if p.shape else 1
            item = _Item(i, p, tuple(p.shape), size,
                         _frag_len(size, self._n), g.C, 0, 0, pos)
            g.C += item.frag
            g.items.append(item)
        for gi, g in enumerate(self._groups):
            for it in g.items:
                it.gi = gi
        for fi, it in enumerate(self._iter_items()):
            # group-major enumeration defines BOTH the fragment row in
            # the hyperparam/report vectors and the position in the
            # flat grad/weight argument lists
            it.fi = fi
            it.pos = fi
            self._items.append(it)
            self._names.append(it.param.name)
        # sharded state allocation: K tensors of (1, C) PER REPLICA —
        # this is the whole point: the full (size,)-shaped state never
        # exists anywhere
        self._state_nd = []
        for g in self._groups:
            kinds = []
            for _k in range(self._nstates):
                kinds.append([nd.zeros((1, g.C), ctx=ctx, dtype=g.dtype)
                              for ctx in self._contexts])
            self._state_nd.append(kinds)
        self._alloc_residuals()
        self._qstep = 0     # stochastic-rounding seed clock
        self._programs.clear()
        self._publish_gauges()

    def _alloc_residuals(self):
        """Error-feedback residuals for the quantized wire
        (docs/QUANTIZE.md): per group per replica, ONE local-gradient-
        domain buffer (1, n*C) for the RS hop(s) — each staged hop's
        rounding error is scattered into the rows its input covered —
        and ONE shard-domain (1, C) buffer for the re-quantized weight
        all-gather. Both are engine state: they ride checkpoints like
        the optimizer shards (gathered/scattered cross-topology)."""
        from .. import ndarray as nd
        self._gres_nd = []
        self._wres_nd = []
        if self._quant is None:
            return
        for g in self._groups:
            self._gres_nd.append(
                [nd.zeros((1, self._n * g.C), ctx=ctx,
                          dtype="float32") for ctx in self._contexts])
            self._wres_nd.append(
                [nd.zeros((1, g.C), ctx=ctx, dtype="float32")
                 for ctx in self._contexts])

    def _iter_items(self):
        for g in self._groups:
            for it in g.items:
                yield it

    # ------------------------------------------------------------------
    # memory accounting
    # ------------------------------------------------------------------
    def state_bytes_per_replica(self) -> int:
        return sum(g.C * np.dtype(g.dtype).itemsize * self._nstates
                   for g in self._groups)

    def replicated_state_bytes_per_replica(self) -> int:
        return sum(it.size * np.dtype(g.dtype).itemsize * self._nstates
                   for g in self._groups for it in g.items)

    def state_bytes_total(self) -> int:
        return self.state_bytes_per_replica() * self._n

    def _publish_gauges(self):
        shard_b = self.state_bytes_per_replica()
        repl_b = self.replicated_state_bytes_per_replica()
        nfrag = len(self._items)
        for ctx in self._contexts:
            telemetry.zero_shard_state(str(ctx), shard_b, nfrag, repl_b)

    # ------------------------------------------------------------------
    # program construction
    # ------------------------------------------------------------------
    def _mesh(self):
        from .. import kvstore as kvs_mod
        return kvs_mod.device_mesh(self._devices, self._axis_names,
                                   self._mesh_shape)

    def _stack_spec(self):
        from jax.sharding import PartitionSpec as P
        return P(self._axis_names if self._dcn_axis else "dp")

    def _program(self, variant: str):
        fn = self._programs.get(variant)
        if fn is None:
            fn = self._build_program(variant)
            self._programs[variant] = fn
        return fn

    def _build_program(self, variant: str):
        """Build one watched SPMD program. Variants:
        'step'   — fused RS -> shard-update -> AG (no guard);
        'reduce' — RS + cross-replica finiteness/sqnorm report;
        'update' — coefficient-masked shard update + AG.

        The '_mw' suffix of each (modelwatch.py, ISSUE 11) extends the
        in-program report with per-parameter stats computed ON THE
        SCATTERED SHARDS and combined by the same single psum the
        guard's fragment check uses: param sqnorms (each replica
        contributes its own weight fragment), post-update sqnorms
        (new - old per fragment, inside 'update_mw'/'step_mw'), and the
        summed per-replica LOCAL grad sqnorm — the noise-scale meter's
        'small batch' estimate, free because the pre-reduce gradients
        are the program's inputs. Still one host read per step."""
        import jax
        import jax.numpy as jnp
        from jax import lax
        from jax.sharding import PartitionSpec as P
        from .. import compilewatch
        from ..parallel import collectives as coll
        from ..parallel import quantize as qz

        n, groups, items = self._n, self._groups, self._items
        dcn = self._dcn_axis
        frag_fn = self._frag_fn
        K = self._nstates
        quant = self._quant
        all_axes = self._axis_names if dcn else "dp"
        mesh = self._mesh()
        spec_s, spec_r = self._stack_spec(), P()
        G = len(self._groups)

        def local_reduce(grads_loc, gres_loc=None, key=None):
            """Per-group reduce-scattered (C,) shard of the summed
            gradients (gradient replicas arrive as (1, *shape) local
            blocks of the stacked global). With quantization the RS
            rides the low-precision wire (parallel/quantize.py) and
            the per-group error-feedback residual `gres_loc` ((1, n*C)
            local buffers) is folded in / carried out — returns
            (shards, new_gres)."""
            shards, new_gres = [], []
            for gi, g in enumerate(groups):
                cols = []
                for it in g.items:
                    gg = grads_loc[it.pos].reshape(-1)
                    gg = coll.pad_to_multiple(gg, it.frag * n)
                    cols.append(gg.reshape(n, it.frag))
                gmat = jnp.concatenate(cols, axis=1) if len(cols) > 1 \
                    else cols[0]
                if quant is not None:
                    gin = gmat.astype(jnp.float32) \
                        + gres_loc[gi].reshape(n, g.C)
                    gkey = None if key is None else \
                        jax.random.fold_in(key, gi)
                    sh, err = qz.quantized_rs(gin, "dp", dcn, quant,
                                              key=gkey)
                    shards.append(sh.astype(gmat.dtype))
                    new_gres.append(err.reshape(1, n * g.C))
                else:
                    sh = coll.hierarchical_reduce_scatter(gmat, "dp",
                                                          dcn, 0)
                    shards.append(sh.reshape(-1))
            return shards, new_gres

        def local_update(shards, weights_loc, states_loc, lrs, wds,
                         rescale, coef, wres_loc=None, want_usq=False,
                         key=None):
            r_own = coll.shard_owner_index("dp", dcn)
            new_w = [None] * len(items)
            new_states = []
            new_wres = []
            usq = [None] * len(items) if want_usq else None
            for gi, g in enumerate(groups):
                gsh = shards[gi]
                w_frags, st_frags = [], [[] for _ in range(K)]
                for it in g.items:
                    gfrag = gsh[it.offset:it.offset + it.frag]
                    if coef is not None:
                        # coef==0 is the guard's ZERO verdict on a
                        # non-finite gradient: a multiply would keep
                        # NaN (NaN*0=NaN) — select, don't scale
                        c = coef[it.fi].astype(gfrag.dtype)
                        gfrag = jnp.where(c == 0,
                                          jnp.zeros_like(gfrag),
                                          gfrag * c)
                    wflat = coll.pad_to_multiple(
                        weights_loc[it.pos].reshape(-1), it.frag * n)
                    wfrag = lax.dynamic_slice(wflat, (r_own * it.frag,),
                                              (it.frag,))
                    sts = tuple(
                        states_loc[gi][k].reshape(-1)
                        [it.offset:it.offset + it.frag]
                        for k in range(K))
                    nw, nst = frag_fn(wfrag, gfrag, sts, lrs[it.fi],
                                      wds[it.fi], rescale)
                    if want_usq:
                        # per-fragment update sqnorm — psummed below
                        # into the modelwatch report (the fragments of
                        # one param partition it, so the psum IS the
                        # full |w_new - w_old|^2)
                        usq[it.fi] = jnp.sum(jnp.square(
                            (nw - wfrag).astype(jnp.float32)))
                    w_frags.append(nw)
                    for k in range(K):
                        st_frags[k].append(nst[k])
                nshard = jnp.concatenate(w_frags) if len(w_frags) > 1 \
                    else w_frags[0]
                if quant is not None:
                    # re-quantized weight all-gather with its own EF
                    # residual: sub-grid updates accumulate in the
                    # carry until they cross a quantization step
                    qin = nshard.astype(jnp.float32) \
                        + wres_loc[gi].reshape(-1)
                    wkey = None if key is None else \
                        jax.random.fold_in(key, 1000 + gi)
                    gathered, werr = qz.quantized_ag(qin, "dp", dcn,
                                                     quant, key=wkey)
                    gathered = gathered.astype(nshard.dtype)
                    new_wres.append(werr.reshape(1, g.C))
                else:
                    gathered = coll.hierarchical_allgather(
                        nshard, "dp", dcn, 0).reshape(n, g.C)
                for it in g.items:
                    fr = gathered[:, it.offset:it.offset + it.frag]
                    fr = fr.reshape(-1)[:it.size].reshape(it.shape)
                    new_w[it.pos] = fr
                new_states.append(tuple(
                    (jnp.concatenate(st_frags[k]) if len(st_frags[k]) > 1
                     else st_frags[k][0]).reshape(1, -1)
                    for k in range(K)))
            if want_usq:
                return new_w, new_states, new_wres, \
                    coll.allreduce_sum(jnp.stack(usq), all_axes)
            return new_w, new_states, new_wres

        def finite_report(shards, weights_loc=None, grads_loc=None):
            """Replicated report, combined across every replica by ONE
            psum: (2F,) = [nonfinite counts, grad sqnorms] per fragment
            — the finiteness check RUNS ON THE SCATTERED SHARDS. With
            `weights_loc`/`grads_loc` (the modelwatch extension) the
            report grows to (3F+1,): per-param weight-fragment sqnorms
            and the summed LOCAL pre-reduce grad sqnorm (noise-scale
            'small batch' numerator) ride the same psum."""
            r_own = coll.shard_owner_index("dp", dcn)
            bads, sqs, psqs = [], [], []
            small = None
            for g in groups:
                for it in g.items:
                    frag = shards[it.gi][it.offset:it.offset + it.frag]
                    f32 = frag.astype(jnp.float32)
                    bads.append(jnp.sum(
                        (~jnp.isfinite(f32)).astype(jnp.float32)))
                    sqs.append(jnp.sum(jnp.square(f32)))
                    if weights_loc is not None:
                        wflat = coll.pad_to_multiple(
                            weights_loc[it.pos].reshape(-1),
                            it.frag * n)
                        wfrag = lax.dynamic_slice(
                            wflat, (r_own * it.frag,), (it.frag,))
                        psqs.append(jnp.sum(jnp.square(
                            wfrag.astype(jnp.float32))))
                    if grads_loc is not None:
                        lsq = jnp.sum(jnp.square(
                            grads_loc[it.pos].astype(jnp.float32)))
                        small = lsq if small is None else small + lsq
            rows = bads + sqs + psqs
            if small is not None:
                rows.append(small)
            return coll.allreduce_sum(jnp.stack(rows), all_axes)

        ni = len(items)
        arg_names = None
        q = quant is not None
        nq = G if q else 0      # residual args per residual kind
        # stochastic rounding: a per-step seed rides as one replicated
        # trailing arg; quantize sites fold it per group/hop/replica
        sto = 1 if (q and quant.stochastic and quant.mode == "int8") \
            else 0

        def _qkey(flat):
            return jax.random.PRNGKey(flat[-1]) if sto else None

        mw_variant = variant.endswith("_mw")
        base_variant = variant[:-3] if mw_variant else variant
        if base_variant == "step":
            def fn(*flat):
                grads_loc = [a for a in flat[:ni]]
                weights_loc = [a for a in flat[ni:2 * ni]]
                states_loc, base = [], 2 * ni
                for g in groups:
                    states_loc.append([flat[base + k] for k in range(K)])
                    base += K
                gres_loc = list(flat[base:base + nq])
                wres_loc = list(flat[base + nq:base + 2 * nq])
                base += 2 * nq
                lrs, wds, rescale = flat[base], flat[base + 1], \
                    flat[base + 2]
                key = _qkey(flat)
                shards, gres_new = local_reduce(grads_loc, gres_loc,
                                                key=key)
                if mw_variant:
                    # full same-step report: grad/param/update sqnorms
                    # + the local small-batch sum, one psum, deferred
                    # host read (modelwatch.py)
                    rep = finite_report(shards, weights_loc, grads_loc)
                    new_w, new_states, wres_new, usq = local_update(
                        shards, weights_loc, states_loc, lrs, wds,
                        rescale, None, wres_loc=wres_loc, want_usq=True,
                        key=key)
                    return tuple(new_w) + tuple(
                        s for grp in new_states for s in grp) \
                        + tuple(gres_new) + tuple(wres_new) \
                        + (jnp.concatenate([rep, usq]),)
                new_w, new_states, wres_new = local_update(
                    shards, weights_loc, states_loc, lrs, wds, rescale,
                    None, wres_loc=wres_loc, key=key)
                return tuple(new_w) + tuple(
                    s for grp in new_states for s in grp) \
                    + tuple(gres_new) + tuple(wres_new)
            in_specs = (spec_s,) * (2 * ni) \
                + (spec_s,) * (G * K) + (spec_s,) * (2 * nq) \
                + (spec_r,) * (3 + sto)
            out_specs = (spec_r,) * ni + (spec_s,) * (G * K) \
                + (spec_s,) * (2 * nq)
            if mw_variant:
                out_specs = out_specs + (spec_r,)
            arg_names = (["grad:%s" % it.param.name for it in items]
                         + ["w:%s" % it.param.name for it in items]
                         + ["state%d:g%d" % (k, gi)
                            for gi in range(G)
                            for k in range(K)]
                         + ["gres:g%d" % gi for gi in range(nq)]
                         + ["wres:g%d" % gi for gi in range(nq)]
                         + ["lrs", "wds", "rescale"]
                         + (["qseed"] if sto else []))
        elif base_variant == "reduce":
            def fn(*flat):
                grads_loc = [a for a in flat[:ni]]
                base = ni * (2 if mw_variant else 1)
                gres_loc = list(flat[base:base + nq])
                shards, gres_new = local_reduce(grads_loc, gres_loc,
                                                key=_qkey(flat))
                if mw_variant:
                    weights_loc = [a for a in flat[ni:2 * ni]]
                    rep = finite_report(shards, weights_loc, grads_loc)
                else:
                    rep = finite_report(shards)
                return tuple(s[None] for s in shards) \
                    + tuple(gres_new) + (rep,)
            in_specs = (spec_s,) * (ni * (2 if mw_variant else 1) + nq) \
                + (spec_r,) * sto
            out_specs = (spec_s,) * (G + nq) + (spec_r,)
            arg_names = ["grad:%s" % it.param.name for it in items]
            if mw_variant:
                arg_names += ["w:%s" % it.param.name for it in items]
            arg_names += ["gres:g%d" % gi for gi in range(nq)]
            arg_names += ["qseed"] if sto else []
        elif base_variant == "update":
            def fn(*flat):
                shards = [flat[gi].reshape(-1) for gi in range(G)]
                base = G
                weights_loc = [a for a in flat[base:base + ni]]
                base += ni
                states_loc = []
                for g in groups:
                    states_loc.append([flat[base + k] for k in range(K)])
                    base += K
                wres_loc = list(flat[base:base + nq])
                base += nq
                lrs, wds, rescale, coef = flat[base], flat[base + 1], \
                    flat[base + 2], flat[base + 3]
                key = _qkey(flat)
                if mw_variant:
                    new_w, new_states, wres_new, usq = local_update(
                        shards, weights_loc, states_loc, lrs, wds,
                        rescale, coef, wres_loc=wres_loc, want_usq=True,
                        key=key)
                    return tuple(new_w) + tuple(
                        s for grp in new_states for s in grp) \
                        + tuple(wres_new) + (usq,)
                new_w, new_states, wres_new = local_update(
                    shards, weights_loc, states_loc, lrs, wds, rescale,
                    coef, wres_loc=wres_loc, key=key)
                return tuple(new_w) + tuple(
                    s for grp in new_states for s in grp) \
                    + tuple(wres_new)
            in_specs = (spec_s,) * G + (spec_s,) * ni \
                + (spec_s,) * (G * K) + (spec_s,) * nq \
                + (spec_r,) * (4 + sto)
            out_specs = (spec_r,) * ni + (spec_s,) * (G * K) \
                + (spec_s,) * nq
            if mw_variant:
                out_specs = out_specs + (spec_r,)
            arg_names = (["gshard:g%d" % gi for gi in range(G)]
                         + ["w:%s" % it.param.name for it in items]
                         + ["state%d:g%d" % (k, gi)
                            for gi in range(G)
                            for k in range(K)]
                         + ["wres:g%d" % gi for gi in range(nq)]
                         + ["lrs", "wds", "rescale", "coef"]
                         + (["qseed"] if sto else []))
        else:
            raise ValueError(variant)

        from ..parallel.collectives import shard_map
        mapped = shard_map(fn, mesh=mesh, in_specs=in_specs,
                           out_specs=out_specs, check_vma=False)
        return compilewatch.watched_jit(
            mapped, "zero.%s" % variant, site="zero",
            arg_names=arg_names, instance="zero.%s" % variant,
            static_repr="n=%d dcn=%d params=%d" % (
                self._n, self._n_dcn, ni))

    # ------------------------------------------------------------------
    # per-step assembly + execution
    # ------------------------------------------------------------------
    def _sharding(self):
        import jax
        from jax.sharding import NamedSharding
        return NamedSharding(self._mesh(), self._stack_spec())

    def _stack(self, bufs):
        """Zero-copy global (N, *shape) from N per-device jax buffers
        (same assembly the grouped kvstore reducer uses)."""
        import jax
        shape = tuple(bufs[0].shape)
        shards = [b.reshape((1,) + shape) for b in bufs]
        return jax.make_array_from_single_device_arrays(
            (self._n,) + shape, self._sharding(), shards)

    def _stack_nd(self, nds):
        import jax
        bufs = []
        for ctx, a in zip(self._contexts, nds):
            b = a._jax()
            # an eager mutation (guard poison, user g[:] = ...) may have
            # rebound the buffer onto the default device — re-pin to the
            # replica's device (same placement contract as the kvstore
            # store entries)
            if b.device != ctx.jax_device:
                b = jax.device_put(b, ctx.jax_device)
            bufs.append(b)
        return self._stack(bufs)

    def _stack_states(self):
        """State shards are STORED block-shaped (1, C) so assembly and
        write-back are both reshape-free."""
        import jax
        out = []
        for gi in range(len(self._groups)):
            for k in range(self._nstates):
                bufs = [a._jax() for a in self._state_nd[gi][k]]
                out.append(jax.make_array_from_single_device_arrays(
                    (self._n, self._groups[gi].C), self._sharding(), bufs))
        return out

    def _stack_res(self, nds):
        import jax
        bufs = [a._jax() for a in nds]
        return jax.make_array_from_single_device_arrays(
            (self._n, int(bufs[0].shape[1])), self._sharding(), bufs)

    def _qseed_args(self):
        """One per-step uint32 seed arg when stochastic rounding is on
        (both of a guarded step's programs share it — the quantize
        sites fold in distinct salts per group/hop/replica); empty
        otherwise."""
        if self._quant is None or not self._quant.stochastic \
                or self._quant.mode != "int8":
            return []
        import jax.numpy as jnp
        self._qstep += 1
        return [jnp.uint32(self._qstep)]

    def _res_args(self):
        """(gres, wres) stacked residual args — both empty lists when
        quantization is off, so the arg assembly below degrades to the
        classic layout byte-for-byte."""
        if self._quant is None:
            return [], []
        return ([self._stack_res(self._gres_nd[gi])
                 for gi in range(len(self._groups))],
                [self._stack_res(self._wres_nd[gi])
                 for gi in range(len(self._groups))])

    def _write_res(self, outs, store):
        """Write residual program outputs back into their per-replica
        NDArrays (`store` = self._gres_nd or self._wres_nd)."""
        for gi, arr in enumerate(outs):
            by_dev = {s.device: s.data for s in arr.addressable_shards}
            for ctx, snd in zip(self._contexts, store[gi]):
                snd._set_jax(by_dev[ctx.jax_device])

    def _hyper_tensors(self):
        import jax.numpy as jnp
        opt = self._trainer._optimizer
        lrs, wds = [], []
        for it in self._items:
            opt._update_count(it.idx)
            lr, wd = opt.zero_hyperparams(it.idx)
            lrs.append(lr)
            wds.append(wd)
        return (jnp.asarray(np.array(lrs, np.float32)),
                jnp.asarray(np.array(wds, np.float32)),
                jnp.asarray(np.float32(opt.rescale_grad)))

    def _distribute(self, outs):
        """Write program outputs back: replicated new weights into every
        replica's NDArray, sharded (1, C) state blocks into the shard
        NDArrays."""
        ni = len(self._items)
        for it, arr in zip(self._items, outs[:ni]):
            by_dev = {s.device: s.data for s in arr.addressable_shards}
            for ctx, rep in zip(self._contexts, it.param.list_data()):
                rep._set_jax(by_dev[ctx.jax_device])
        base = ni
        for gi in range(len(self._groups)):
            for k in range(self._nstates):
                arr = outs[base]
                base += 1
                by_dev = {s.device: s.data
                          for s in arr.addressable_shards}
                for ctx, snd in zip(self._contexts,
                                    self._state_nd[gi][k]):
                    snd._set_jax(by_dev[ctx.jax_device])

    def _check_rebuild(self) -> bool:
        """Cheap per-step staleness check; returns False on a state the
        engine cannot carry forward (caller bails to classic)."""
        frag = self._trainer._optimizer.zero_fragment_update()
        if frag is None:
            return False
        if self._signature() != self._structure \
                or frag[0] != self._nstates:
            # parameter set/shape or state-tensor count changed
            # mid-training: rebuilding would RESET momentum — hand the
            # accumulated shards back to the classic path instead
            return False
        if frag[1] != self._hyper_key:
            # same structure, new static hypers (momentum/beta edits):
            # states carry over, programs rebuild
            self._hyper_key, self._frag_fn = frag[1], frag[2]
            self._programs.clear()
        from ..parallel import quantize as qz
        newq = qz.from_env()
        if (newq.key() if newq else None) != \
                (self._quant.key() if self._quant else None):
            # MXNET_KVSTORE_QUANTIZE flipped mid-run: the quantize is
            # baked into the compiled programs, so rebuild them (and
            # the residual buffers — the carried correction is at most
            # one sub-grid step, safe to drop). Optimizer state shards
            # carry over untouched.
            self._quant = newq
            self._alloc_residuals()
            self._programs.clear()
        return True

    @staticmethod
    def _norm32(sq: float) -> float:
        """float32-rounded norm from a float64 squared sum — float64
        sqrt carries enough bits that this equals the device's direct
        float32 sqrt, so the zero path's per-layer gauges compare
        bitwise with the replicated path's (modelwatch parity)."""
        return float(np.float32(np.sqrt(sq)))

    def _consume_mw_pending(self, mw):
        """Read + publish the modelwatch report deferred from the
        previous sampled step (one device_get; that program completed
        during the intervening fwd/bwd, so the read is pipelined, not
        serializing). Stale 'usq' fragments from a mid-run guard
        toggle are dropped — the next sampled step re-primes."""
        import jax
        pend, self._mw_pending = self._mw_pending, None
        if pend is None or mw is None:
            return
        kind, names, handle, rescale = pend
        if kind != "full":
            return
        vec = np.asarray(jax.device_get(handle), dtype=np.float64)
        mw.sync_count += 1
        F = len(names)
        # [bad(F), gsq(F), psq(F), small(1), usq(F)]
        flags = [bool(vec[i] == 0) for i in range(F)]
        gnorms = [self._norm32(v) for v in vec[F:2 * F]]
        pnorms = [self._norm32(v) for v in vec[2 * F:3 * F]]
        small = float(vec[3 * F])
        unorms = [self._norm32(v) for v in vec[3 * F + 1:4 * F + 1]]
        mw.publish(names, gnorms, pnorms, unorms, names,
                   small if mw.want_noise() else None,
                   rescale=rescale, flags=flags, same_step_update=True)

    def run_step(self, ignore_stale_grad: bool = False) -> str:
        import jax
        from .. import commwatch, faultinject, guardrails
        from ..ndarray.sparse import RowSparseNDArray
        trainer = self._trainer
        if not self._check_rebuild():
            return BAIL
        for it in self._items:
            for g in it.param.list_grad():
                if isinstance(g, RowSparseNDArray):
                    return BAIL
        guard = trainer.grad_guard
        guarded = guard is not None and guard.enabled
        mw = trainer.modelwatch
        mw_on = mw is not None and mw.sampling
        watching = commwatch.enabled()
        if (guarded or mw_on) and faultinject.active():
            # same deterministic poison sites the replicated guard uses
            # (nan_grad on the first param, scaled_grad on the last)
            guardrails.inject_grad_faults(
                [(it.param.name, it.param.list_grad()[0])
                 for it in self._items])
        if mw_on and self._mw_pending is not None \
                and (not guarded or self._mw_pending[0] == "full"):
            self._consume_mw_pending(mw)

        grad_args = [self._stack_nd(it.param.list_grad())
                     for it in self._items]
        w_args = [self._stack_nd(it.param.list_data())
                  for it in self._items]
        state_args = self._stack_states()
        gres_args, wres_args = self._res_args()
        seed_args = self._qseed_args()
        G = len(self._groups)
        nq = G if self._quant is not None else 0

        if not guarded:
            lrs, wds, rescale = self._hyper_tensors()
            variant = "step_mw" if mw_on else "step"
            with telemetry.phase("zero_step"):
                with commwatch.program_watch("zero.step", "zero.step"):
                    outs = self._program(variant)(
                        *(grad_args + w_args + state_args
                          + gres_args + wres_args
                          + [lrs, wds, rescale] + seed_args))
                    if watching:
                        jax.block_until_ready(outs)
            if mw_on:
                # same-step in-program report (grad/param/update/small
                # all from this step), read at the NEXT sampled step —
                # one pipelined host sync per step, zero added stalls
                self._mw_pending = (
                    "full", list(self._names), outs[-1],
                    float(trainer._optimizer.rescale_grad))
                outs = outs[:-1]
            if nq:
                core = len(self._items) + G * self._nstates
                self._write_res(outs[core:core + nq], self._gres_nd)
                self._write_res(outs[core + nq:core + 2 * nq],
                                self._wres_nd)
                outs = outs[:core]
            self._distribute(outs)
            return DONE

        # guarded: RS + scattered finiteness/stats report, policy on
        # host, then the masked shard update
        variant = "reduce_mw" if mw_on else "reduce"
        with telemetry.phase("allreduce"):
            with commwatch.program_watch("zero.reduce", "zero.reduce"):
                red = self._program(variant)(
                    *(grad_args + (w_args if mw_on else [])
                      + gres_args + seed_args))
                if watching:
                    jax.block_until_ready(red)
        gshards, rep = list(red[:G]), red[-1]
        if nq:
            # the wire already carried the quantized gradients: the EF
            # residual advances even when the guard skips this step
            self._write_res(list(red[G:G + nq]), self._gres_nd)
        F = len(self._items)
        pend = None
        if mw_on and self._mw_pending is not None:
            pend, self._mw_pending = self._mw_pending, None
        got = jax.device_get([rep] + ([pend[2]] if pend else []))
        rep = np.asarray(got[0], dtype=np.float64)
        guard.sync_count += 1
        flags = [bool(rep[i] == 0) for i in range(F)]
        norm = float(np.sqrt(np.sum(rep[F:2 * F])))
        if mw_on:
            gnorms = [self._norm32(v) for v in rep[F:2 * F]]
            pnorms = [self._norm32(v) for v in rep[2 * F:3 * F]]
            unames = unorms = None
            if pend is not None:
                usq = np.asarray(got[1], dtype=np.float64)
                unames = pend[1]
                unorms = [self._norm32(v) for v in usq]
            mw.sync_count += 1
            mw.publish(self._names, gnorms, pnorms, unorms, unames,
                       float(rep[3 * F]) if mw.want_noise() else None,
                       rescale=trainer._optimizer.rescale_grad,
                       flags=flags)
        with telemetry.phase("guard"):
            proceed, bad, clip_scale = guard.evaluate(
                self._names, flags, norm,
                rescale=trainer._optimizer.rescale_grad)
        if not proceed:
            # counters have NOT advanced: a skipped step must leave
            # num_update / Adam bias-correction t exactly where the
            # replicated path (which returns before _update) leaves
            # them
            return SKIPPED
        # only a proceeding step advances the update counters — the
        # hyperparams (Adam's folded t) must be computed AFTER the
        # guard verdict for parity with the replicated path
        lrs, wds, rescale = self._hyper_tensors()
        coef = np.ones(F, np.float32)
        if bad:
            bad_set = set(bad)
            for it in self._items:
                if it.param.name in bad_set:
                    coef[it.fi] = 0.0
        if clip_scale is not None:
            coef *= np.float32(clip_scale)
        import jax.numpy as jnp
        variant = "update_mw" if mw_on else "update"
        with telemetry.phase("zero_step"):
            with commwatch.program_watch("zero.update", "zero.update"):
                outs = self._program(variant)(
                    *(gshards + w_args + state_args + wres_args
                      + [lrs, wds, rescale, jnp.asarray(coef)]
                      + seed_args))
                if watching:
                    jax.block_until_ready(outs)
        if mw_on:
            # update-norm fragment psum: read at the next sampled step
            self._mw_pending = ("usq", list(self._names), outs[-1],
                                float(trainer._optimizer.rescale_grad))
            outs = outs[:-1]
        if nq:
            core = len(self._items) + G * self._nstates
            self._write_res(outs[core:core + nq], self._wres_nd)
            outs = outs[:core]
        self._distribute(outs)
        return DONE

    # ------------------------------------------------------------------
    # topology-portable checkpoints (ROADMAP item 5 feeder)
    # ------------------------------------------------------------------
    def _gathered_state_arrays(self):
        """{param index: [full numpy state, ...K]} reassembled from the
        shards (host-side; honors the dcn ownership permutation)."""
        out: Dict[int, List[np.ndarray]] = {}
        for gi, g in enumerate(self._groups):
            if not self._nstates:
                for it in g.items:
                    out[it.idx] = []
                continue
            per_kind = []
            for k in range(self._nstates):
                shards = [np.asarray(self._state_nd[gi][k][p].asnumpy())
                          .reshape(-1) for p in range(self._n)]
                by_frag = [None] * self._n
                for p in range(self._n):
                    by_frag[self._owner[p]] = shards[p]
                per_kind.append(by_frag)
            for it in g.items:
                ks = []
                for k in range(self._nstates):
                    full = np.concatenate(
                        [per_kind[k][r][it.offset:it.offset + it.frag]
                         for r in range(self._n)])
                    ks.append(full[:it.size].reshape(it.shape))
                out[it.idx] = ks
        return out

    def gather_states(self) -> dict:
        """Canonical replicated-layout optimizer states ({index: state}
        with the exact per-optimizer state shapes `create_state`
        builds), on the first replica's context — what a plain
        replicated Trainer pickles, so the checkpoint is
        topology-portable."""
        from .. import ndarray as nd
        ctx0 = self._contexts[0]
        gathered = self._gathered_state_arrays()
        states: Dict[int, object] = {}
        for it in self._items:
            arrs = [nd.array(a, ctx=ctx0, dtype=a.dtype)
                    for a in gathered[it.idx]]
            if self._nstates == 0:
                states[it.idx] = None
            elif self._nstates == 1:
                states[it.idx] = arrs[0]
            else:
                states[it.idx] = tuple(arrs)
        return states

    def serialized_states(self) -> bytes:
        """Pickle in the exact `optimizer.Updater.get_states` format —
        byte-compatible with a replicated Trainer's save. With wire
        quantization active the error-feedback residuals are REAL
        carried state (dropping them silently loses the accumulated
        sub-grid gradient/weight mass), so the blob becomes a tagged
        wrapper dict also holding the param-space residuals; the load
        side of every path (quantized or not, sharded or replicated,
        any topology) understands both forms."""
        if self._quant is None:
            return pickle.dumps(self.gather_states())
        gres, wres = self._gathered_residuals()
        return pickle.dumps({"__mx_zero_quant__": 1,
                             "states": self.gather_states(),
                             "grad_residual": gres,
                             "weight_residual": wres})

    # ------------------------------------------------------------------
    # error-feedback residual checkpointing (docs/QUANTIZE.md): gathered
    # to PARAM SPACE (full per-param arrays) so the checkpoint is
    # topology-portable exactly like the optimizer state above.
    # ------------------------------------------------------------------
    def _gathered_residuals(self):
        """({idx: grad residual}, {idx: weight residual}) as full
        param-shaped numpy arrays. The grad residual is the SUM over
        replicas (row j of each replica's (n, C) buffer is its carried
        correction for global fragment j — the carry identity conserves
        the sum); the weight residual is shard-assembled with the
        ownership permutation, like optimizer state."""
        gres: Dict[int, np.ndarray] = {}
        wres: Dict[int, np.ndarray] = {}
        if self._quant is None:
            return gres, wres
        for gi, g in enumerate(self._groups):
            tot = None
            for p in range(self._n):
                a = np.asarray(self._gres_nd[gi][p].asnumpy(),
                               np.float32).reshape(self._n, g.C)
                tot = a if tot is None else tot + a
            by_frag = [None] * self._n
            for p in range(self._n):
                by_frag[self._owner[p]] = np.asarray(
                    self._wres_nd[gi][p].asnumpy(),
                    np.float32).reshape(-1)
            for it in g.items:
                full = np.concatenate(
                    [tot[j, it.offset:it.offset + it.frag]
                     for j in range(self._n)])
                gres[it.idx] = full[:it.size].reshape(it.shape)
                wfull = np.concatenate(
                    [by_frag[r][it.offset:it.offset + it.frag]
                     for r in range(self._n)])
                wres[it.idx] = wfull[:it.size].reshape(it.shape)
        return gres, wres

    def _scatter_residuals(self, gres, wres):
        """Load param-space residuals (from ANY topology) into this
        engine's layout: the grad residual lands WHOLE on replica 0
        (zeros elsewhere) — the carry identity only conserves the
        replica SUM, and `x + 0 + ... + 0` is the one split that
        re-gathers bitwise exactly for every replica count; the weight
        residual re-slices onto shard owners through the same explicit
        reshard placement as scatter_states (FragLayout.data_extent
        clamps; tiny params exact, padding zeroed — docs/ELASTIC.md)."""
        import jax
        from ..parallel import reshard as rs
        if self._quant is None:
            return
        devs = [ctx.jax_device for ctx in self._contexts]
        for gi, g in enumerate(self._groups):
            gbuf = np.zeros((self._n, g.C), np.float32)
            wentries = []
            for it in g.items:
                lay = self._frag_layout(it)
                arr = gres.get(it.idx) if gres else None
                if arr is not None:
                    flat = np.asarray(arr, np.float32).reshape(-1)
                    for r in range(self._n):
                        lo, hi = lay.data_extent(r)
                        if hi > lo:
                            gbuf[r, it.offset:it.offset + (hi - lo)] = \
                                flat[lo:hi]
                warr = wres.get(it.idx) if wres else None
                if warr is not None:
                    wentries.append(
                        (np.asarray(warr, np.float32).reshape(-1), lay))
            gflat = gbuf.reshape(1, self._n * g.C)
            gzero = np.zeros_like(gflat)
            wbufs = rs.place_from_host(wentries, self._n, g.C, devs,
                                       np.float32, label="zero.residual")
            for p, ctx in enumerate(self._contexts):
                self._gres_nd[gi][p]._set_jax(jax.device_put(
                    gflat if p == 0 else gzero, ctx.jax_device))
                self._wres_nd[gi][p]._set_jax(wbufs[p].reshape(1, g.C))

    def _frag_layout(self, it):
        """This engine's FragLayout for one item — the single source of
        truth the reshard pass shares (parallel/reshard.py): fragment
        ceil-split, dcn ownership permutation, shard-local offset."""
        from ..parallel import reshard as rs
        return rs.FragLayout(it.size, self._n, tuple(self._owner),
                             it.offset)

    def scatter_states(self, states: dict):
        """Load a canonical replicated-layout state dict (a checkpoint
        from ANY topology — sharded elsewhere or never sharded) into
        this engine's shard layout. Parameters absent from the dict —
        the whole dict is empty for a step-0 checkpoint — get FRESH
        (zero) state, exactly the replicated path's lazy creation on
        first update.

        Placement routes through parallel/reshard.place_from_host
        (ISSUE 16): the shard-local math is the EXPLICIT
        FragLayout.data_extent clamp — a param smaller than one
        fragment per replica lands exactly, whole-padding fragments
        write nothing and destination padding is zeroed by construction
        instead of by pad_to_multiple alignment — and the assembled
        stack passes through the watched + shardcheck-validated
        transition program before first use (docs/ELASTIC.md)."""
        from ..parallel import reshard as rs
        for gi, g in enumerate(self._groups):
            if not self._nstates:
                continue
            dt = np.dtype(g.dtype)
            per_kind = [[] for _k in range(self._nstates)]
            for it in g.items:
                st = states.get(it.idx)
                if it.idx not in states:
                    continue           # fresh state: implicit zeros
                ks = st if isinstance(st, (tuple, list)) else (st,)
                if len(ks) != self._nstates or any(k is None for k in ks):
                    raise MXNetError(
                        "state for parameter %s has %d tensor(s); this "
                        "optimizer shards %d — was the checkpoint saved "
                        "with a different optimizer?"
                        % (it.param.name,
                           0 if st is None else len(ks), self._nstates))
                lay = self._frag_layout(it)
                for k in range(self._nstates):
                    arr = np.asarray(
                        ks[k].asnumpy()
                        if hasattr(ks[k], "asnumpy") else ks[k],
                        dtype=dt).reshape(-1)
                    per_kind[k].append((arr, lay))
            devs = [ctx.jax_device for ctx in self._contexts]
            for k in range(self._nstates):
                bufs = rs.place_from_host(per_kind[k], self._n, g.C,
                                          devs, dt, label="zero.states")
                for p in range(self._n):
                    self._state_nd[gi][k][p]._set_jax(
                        bufs[p].reshape(1, g.C))

    def load_serialized_states(self, blob: bytes):
        states = pickle.loads(blob)
        gres = wres = None
        if isinstance(states, dict) and states.get("__mx_quant__"):
            # a quantized KVSTORE-path checkpoint (gluon/trainer.py):
            # its per-key grad residual has the same param-space carry
            # semantics as our gres — adopt it; store keys are the
            # Trainer's parameter indices
            raw = states.get("kv_residual") or {}
            gres = {}
            for k, v in raw.items():
                try:
                    gres[int(k)] = v
                except (TypeError, ValueError):
                    pass
            states = pickle.loads(states["updater"])
        elif isinstance(states, dict) and states.get("__mx_zero_quant__"):
            gres = states.get("grad_residual")
            wres = states.get("weight_residual")
            states = states["states"]
        if isinstance(states, tuple) and len(states) == 2:
            states = states[0]      # dump_optimizer=True form
        self.scatter_states(states)
        if self._quant is not None:
            # a non-quantized checkpoint restores with fresh (zero)
            # residuals — same lazy semantics as absent optimizer state
            self._scatter_residuals(gres or {}, wres or {})

    # ------------------------------------------------------------------
    def reshard_from(self, old, blk_bytes=None):
        """Live shrink/grow state transition (docs/ELASTIC.md): move
        the OLD engine's sharded optimizer state into this engine's
        layout device-to-device through the staged parallel/reshard
        plan — per (group, kind) one fragment move plan covering every
        param, executed in memory-bounded blocks, so the full state is
        never materialized on any device (arxiv 2112.01075). The dcn
        ownership permutations of both sides are honored by the plan
        (arxiv 2004.13336). Error-feedback residuals are param-space
        carried state and move through the same gathered/scattered
        host path the checkpoint uses (bounded by one group's C).

        Raises MXNetError when the layouts are not plan-compatible
        (different params / optimizer); callers degrade to
        checkpoint-restore."""
        from ..parallel import reshard as rs
        if old._nstates != self._nstates or \
                len(old._items) != len(self._items):
            raise MXNetError(
                "reshard_from: engine layouts disagree (states %d vs "
                "%d, params %d vs %d) — was the optimizer swapped "
                "mid-run?" % (old._nstates, self._nstates,
                              len(old._items), len(self._items)))
        old_by_idx = {it.idx: it for it in old._items}
        devs = [ctx.jax_device for ctx in self._contexts]
        if self._nstates:
            for gi, g in enumerate(self._groups):
                moves = []
                for it in g.items:
                    oit = old_by_idx.get(it.idx)
                    if oit is None or oit.size != it.size \
                            or oit.gi != gi:
                        raise MXNetError(
                            "reshard_from: parameter %s has no "
                            "matching fragment layout in the old "
                            "engine" % it.param.name)
                    moves += rs.plan_moves(old._frag_layout(oit),
                                           self._frag_layout(it))
                for k in range(self._nstates):
                    src = [old._state_nd[gi][k][p]._jax().reshape(-1)
                           for p in range(old._n)]
                    bufs = rs.reshard_fragments(
                        src, moves, self._n, g.C, devs,
                        blk_bytes=blk_bytes, label="zero.state")
                    for p in range(self._n):
                        self._state_nd[gi][k][p]._set_jax(
                            bufs[p].reshape(1, g.C))
        if self._quant is not None:
            if old._quant is not None:
                gres, wres = old._gathered_residuals()
            else:
                gres, wres = {}, {}
            self._scatter_residuals(gres, wres)

    # ------------------------------------------------------------------
    def dissolve_into(self, updaters, contexts):
        """Hand the accumulated sharded state back to the replicated
        per-context updaters (the structural-bail path): momentum /
        Adam moments survive the fallback instead of silently resetting
        to zero."""
        from .. import ndarray as nd
        if not self._nstates:
            return
        gathered = self._gathered_state_arrays()
        for upd, ctx in zip(updaters, contexts):
            for it in self._items:
                arrs = [nd.array(a, ctx=ctx, dtype=a.dtype)
                        for a in gathered[it.idx]]
                upd.states[it.idx] = arrs[0] if self._nstates == 1 \
                    else tuple(arrs)
