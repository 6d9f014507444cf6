"""Gluon Trainer (ref: python/mxnet/gluon/trainer.py :: Trainer).

The north star requires ``Trainer.step()`` to run unchanged
(BASELINE.json:5): _init_kvstore picks the store, _allreduce_grads
pushes/pulls per-parameter gradients (engine-async so comm overlaps the
tail of backward, as in the reference), _update runs the fused optimizer
kernel per device replica.
"""
from __future__ import annotations

from collections import namedtuple
from typing import Dict, List, Optional

from ..base import MXNetError
from .. import optimizer as opt_mod
from .. import kvstore as kvs_mod
from .. import telemetry
from .parameter import Parameter, ParameterDict

__all__ = ["Trainer"]

# What the fused consume derives from the optimizer before its launch
# (Trainer._prep_fused_plan).
FusedPrep = namedtuple("FusedPrep", [
    "items",        # [(i, param, data_arr, state, grad_pos, ws_slot)]
    "rows",         # ((grad_pos, ws_slot, has_mom), ...)
    "gdt",          # grad dtypes per row
    "mom_rows", "plain_rows",
    "upd_key",      # ("sgd", momentum, clip, rescale, rows, gdt)
    "lrs", "wds",   # np.float32 per row
    "momentum", "clip", "rescale",
])


class Trainer:
    def __init__(self, params, optimizer, optimizer_params=None, kvstore="device",
                 compression_params=None, update_on_kvstore=None):
        if isinstance(params, (dict, ParameterDict)):
            params = [params[key] for key in sorted(list(params.keys()))]
        if not isinstance(params, (list, tuple)):
            raise ValueError("params must be a list/tuple/ParameterDict")
        self._params: List[Parameter] = []
        self._param2idx = {}
        for i, param in enumerate(params):
            if not isinstance(param, Parameter):
                raise ValueError("invalid parameter %r" % param)
            self._param2idx[param.name] = i
            self._params.append(param)
        self._compression_params = compression_params
        self._contexts = self._check_contexts()
        optimizer_params = optimizer_params or {}
        self._init_optimizer(optimizer, optimizer_params)
        self._scale = self._optimizer.rescale_grad
        self._kvstore_type = kvstore
        self._update_on_kvstore = update_on_kvstore
        self._kvstore = None
        self._kv_initialized = False
        self._params_to_init = []
        self._grad_guard = None        # guardrails.GradGuard (lazy)
        self._guard_resolved = False
        self._modelwatch = None        # modelwatch.ModelWatch (lazy)
        self._mw_resolved = False
        self._mw_fused_caps = None     # fused-path pre-update captures
        self._fused_armed = False      # MXNET_TRAINER_FUSED_UPDATE state
        self._fused_structural_bail = False
        self._zero = None              # MXNET_ZERO engine: None=unresolved,
        self._zero_bailed = False      # False=disabled, else zero.ZeroEngine

    # ------------------------------------------------------------------
    def _check_contexts(self):
        contexts = None
        for param in self._params:
            ctx = param.list_ctx() if param._data is not None else \
                (param._ctx_list or [])
            if contexts is not None and contexts != ctx and ctx:
                raise ValueError(
                    "All Parameters must be initialized on the same set of "
                    "contexts, but Parameter %s is on %s while previous "
                    "params are on %s" % (param.name, str(ctx), str(contexts)))
            if ctx:
                contexts = ctx
        return contexts or []

    def _init_optimizer(self, optimizer, optimizer_params):
        param_dict = {i: param for i, param in enumerate(self._params)}
        if isinstance(optimizer, opt_mod.Optimizer):
            assert not optimizer_params, \
                "optimizer_params must be empty for a pre-built Optimizer"
            self._optimizer = optimizer
            self._optimizer.param_dict = param_dict
        else:
            self._optimizer = opt_mod.create(optimizer, param_dict=param_dict,
                                             **optimizer_params)
        self._updaters = [opt_mod.get_updater(self._optimizer)
                          for _ in self._contexts]

    def _init_kvstore(self):
        if self._kvstore_type is None or len(self._contexts) <= 1 and \
                self._kvstore_type in (None, "local", "device", "tpu"):
            # single device: no store needed; update directly
            self._kvstore = None
            self._update_on_kvstore = False
        else:
            kv = self._kvstore_type if not isinstance(self._kvstore_type, str) \
                else kvs_mod.create(self._kvstore_type)
            self._kvstore = kv
            if self._compression_params and \
                    hasattr(kv, "set_gradient_compression"):
                kv.set_gradient_compression(self._compression_params)
            if self._update_on_kvstore is None:
                self._update_on_kvstore = False
            for i, param in enumerate(self._params):
                if param._data is not None:
                    self._kvstore.init(i, param.data(self._contexts[0]))
        self._kv_initialized = True

    # ------------------------------------------------------------------
    @property
    def learning_rate(self):
        return self._optimizer._get_lr(0) if self._optimizer.lr_scheduler \
            else self._optimizer.lr

    @property
    def optimizer(self):
        return self._optimizer

    def set_learning_rate(self, lr):
        self._optimizer.set_learning_rate(lr)

    # ------------------------------------------------------------------
    @property
    def grad_guard(self):
        """The training guardrail applied each step (guardrails.GradGuard),
        configured from MXNET_GUARD_* env on first use; None when every
        guard feature is off. Assign to install a custom guard. An AMP
        loss scaler attached via amp.init_trainer is wired into the
        guard so overflow drives its backoff (one shared code path)."""
        if self._grad_guard is None and not self._guard_resolved:
            from .. import guardrails
            self._grad_guard = guardrails.from_env(
                scaler=getattr(self, "_amp_loss_scaler", None))
            self._guard_resolved = True
        return self._grad_guard

    @grad_guard.setter
    def grad_guard(self, guard):
        self._grad_guard = guard
        self._guard_resolved = True

    def _guard_grads(self):
        """(named ctx-0 grads, every grad replica) for the guard pass —
        post-allreduce the replicas are identical, so one representative
        per parameter is checked and actions (zero/clip) reach all."""
        named, action = [], []
        for param in self._params:
            if param.grad_req == "null" or param._data is None:
                continue
            grads = param.list_grad()
            named.append((param.name, grads[0]))
            action.extend(grads)
        return named, action

    # ------------------------------------------------------------------
    @property
    def modelwatch(self):
        """The training-dynamics collector applied each step
        (modelwatch.ModelWatch), configured from MXNET_MODELWATCH_* env
        on first use; None when the layer is off. Assign to install a
        custom collector. Its per-layer stats ride the guard's single
        per-step host sync (docs/OBSERVABILITY.md 'Training
        dynamics')."""
        if self._modelwatch is None and not self._mw_resolved:
            from .. import modelwatch as mw_mod
            self._modelwatch = mw_mod.from_env()
            self._mw_resolved = True
        return self._modelwatch

    @modelwatch.setter
    def modelwatch(self, watch):
        self._modelwatch = watch
        self._mw_resolved = True

    def _trainable_named(self):
        """[(name, ctx-0 data replica)] in _guard_grads order — the
        weight inputs of modelwatch's extended reduction and the
        update-norm capture (replicas are identical post-update, so
        one representative is measured)."""
        return [(p.name, p.list_data()[0]) for p in self._params
                if p.grad_req != "null" and p._data is not None]

    def _per_replica_grads(self):
        """One gradient list per replica, each on its own device — the
        pre-allreduce view modelwatch's noise-scale meter reduces (the
        'small batch' estimate the dp replicas provide for free)."""
        out = [[] for _ in self._contexts]
        for param in self._params:
            if param.grad_req == "null" or param._data is None:
                continue
            for r, g in enumerate(param.list_grad()):
                out[r].append(g)
        return out

    def step(self, batch_size, ignore_stale_grad=False):
        """allreduce + update (ref: trainer.py :: step → _allreduce_grads
        → _update). rescale_grad folds 1/batch_size into the fused
        optimizer kernel — no separate scaling pass over HBM. A
        configured GradGuard checks the reduced gradients in ONE fused
        device reduction (single extra sync) and may skip/zero/raise per
        MXNET_GUARD_NONFINITE before the optimizer runs.

        Fused-update mode (MXNET_TRAINER_FUSED_UPDATE, default on): once
        a step has run classically and the loop is eligible (local
        single-device kvstore, update_on_kvstore=False, SGD with a
        multi-tensor kernel, grad_req='write' everywhere, no GradGuard),
        the Trainer arms autograd so the NEXT backward() defers, and
        this step executes fwd+bwd+optimizer as ONE compiled program —
        removing the separate optimizer dispatch that re-reads w/g/m
        from HBM (0.49 ms on ResNet-50, round-5 builder figure). Any
        mismatch falls back to the reference-idiomatic separate program.

        ZeRO mode (MXNET_ZERO, multi-replica loops; gluon/zero.py,
        docs/ZERO.md): gradients are reduce-scattered instead of
        allreduced, each replica updates only its 1/N shard of the
        flattened parameter space against SHARDED optimizer state, and
        the updated parameters are all-gathered back — one watched SPMD
        program per step (two with a GradGuard: the finiteness check
        runs on the scattered shards, still one extra sync). Same
        wire traffic as allreduce, ~N x less optimizer-state HBM.

        The whole of it is one span, ``step::update``
        (docs/OBSERVABILITY.md "Step spans"): parent of
        ``step::update.prep`` / ``.launch`` / ``.writeback`` on the
        fused path, where the phase histogram files it under
        ``fused_step``, and of ``step::allreduce`` / ``step::guard`` /
        ``step::optimizer`` on the classic one. The step is marked
        after the span has closed, so the span lands in its own step's
        record of ``telemetry.step_log``."""
        with telemetry.phase("update") as span:
            useful = self._step(batch_size, ignore_stale_grad, span)
        telemetry.mark_step(useful=useful)

    def _step(self, batch_size, ignore_stale_grad, span):
        """``step`` inside its span. Returns whether the step was
        useful (False: a guard dropped the update)."""
        if not self._kv_initialized:
            self._contexts = self._check_contexts()
            self._init_kvstore()
        self._optimizer.rescale_grad = self._scale / batch_size
        mw = self.modelwatch
        if mw is not None:
            mw.begin_step(batch_size, len(self._contexts))
        if self._fused_armed:
            from .. import autograd as _ag
            plan = _ag.take_pending_step(self)
            if plan is not None:
                # re-validate NOW, not just at arm time: a GradGuard (or
                # flag/optimizer change) installed between steps must
                # not be bypassed for the already-stashed update
                if self._fused_update_eligible():
                    done = self._consume_fused_plan(plan)
                    if not done:
                        # a consume-level bail is STRUCTURAL (param
                        # missing from the tape, mp tuple state): it
                        # would recur every step, deferring each
                        # backward for nothing — stop re-arming.
                        self._fused_structural_bail = True
                else:
                    # eligibility change (guard installed, flag
                    # flipped) — not structural; re-arming may
                    # succeed later
                    done = False
                    plan.execute()     # plain fused backward
                if done:
                    fused_mw = self._mw_fused_caps
                    self._mw_fused_caps = None
                    if mw is not None and mw.sampling and fused_mw:
                        # stats on the step program's own outputs: the
                        # written grads + the pre-update weight aliases
                        # captured around the fused write-back — the
                        # read here is the step's ONE host sync (the
                        # fused path pays none otherwise). The update
                        # norms are SAME-step here (measured after the
                        # program, read in the same report), so they
                        # pair with this report's own param norms
                        caps, unorm = fused_mw
                        with telemetry.phase("modelwatch"):
                            named, _ = self._guard_grads()
                            mw.step_report(
                                named,
                                [(n, alias) for n, alias, _arr in caps],
                                rescale=self._optimizer.rescale_grad,
                                update_now=unorm)
                    self._rearm_fused_update()   # stay armed
                    # own phase label: this program contains
                    # fwd+bwd+update, so charging it to 'optimizer'
                    # would gut the per-step phase breakdown
                    # (docs/OBSERVABILITY.md); the span keeps its name
                    span.labels["phase"] = "fused_step"
                    return True
                # plan executed plainly (grads written) — fall through
                # to the classic guard/update path
                self._fused_armed = False
                _ag.disarm_fused_update(self)
            else:
                # backward never stashed (ineligible tape / classic walk)
                self._fused_armed = False
                _ag.disarm_fused_update(self)
        engine = self._zero_engine()
        if engine is not None:
            from . import zero as zero_mod
            status = engine.run_step(ignore_stale_grad)
            if status == zero_mod.DONE:
                return True
            if status == zero_mod.SKIPPED:
                # not useful: a guard-skipped step's interval is
                # debited from the mx_goodput meter (same contract as
                # the replicated guard path below)
                return False
            # BAIL is structural (sparse grads, parameter set changed):
            # it would recur every step — dissolve the accumulated
            # state shards into the per-context updaters and fall back
            # to the replicated path permanently
            engine.dissolve_into(self._updaters, self._contexts)
            self._zero = False
            self._zero_bailed = True
            import logging
            logging.getLogger("mxnet_tpu.zero").warning(
                "MXNET_ZERO: structural change mid-training — sharded "
                "optimizer state handed back to the replicated path")
        if mw is not None and mw.want_noise():
            # pre-allreduce per-replica grad norms — the noise-scale
            # meter's 'small batch' estimate, captured before the sync
            # overwrites the local values (async device work only)
            mw.collect_replica_norms(self._per_replica_grads())
        with telemetry.phase("allreduce"):
            from .. import commwatch
            with commwatch.exposed_region():
                # the grad sync blocks the step thread here: its comm
                # wall time is EXPOSED (ISSUE 6 attribution), unlike
                # collectives XLA overlaps inside compiled programs
                self._allreduce_grads()
        guard = self.grad_guard
        guard_on = guard is not None and guard.enabled
        mw_on = mw is not None and mw.sampling
        if guard_on or mw_on:
            with telemetry.phase("guard" if guard_on else "modelwatch"):
                named, action = self._guard_grads()
                # rescale_grad carries 1/batch_size (and 1/loss_scale
                # under AMP): the guard clips on the EFFECTIVE norm
                proceed = True
                if mw_on:
                    # ONE extended reduction + ONE read serves both the
                    # per-layer stats and the guard verdict — the same
                    # single host sync a guard-only step costs
                    report = mw.step_report(
                        named, self._trainable_named(),
                        rescale=self._optimizer.rescale_grad)
                    if guard_on:
                        proceed = guard.check(
                            named, action,
                            rescale=self._optimizer.rescale_grad,
                            report=report)
                else:
                    proceed = guard.check(
                        named, action,
                        rescale=self._optimizer.rescale_grad)
            if not proceed:
                # not useful: a guard-skipped step's interval is
                # debited from the mx_goodput meter
                return False    # skipped step (counted by the guard)
        with telemetry.phase("optimizer"):
            caps = mw.note_pre_update(self._trainable_named()) \
                if mw_on else None
            self._update(ignore_stale_grad)
            if caps:
                mw.note_post_update(caps)
        self._rearm_fused_update()
        return True

    # ------------------------------------------------------------------
    # ZeRO weight-update sharding (MXNET_ZERO; gluon/zero.py,
    # docs/ZERO.md)
    # ------------------------------------------------------------------
    def _zero_engine(self):
        """The ZeRO engine for this Trainer, or None. Resolved lazily
        at the first step after the kvstore is up: MXNET_ZERO off is a
        cheap re-checkable no; on-but-ineligible logs the failing rung
        of the eligibility ladder ONCE and permanently falls back; a
        later structural bail (run_step returning BAIL) also disables
        permanently after dissolving the state shards back into the
        replicated updaters."""
        if self._zero_bailed:
            return None
        if self._zero is None or self._zero is False:
            from .. import config as _cfg_mod
            if not _cfg_mod.get("MXNET_ZERO"):
                self._zero = False
                return None
            from ..base import MXNetError
            from . import zero as zero_mod
            ok, reason = zero_mod.eligibility(self)
            if not ok:
                import logging
                logging.getLogger("mxnet_tpu.zero").warning(
                    "MXNET_ZERO=1 but the Trainer is not eligible for "
                    "weight-update sharding: %s — using the replicated "
                    "update path (docs/ZERO.md)", reason)
                self._zero = False
                self._zero_bailed = True
                return None
            try:
                self._zero = zero_mod.ZeroEngine(self)
            except MXNetError:
                self._zero = False
                self._zero_bailed = True
                raise
        return self._zero or None

    def optimizer_state_bytes(self) -> int:
        """Total live optimizer-state bytes across every replica: the
        shard totals under MXNET_ZERO (~1/N of replicated), the full
        per-replica states otherwise. Benchmarks publish this in their
        JSON (bench.py / tools/bert_bench.py) and tools/zero_micro.py
        gates the sharded-vs-replicated ratio on it."""
        from . import zero as zero_mod
        if isinstance(self._zero, zero_mod.ZeroEngine):
            return self._zero.state_bytes_total()

        def _arrays(state):
            if state is None:
                return
            if isinstance(state, (tuple, list)):
                for s in state:
                    yield from _arrays(s)
                return
            yield state

        total = 0
        for upd in self._updaters:
            for state in upd.states.values():
                for arr in _arrays(state):
                    try:
                        total += int(arr.size) * arr.dtype.itemsize
                    except Exception:
                        pass
        return total

    # ------------------------------------------------------------------
    # fused-update mode (MXNET_TRAINER_FUSED_UPDATE; docs/KERNELS.md)
    # ------------------------------------------------------------------
    def _fused_update_eligible(self):
        from .. import config as _cfg_mod
        from .. import optimizer as opt_mod
        if not _cfg_mod.get("MXNET_TRAINER_FUSED_UPDATE"):
            return False
        if self._fused_structural_bail:
            return False
        if self._kvstore is not None or self._update_on_kvstore:
            return False
        if len(self._contexts) != 1 or not self._updaters:
            return False
        guard = self.grad_guard
        if guard is not None and getattr(guard, "enabled", False):
            return False               # the guard's pass is per step
        opt = self._optimizer
        # exact-class check: a subclass may override the update math the
        # in-graph form replicates
        if type(opt) is not opt_mod.SGD:
            return False
        if getattr(opt, "multi_precision", False):
            return False               # tuple states: not in-graph
        if getattr(opt, "aggregate_num", 1) <= 1:
            return False
        for param in self._params:
            if param.grad_req not in ("null", "write"):
                return False
        return True

    def _rearm_fused_update(self):
        from .. import autograd as _ag
        if self._fused_update_eligible():
            leaf_ids = [id(p.list_data()[0]) for p in self._params
                        if p.grad_req != "null" and p._data is not None]
            if leaf_ids:
                _ag.arm_fused_update(self, leaf_ids)
                self._fused_armed = True
                return
        if self._fused_armed:
            _ag.disarm_fused_update(self)
        self._fused_armed = False

    def _note_pre_update(self, prep):
        """Pre-update weight aliases, for modelwatch's update norms, on
        a sampled step: taken by the fused consume BEFORE its launch
        (an alias is a second holder, so that step donates nothing and
        the aliases stay readable)."""
        mw = self._modelwatch
        if mw is None or not mw.sampling:
            return None
        return mw.note_pre_update(
            [(it[1].name, it[2]) for it in prep.items])

    def _prep_fused_plan(self, plan):
        """The optimizer-side prologue of the fused consume: validate
        the tape<->parameter mapping and advance the update counters
        exactly as the classic update would, so schedule-dependent
        hyperparams (lr keyed on num_update) carry their per-step
        values. Returns a FusedPrep, or None on structural mismatch
        (counters untouched — the caller falls back)."""
        import numpy as np
        opt = self._optimizer
        upd = self._updaters[0]
        pos_by_id = {}
        for pos, s in enumerate(plan.grad_slots):
            pos_by_id.setdefault(id(plan.leaf_arrays[s]), []).append((pos, s))
        items = []
        for i, param in enumerate(self._params):
            if param.grad_req == "null" or param._data is None:
                continue
            if param.grad_req != "write":
                return None
            data_arr = param.list_data()[0]
            ent = pos_by_id.get(id(data_arr))
            if ent is None or len(ent) != 1:
                # param absent from this tape (stale grad) or mutated
                # mid-forward — the in-graph update can't reproduce the
                # separate path's semantics; run reference-idiomatic
                return None
            if i not in upd.states:
                upd.states[i] = opt.create_state_multi_precision(
                    i, data_arr)
            state = upd.states[i]
            if isinstance(state, tuple):     # multi-precision: not in-graph
                return None
            items.append((i, param, data_arr, state, ent[0][0], ent[0][1]))
        if not items:
            return None

        # hyperparams exactly as SGD.update_multi's hyper(): counters
        # advance, then per-tensor lrs/wds ride as device tensors
        for i, *_ in items:
            opt._update_count(i)
        lrs = np.array([opt._get_lr(it[0]) for it in items], np.float32)
        wds = np.array([opt._get_wd(it[0]) for it in items], np.float32)
        momentum = float(opt.momentum)
        clip = -1.0 if opt.clip_gradient is None else float(opt.clip_gradient)
        rescale = float(opt.rescale_grad)
        rows = tuple((it[4], it[5], it[3] is not None) for it in items)
        gdt = tuple(str(next(iter(it[1]._grad.values())).dtype)
                    for it in items)
        mom_rows = tuple(k for k, r in enumerate(rows) if r[2])
        plain_rows = tuple(k for k, r in enumerate(rows) if not r[2])
        upd_key = ("sgd", momentum, clip, rescale, rows, gdt)
        return FusedPrep(
            items, rows, gdt, mom_rows, plain_rows, upd_key, lrs, wds,
            momentum, clip, rescale)

    def _make_upd_math(self, prep):
        """The pure multi-tensor SGD update over a prep's rows, traced
        into the fused step program."""
        import jax.numpy as jnp
        from ..ops import get_op
        mom_impl = get_op("preloaded_multi_sgd_mom_update").impl
        plain_impl = get_op("preloaded_multi_sgd_update").impl
        rows, gdt = prep.rows, prep.gdt
        mom_rows, plain_rows = prep.mom_rows, prep.plain_rows
        momentum, clip, rescale = prep.momentum, prep.clip, prep.rescale

        def upd_math(leaf_vals, grads, state_vals, hp_vals):
            lrs_m, wds_m, lrs_p, wds_p = hp_vals
            new_ws = [None] * len(rows)
            new_moms = []

            def gval(k):
                gp, _, _ = rows[k]
                return grads[gp].astype(jnp.dtype(gdt[k]))

            if mom_rows:
                arrays = []
                for mi, k in enumerate(mom_rows):
                    arrays += [leaf_vals[rows[k][1]], gval(k),
                               state_vals[mi]]
                outs = mom_impl(*arrays, lrs_m, wds_m, momentum=momentum,
                                rescale_grad=rescale, clip_gradient=clip,
                                num_weights=len(mom_rows))
                n = len(mom_rows)
                for mi, k in enumerate(mom_rows):
                    new_ws[k] = outs[mi]
                    new_moms.append(outs[n + mi])
            if plain_rows:
                arrays = []
                for k in plain_rows:
                    arrays += [leaf_vals[rows[k][1]], gval(k)]
                outs = plain_impl(*arrays, lrs_p, wds_p,
                                  rescale_grad=rescale, clip_gradient=clip,
                                  num_weights=len(plain_rows))
                outs = outs if isinstance(outs, tuple) else (outs,)
                for oi, k in enumerate(plain_rows):
                    new_ws[k] = outs[oi]
            return new_ws, new_moms

        return upd_math

    def _consume_fused_plan(self, plan):
        """Execute a deferred backward plan with the SGD multi-tensor
        update appended — one XLA program. Returns True on success;
        on any structural mismatch the plan is executed plainly (grads
        written) and False is returned so the classic path proceeds."""
        import jax.numpy as jnp
        with telemetry.phase("update.prep"):
            prep = self._prep_fused_plan(plan)
            if prep is not None:
                items = prep.items
                mom_rows, plain_rows = prep.mom_rows, prep.plain_rows
                upd_math = self._make_upd_math(prep)
                state_arrs = [items[k][3] for k in mom_rows]
                state_vals = [a._jax() for a in state_arrs]
                hp_vals = (jnp.asarray(prep.lrs[list(mom_rows)]),
                           jnp.asarray(prep.wds[list(mom_rows)]),
                           jnp.asarray(prep.lrs[list(plain_rows)]),
                           jnp.asarray(prep.wds[list(plain_rows)]))
                # what this step rebinds: each row's weight (by leaf
                # slot), momentum and gradient. The program takes their
                # buffers for its outputs when nothing else holds them
                owners = ([it[5] for it in items], state_arrs,
                          [it[2]._grad for it in items])
                caps = self._note_pre_update(prep)
        if prep is None:
            plan.execute()
            return False
        try:
            with telemetry.phase("update.launch"):
                new_ws, new_moms = plan.execute_with_update(
                    prep.upd_key, upd_math, state_vals, hp_vals, owners)
            with telemetry.phase("update.writeback"):
                self._write_back_fused(prep, new_ws, new_moms, caps)
        finally:
            # readers on other threads waited at the step's gate from
            # the launch on: every handle reads its new value now
            plan.release()
        return True

    def _write_back_fused(self, prep, new_ws, new_moms, caps=None):
        """Rebind the parameters and momenta to the fused step's
        outputs (``step::update.writeback``). ``caps``: modelwatch's
        pre-update weight aliases of a sampled step — they feed both
        the update-norm reduction and the param-norm side of the
        fused-path stats."""
        items, mom_rows = prep.items, prep.mom_rows
        for k, (i, param, data_arr, state, _gp, _ws) in enumerate(items):
            data_arr._set_jax(new_ws[k])
        for mi, k in enumerate(mom_rows):
            items[k][3]._set_jax(new_moms[mi])
        if caps is not None:
            # defer=False: the fused path's read happens AFTER this
            # update, so the vector rides the same step's report
            # instead of the classic one-step-stale stash
            unorm = self._modelwatch.note_post_update(caps, defer=False)
            self._mw_fused_caps = (caps, unorm)

    def allreduce_grads(self):
        if not self._kv_initialized:
            self._contexts = self._check_contexts()
            self._init_kvstore()
        self._allreduce_grads()

    def _allreduce_grads(self):
        if self._kvstore is None:
            return
        if not self._update_on_kvstore and \
                hasattr(self._kvstore, "pushpull_list"):
            # batch every key into ONE compiled collective program per
            # step (ref: KVStoreNCCL grouped allreduce) instead of a
            # per-param push/pull loop
            keys, values = [], []
            for i, param in enumerate(self._params):
                if param.grad_req != "null":
                    keys.append(i)
                    values.append(param.list_grad())
            if keys:
                self._kvstore.pushpull_list(keys, values)
            return
        for i, param in enumerate(self._params):
            if param.grad_req != "null":
                grads = param.list_grad()
                self._kvstore.push(i, grads, priority=-i)
                if not self._update_on_kvstore:
                    self._kvstore.pull(i, grads, priority=-i,
                                       ignore_sparse=False)

    def update(self, batch_size, ignore_stale_grad=False):
        if not self._kv_initialized:
            self._contexts = self._check_contexts()
            self._init_kvstore()
        self._optimizer.rescale_grad = self._scale / batch_size
        self._update(ignore_stale_grad)

    def _update(self, ignore_stale_grad=False):
        # collect the whole update pass per device and dispatch it as ONE
        # compiled multi-tensor program when the optimizer supports it
        # (ref: MXNet 1.6 aggregate updates / multi_sgd kernels) — on TPU
        # this collapses ~#params dispatches into one XLA execution
        per_dev = [[] for _ in self._updaters]
        for i, param in enumerate(self._params):
            if param.grad_req == "null" or param._data is None:
                continue
            for d, (arr, grad) in enumerate(zip(param.list_data(),
                                                param.list_grad())):
                per_dev[d].append((i, grad, arr))
        aggregate = getattr(self._optimizer, "aggregate_num", 1) > 1
        # the N per-device updaters SHARE the optimizer: without
        # rewinding, _update_count advances once per REPLICA per step,
        # so step-dependent updates (Adam/AdamW bias correction, LR
        # schedules keyed on num_update) see a different t on every
        # device and the replicas silently drift apart. Rewind the
        # counters between devices so every replica updates from the
        # same base and the step advances the count by exactly one —
        # the single-device (and ZeRO-sharded) trajectory.
        opt = self._optimizer
        multi = len(self._updaters) > 1
        if multi:
            base_counts = dict(opt._index_update_count)
            base_num = opt.num_update
        for d, (upd, items) in enumerate(zip(self._updaters, per_dev)):
            if multi and d > 0:
                opt._index_update_count = dict(base_counts)
                opt.num_update = base_num
            if aggregate and len(items) > 1:
                upd.update_multi([i for i, _, _ in items],
                                 [g for _, g, _ in items],
                                 [w for _, _, w in items])
            else:
                for i, grad, arr in items:
                    upd(i, grad, arr)

    # ------------------------------------------------------------------
    def save_states(self, fname):
        """Optimizer-state checkpoint. Under MXNET_ZERO the sharded
        state is GATHERED to the canonical replicated layout first
        (gluon/zero.py), so the file is identical in format to a
        replicated Trainer's and restores on any topology (ROADMAP
        item 5). An engine that never stepped doesn't exist yet — the
        classic (empty-states) path covers that, same as replicated.

        With MXNET_KVSTORE_QUANTIZE active the error-feedback
        residuals of the quantized grad sync are real carried state
        (docs/QUANTIZE.md): the kvstore path wraps them alongside the
        canonical updater blob (the ZeRO engine does its own wrapping);
        with quantization off the file stays byte-identical to
        today's."""
        with open(fname, "wb") as f:
            f.write(self.states_blob())

    def states_blob(self) -> bytes:
        """The save_states payload as bytes — what the Estimator's
        elastic checkpointing writes as the manifest's optimizer-state
        sidecar (model.save_checkpoint states_blob=, docs/ELASTIC.md)
        without touching the filesystem here."""
        assert self._optimizer is not None
        if not self._kv_initialized:
            self._contexts = self._check_contexts()
            self._init_kvstore()
        from . import zero as zero_mod
        if isinstance(self._zero, zero_mod.ZeroEngine):
            blob = self._zero.serialized_states()
        else:
            blob = self._updaters[0].get_states(dump_optimizer=False)
            kv = self._kvstore
            if kv is not None and getattr(kv, "_quant_state", None):
                res = kv.quant_residuals_export()
                if res:
                    import pickle
                    blob = pickle.dumps({"__mx_quant__": 1,
                                         "updater": blob,
                                         "kv_residual": res})
        return blob

    def load_states(self, fname):
        """Restore optimizer state from a canonical checkpoint. Under
        MXNET_ZERO the states are RE-SCATTERED onto this Trainer's
        shard layout (whatever its replica count — the checkpoint is
        topology-portable); otherwise the replicated updaters load it
        as before. Quantize-wrapped blobs (either sync path's, see
        save_states) restore their error-feedback residuals when the
        target path quantizes too, and degrade to the plain states
        otherwise — a checkpoint never fails to load over a quantize
        or topology change."""
        with open(fname, "rb") as f:
            states = f.read()
        self.load_states_blob(states)

    def load_states_blob(self, states: bytes):
        """load_states from an in-memory payload (the manifest's
        optimizer-state sidecar on an elastic resume — the blob may
        have been written on ANY topology; docs/ELASTIC.md)."""
        if not self._kv_initialized:
            self._contexts = self._check_contexts()
            self._init_kvstore()
        engine = self._zero_engine()
        if engine is not None:
            engine.load_serialized_states(states)
            return
        import pickle
        try:
            obj = pickle.loads(states)
        except Exception:
            obj = None
        if isinstance(obj, dict) and obj.get("__mx_quant__"):
            states = obj["updater"]
            kv = self._kvstore
            if kv is not None and hasattr(kv, "quant_residuals_restore"):
                kv.quant_residuals_restore(obj.get("kv_residual") or {})
        elif isinstance(obj, dict) and obj.get("__mx_zero_quant__"):
            # a quantized-ZeRO checkpoint on a replicated Trainer: the
            # canonical states restore as-is; the grad residual maps
            # onto the kvstore path's carry (same param-space
            # semantics), the weight residual has no replicated
            # analogue (the weights here are exact) and is dropped
            states = pickle.dumps(obj["states"])
            kv = self._kvstore
            if kv is not None and hasattr(kv, "quant_residuals_restore"):
                kv.quant_residuals_restore(
                    {str(k): v for k, v in
                     (obj.get("grad_residual") or {}).items()})
        for updater in self._updaters:
            updater.set_states(states)
            updater.optimizer = self._optimizer

    # ------------------------------------------------------------------
    def reshard_to(self, contexts, blk_bytes=None):
        """Live shrink/grow (ISSUE 16, docs/ELASTIC.md): rebind this
        Trainer IN PLACE onto a new device set — params, replicated
        updater states, the kvstore device mesh, and (under MXNET_ZERO)
        the sharded engine state — without a restart:

        1. drain in-flight engine work and pending checkpoint writes;
        2. rebind every parameter onto the survivor contexts
           (Parameter.reset_ctx — replicas are identical post-step);
        3. clone replicated updater states from replica 0 onto the new
           context set;
        4. drop the kvstore so the next step lazily rebuilds it (and
           its watched programs) on the new mesh;
        5. rebuild the ZeRO engine on the new topology and move its
           sharded optimizer state + EF residuals over device-to-device
           through the staged parallel/reshard pass (memory-bounded,
           arxiv 2112.01075); a survivor set too small to shard
           dissolves the engine into the replicated updaters.

        Raises on failure (plan mismatch, injected reshard_fail) —
        elastic.run_transition catches and degrades to
        checkpoint-restore (model.load_latest_checkpoint)."""
        from .. import faultinject
        from .. import model as model_mod
        from ..engine import native_or_none
        from ..parallel.reshard import ReshardError
        from . import zero as zero_mod
        contexts = list(contexts)
        if not contexts:
            raise ValueError("reshard_to: empty context list")
        # transition entry: the deterministic failure hook for the
        # degradation path — replicated moves never reach a reshard
        # primitive's own site, so the live transition checks here too
        faultinject.maybe_fail("reshard_fail", ReshardError)
        eng = native_or_none()
        if eng is not None:
            eng.wait_for_all()
        model_mod.wait_checkpoints()
        old_zero = self._zero \
            if isinstance(self._zero, zero_mod.ZeroEngine) else None
        for param in self._params:
            if param._data is not None:
                param.reset_ctx(contexts)
        self._contexts = contexts
        src = self._updaters[0] if self._updaters else None
        self._updaters = [opt_mod.get_updater(self._optimizer)
                          for _ in contexts]
        if src is not None and src.states:
            def _move(a, ctx):
                return a.as_in_context(ctx) \
                    if hasattr(a, "as_in_context") else a
            for upd, ctx in zip(self._updaters, contexts):
                for i, st in src.states.items():
                    upd.states[i] = tuple(_move(a, ctx) for a in st) \
                        if isinstance(st, (tuple, list)) \
                        else _move(st, ctx)
        self._kvstore = None
        self._kv_initialized = False
        if old_zero is not None:
            self._zero = None
            self._zero_bailed = False
            self._contexts = self._check_contexts()
            self._init_kvstore()
            ok, why = zero_mod.eligibility(self)
            if ok:
                engine = zero_mod.ZeroEngine(self)
                engine.reshard_from(old_zero, blk_bytes=blk_bytes)
                self._zero = engine
            else:
                # survivor set can't shard (e.g. one device): hand the
                # accumulated state to the replicated updaters — the
                # run continues un-sharded rather than resetting moments
                old_zero.dissolve_into(self._updaters, contexts)
                self._zero = False
                self._zero_bailed = True
