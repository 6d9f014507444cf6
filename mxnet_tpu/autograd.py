"""Autograd: imperative differentiation on a dynamic graph tape.

Ref: python/mxnet/autograd.py (record/pause/train_mode scopes, backward,
Function) over src/imperative/imperative.cc (Imperative::RecordOp builds
nnvm nodes with AGInfo; Imperative::Backward composes per-op FGradient
and executes via RunGraph).

TPU-native design: instead of per-op hand-written FGradient kernels,
each recorded node captures the ``jax.vjp`` closure of the op's pure-JAX
impl — forward consistency is structural, and the vjp's residuals live
in HBM like the reference's saved forward buffers. ``backward()`` walks
the graph reverse-topologically and applies each node's vjp; every
cotangent application is itself XLA-dispatched asynchronously, so
backward overlaps with communication exactly like engine pushes do in
the reference (SURVEY.md §3.2).

This is deliberately NOT ``jax.grad``: mutation, ``grad_req='add'``,
partial graphs, ``autograd.Function`` custom VJPs and cross-scope
recording all require the MXNet tape semantics (SURVEY.md §7.1 M2).
The fused fast path (whole-graph jax.grad) lives in CachedOp instead.
"""
from __future__ import annotations

import collections
import sys
import threading

from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from .base import MXNetError
from . import telemetry

__all__ = ["record", "pause", "train_mode", "predict_mode", "is_recording",
           "is_training", "set_recording", "set_training", "backward", "grad",
           "mark_variables", "get_symbol", "Function"]


class _State(threading.local):
    def __init__(self):
        self.recording = False
        self.training = False


_STATE = _State()


def is_recording() -> bool:
    return _STATE.recording


def is_training() -> bool:
    return _STATE.training


def set_recording(is_rec: bool) -> bool:
    prev, _STATE.recording = _STATE.recording, bool(is_rec)
    return prev


def set_training(train_mode_: bool) -> bool:
    prev, _STATE.training = _STATE.training, bool(train_mode_)
    return prev


class _Scope:
    def __init__(self, recording: Optional[bool], training: Optional[bool]):
        self._rec, self._train = recording, training

    def __enter__(self):
        if self._rec is not None:
            self._prev_rec = set_recording(self._rec)
        if self._train is not None:
            self._prev_train = set_training(self._train)
        return self

    def __exit__(self, *exc):
        if self._rec is not None:
            set_recording(self._prev_rec)
        if self._train is not None:
            set_training(self._prev_train)
        return False

    # allow use as decorator, like mxnet's scopes
    def __call__(self, fn):
        def wrapped(*a, **kw):
            with self.__class__(self._rec, self._train):
                return fn(*a, **kw)
        return wrapped


def record(train_mode: bool = True) -> _Scope:
    return _Scope(True, train_mode)


def pause(train_mode: bool = False) -> _Scope:
    return _Scope(False, train_mode)


def train_mode() -> _Scope:
    return _Scope(None, True)


def predict_mode() -> _Scope:
    return _Scope(None, False)


# ---------------------------------------------------------------------------
# graph nodes
# ---------------------------------------------------------------------------
class _Node:
    """One recorded op application (ref: nnvm::Node + AGInfo). Output
    identity lives in each NDArray's (_ag_node, _ag_out_idx) pointer;
    backward() keys cotangents on that SSA pair, not on objects."""

    __slots__ = ("inputs", "vjp_fn", "out_avals", "n_rng", "n_extra",
                 "op_name", "fwd_fn", "rng_key", "input_ssa", "raw_inputs",
                 "fused_key", "fused_ok", "executed", "force_cb", "out_refs",
                 "out_values", "aux_in")

    def __init__(self, op_name, inputs, vjp_fn, out_avals, n_rng, n_extra,
                 fwd_fn=None, rng_key=None, raw_inputs=None, fused_key=None,
                 fused_ok=True, executed=True, force_cb=None):
        self.op_name = op_name
        self.inputs = list(inputs)      # strong refs keep the graph alive
        self.vjp_fn = vjp_fn            # holds residuals in HBM
        self.out_avals = out_avals      # ShapeDtypeStruct per raw output
        self.n_rng = n_rng
        self.n_extra = n_extra
        self.fwd_fn = fwd_fn            # pure fn for replay (create_graph)
        self.rng_key = rng_key          # key used at record time
        # record-time raw input VALUES (jax arrays, rng excluded) — the
        # fused backward replays from these, immune to later mutation of
        # the live NDArray objects (same capture the vjp closure does)
        self.raw_inputs = raw_inputs
        # stable identity of fwd_fn across steps, so the fused-backward
        # program cache hits on the second iteration: ("cop", id) for
        # CachedOp, ("op", name, attrs_key, ...) for eager ops
        self.fused_key = fused_key
        self.fused_ok = fused_ok        # False: custom vjp (sparse emb, grad-of-grad)
        self.executed = executed        # False: deferred CachedOp, not yet run
        self.force_cb = force_cb        # fills outputs + vjp_fn when forced
        self.out_refs = None            # weakrefs to out arrays (deferred only)
        self.out_values = None          # raw outputs after force (replay feed)
        self.aux_in = ()                # input positions rewritten by extra outputs
        # SSA producers captured AT RECORD TIME: a later recorded
        # mutation rebinds inp._ag_node, so replay must not chase the
        # live pointer (it would feed post-mutation values to
        # pre-mutation uses)
        self.input_ssa = [(inp._ag_node, inp._ag_out_idx)
                          if inp._ag_node is not None else None
                          for inp in self.inputs]

    def force(self):
        """Materialize a deferred node (run fwd, fill outputs, set
        vjp_fn). No-op for already-executed nodes."""
        if self.executed:
            return
        self.executed = True
        cb, self.force_cb = self.force_cb, None
        cb(self)


def _record_node(op, inputs, out_arrays, vjp_fn, out_avals, n_rng=0,
                 n_extra=0, fwd_fn=None, rng_key=None, raw_inputs=None,
                 fused_key=None, fused_ok=True):
    node = _Node(op.name, inputs, vjp_fn, out_avals, n_rng, n_extra,
                 fwd_fn=fwd_fn, rng_key=rng_key, raw_inputs=raw_inputs,
                 fused_key=fused_key, fused_ok=fused_ok)
    for i, arr in enumerate(out_arrays):
        arr._ag_node = node
        arr._ag_out_idx = i
    return node


def _record_deferred_node(op_name, inputs, out_arrays, out_avals, n_rng,
                          n_extra, fwd_fn, rng_key, raw_inputs, fused_key,
                          force_cb, aux_arrays=(), aux_in=()):
    """Record a node whose execution is DEFERRED: outputs are pending
    NDArrays filled either by node.force() (classic path / value read)
    or by the fused backward program (autograd.backward bulking —
    the XLA analogue of the reference CachedOp's bulked engine
    segments). aux_arrays are mutated inputs (BatchNorm stats) whose
    new values are extra outputs of the deferred program."""
    import weakref
    node = _Node(op_name, inputs, None, out_avals, n_rng, n_extra,
                 fwd_fn=fwd_fn, rng_key=rng_key, raw_inputs=raw_inputs,
                 fused_key=fused_key, executed=False, force_cb=force_cb)
    # where the mutated inputs sit among `inputs`: the fused step hands
    # their old buffers to the program as the ones its extra outputs
    # may take
    node.aux_in = aux_in
    refs = []
    for i, arr in enumerate(out_arrays):
        arr._ag_node = node
        arr._ag_out_idx = i
        arr._pending = (node, i, out_avals[i])
        refs.append(weakref.ref(arr))
    for k, arr in enumerate(aux_arrays):
        # the aux array's CURRENT value was already captured into
        # raw_inputs; rebinding it to pending is the deferred analogue
        # of the immediate _write_aux
        arr._pending = (node, len(out_arrays) + k,
                        out_avals[len(out_arrays) + k])
        refs.append(weakref.ref(arr))
    node.out_refs = refs
    return node


def mark_variables(variables, gradients, grad_reqs="write"):
    """Ref: autograd.mark_variables — associate grads with vars."""
    if isinstance(grad_reqs, str):
        grad_reqs = [grad_reqs] * len(variables)
    for v, g, req in zip(variables, gradients, grad_reqs):
        v._ag_var = True
        v._grad = g
        v._grad_req = req


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------
_ZERO_COTS = {}   # (shape, dtype) -> cached zero cotangent constant

# ---------------------------------------------------------------------------
# fused backward — tape bulking into ONE XLA program
#
# When every node on the tape can be replayed from a stable pure function
# (deferred CachedOps + eager registry ops), loss.backward() compiles the
# WHOLE forward+backward into a single jitted program (cached on the
# tape's structure), instead of the two-program vjp split per CachedOp.
# This is the XLA analogue of the reference CachedOp's bulked engine
# segments (src/imperative/cached_op.cc static_alloc bulking): residuals
# never cross a program boundary, XLA fuses and schedules fwd+bwd
# globally, and the hybridize()+Trainer loop reaches the same device
# time as a hand-fused train step.
# ---------------------------------------------------------------------------
_FUSED_CACHE: Dict = {}
_COP_FNS: Dict = {}      # CachedOp uid -> train_flat (resolved at build)


def _release_cop(uid):
    """CachedOp finalizer hook: drop its fn/symbol registrations AND
    every fused-backward compiled program whose tape referenced it —
    the runners close over train_flat, so without this eviction the
    finalizer would free nothing."""
    _COP_FNS.pop(uid, None)
    _COP_SYMS.pop(uid, None)
    dead = [skey for skey in _FUSED_CACHE
            if any(sp[0] == ("cop", uid) for sp in skey[0])]
    for skey in dead:
        del _FUSED_CACHE[skey]
    dead_step = [k for k in _FUSED_STEP_CACHE
                 if any(sp[0] == ("cop", uid) for sp in k[0][0])]
    for k in dead_step:
        del _FUSED_STEP_CACHE[k]


def _fused_enabled():
    from .config import get as _cfg
    return _cfg("MXNET_FUSED_BACKWARD")


def _fill_pending(node, values):
    """Write a deferred node's produced raw outputs into every pending
    NDArray still alive (single source of truth for the fill contract)."""
    node.out_values = tuple(values)
    if node.out_refs:
        for ref in node.out_refs:
            arr = ref()
            if arr is not None and arr._pending is not None \
                    and arr._pending[0] is node:
                arr._set_jax(values[arr._pending[1]])


def _rebuild_callable(fused_key):
    if fused_key[0] == "cop":
        return _COP_FNS[fused_key[1]]
    _, name, attrs_key, none_slots, total, n_rng = fused_key
    from .ops import get_op
    fn = get_op(name).bind_attrs(dict(attrs_key))
    if none_slots:
        from .ndarray.ndarray import _scatter_none_wrapper
        fn = _scatter_none_wrapper(fn, list(none_slots), total, n_rng)
    return fn


def _fused_compute(node_specs, head_specs, grad_slots, hg_present):
    """The pure fwd+bwd body shared by the fused-backward program and
    the fused-STEP program (fwd+bwd+optimizer; MXNET_TRAINER_FUSED_UPDATE)."""
    callables = [_rebuild_callable(sp[0]) for sp in node_specs]
    rng_pos = []
    k = 0
    for sp in node_specs:
        rng_pos.append(k if sp[1] else -1)
        k += sp[1]

    def compute(leaf_vals, rng_vals, hg_vals):
        def inner(grad_vals):
            full = list(leaf_vals)
            for s, v in zip(grad_slots, grad_vals):
                full[s] = v
            vals = []
            for (fk, has_rng, ins, n_out), fn, rp in zip(
                    node_specs, callables, rng_pos):
                args = [rng_vals[rp]] if has_rng else []
                for spec in ins:
                    if spec[0] == "l":
                        args.append(full[spec[1]])
                    else:
                        args.append(vals[spec[1]][spec[2]])
                out = fn(*args)
                vals.append(tuple(out) if isinstance(out, (tuple, list))
                            else (out,))
            total = jnp.zeros((), jnp.float32)
            hi = 0
            for (ni, oi), has_hg in zip(head_specs, hg_present):
                v = vals[ni][oi]
                if has_hg:
                    total = total + (v * hg_vals[hi]).sum().astype(jnp.float32)
                    hi += 1
                else:
                    total = total + v.sum().astype(jnp.float32)
            flat = tuple(v for outs in vals for v in outs)
            return total, flat

        (_, flat), grads = jax.value_and_grad(inner, has_aux=True)(
            [leaf_vals[s] for s in grad_slots])
        return flat, grads

    return compute


def _build_fused(node_specs, head_specs, grad_slots, hg_present):
    runner = _fused_compute(node_specs, head_specs, grad_slots, hg_present)
    # watched jit (ISSUE 4): the fused fwd+bwd program is the biggest
    # compile in the process — stage timing, FLOPs/HBM accounting and
    # recompile attribution all flow through compilewatch
    from .compilewatch import watched_jit
    return watched_jit(runner, fn_label="autograd.fused_backward",
                       site="autograd.backward",
                       arg_names=["leaves", "rng", "head_grads"],
                       instance="tape[%d nodes]" % len(node_specs))


def _build_fused_step(node_specs, head_specs, grad_slots, hg_present,
                      upd_math, owned_slots=None):
    """fwd+bwd+optimizer in ONE program (MXNET_TRAINER_FUSED_UPDATE):
    upd_math is the Trainer-supplied pure update — it receives
    (leaf_vals, grads, state_vals, hp_vals) and returns (new_ws,
    new_states) for its parameter rows. Gradients are still produced as
    program outputs so Parameter.grad() keeps its post-step contents.

    ``owned_slots`` = (weight leaf slots, running-statistic leaf slots,
    the other leaves' slots) builds the DONATING variant: the buffers the step
    overwrites arrive apart from the leaves it only reads, as one
    donated argument (last step's gradients, weights, optimizer states,
    running statistics), so every output that replaces one of them
    takes its buffer and the runtime allocates nothing for it. jax
    pairs a donated input with the first unclaimed output of its shape
    and dtype, so both sides keep one order — gradients, weights,
    states, then the tape's own outputs — and the large buffers pair
    with their own successors whatever the tape returns. The old
    gradients are inputs nothing reads (``keep_unused``): they are
    there to be overwritten."""
    compute = _fused_compute(node_specs, head_specs, grad_slots, hg_present)

    def runner(leaf_vals, rng_vals, hg_vals, state_vals, hp_vals):
        flat, grads = compute(leaf_vals, rng_vals, hg_vals)
        new_ws, new_states = upd_math(leaf_vals, grads, state_vals, hp_vals)
        return flat, grads, new_ws, new_states

    from .compilewatch import watched_jit
    instance = "tape[%d nodes]+update" % len(node_specs)
    if owned_slots is None:
        return watched_jit(runner, fn_label="autograd.fused_step",
                           site="trainer.step",
                           arg_names=["leaves", "rng", "head_grads",
                                      "opt_states", "opt_hyper"],
                           instance=instance)
    w_slots, aux_slots, rest_slots = owned_slots
    n_leaves = len(w_slots) + len(aux_slots) + len(rest_slots)

    def donating(owned, rest_vals, rng_vals, hg_vals, hp_vals):
        _old_grads, w_vals, state_vals, aux_vals = owned
        leaf_vals = [None] * n_leaves
        for slots, vals in ((w_slots, w_vals), (aux_slots, aux_vals),
                            (rest_slots, rest_vals)):
            for s, v in zip(slots, vals):
                leaf_vals[s] = v
        flat, grads, new_ws, new_states = runner(
            leaf_vals, rng_vals, hg_vals, state_vals, hp_vals)
        return grads, new_ws, new_states, flat

    return watched_jit(donating, fn_label="autograd.fused_step",
                       site="trainer.step",
                       arg_names=["owned", "leaves", "rng", "head_grads",
                                  "opt_hyper"],
                       instance=instance + "/donating",
                       donate_argnums=(0,), keep_unused=True)


def _rest_slots(w_slots, aux_slots, n_leaves):
    """The leaf slots a donating step only reads: batch, label,
    whatever else the tape captured."""
    taken = set(w_slots)
    taken.update(aux_slots)
    return [s for s in range(n_leaves) if s not in taken]


def _publish_aliasing(runner, n_outputs):
    """``mx_fused_step_outputs{kind=aliased|all}``: how many outputs of
    the donating program just built take an input's buffer, from the
    compiled program's own input-output alias table. Read once per
    program, never per step."""
    if not telemetry.enabled():
        return
    try:
        aliased = 0
        for compiled in runner.executables():
            # the module's header line: input_output_alias={ {0}:
            # (3, {}, may-alias), ... }
            head = compiled.as_text().split("\n", 1)[0]
            aliased = head.count("-alias)")
        telemetry.gauge("mx_fused_step_outputs", kind="aliased").set(aliased)
        telemetry.gauge("mx_fused_step_outputs", kind="all").set(n_outputs)
    except Exception:
        pass


# ---------------------------------------------------------------------------
# fused-update deferral (MXNET_TRAINER_FUSED_UPDATE)
#
# A Trainer in a steady hybridize loop ARMS this module; the next
# loss.backward() then stashes its fully-built fused-backward plan
# instead of executing it, and Trainer.step() executes the plan with
# the multi-tensor optimizer appended — fwd+bwd+update as ONE XLA
# program, no separate optimizer dispatch re-reading w/g/m from HBM
# (that program: 0.49 ms on ResNet-50, round-5 builder figure).
#
# Safety contract: anything that needs gradients before step() flushes
# the pending plan first (Parameter.grad()/list_grad() call
# flush_pending_step(); a new backward() flushes too). Reading a
# deferred forward output in the window forces that node individually
# through the classic deferred machinery — same values, the later
# program execution simply skips its fill.
# ---------------------------------------------------------------------------
_FUSED_STEP_CACHE: Dict = {}
_ARM_TOKEN = [None]
_ARM_LEAF_IDS = [frozenset()]
_PENDING = [None]


class _StepGate:
    """``NDArray._pending``-compatible gate (cf. ``engine.EngineGate``)
    over what a fused step is rewriting, from its launch to its
    write-back. A donated buffer is deleted at the launch and its
    handle is rebound only when the program's outputs are back; a
    reader on another thread in between (a serving scheduler on the
    live parameters, a logging or checkpoint thread holding a handle)
    must not meet the deleted array, so the step stands in as the
    pending producer of every handle it donates and of every deferred
    node it executes: ``_jax()`` on one waits here and then reads the
    new value. The stepping thread itself passes."""

    __slots__ = ("_done", "_owner")

    def __init__(self):
        self._done = threading.Event()
        self._owner = threading.get_ident()

    def force(self, _node=None):
        if threading.get_ident() != self._owner:
            self._done.wait()

    def open(self):
        self._done.set()


class _PendingStep:
    """A built-but-unexecuted fused backward (all specs + captured
    values). execute() runs the plain fused-backward program;
    execute_with_update() runs the combined fwd+bwd+optimizer program."""

    __slots__ = ("skey", "node_specs", "head_specs", "grad_slots",
                 "hg_present", "leaf_arrays", "leaf_vals", "rng_vals",
                 "hg_vals", "order", "token", "aux_slots", "_gate",
                 "_gated", "_todo")

    def __init__(self, skey, node_specs, head_specs, grad_slots, hg_present,
                 leaf_arrays, leaf_vals, rng_vals, hg_vals, order,
                 aux_slots=()):
        self.skey = skey
        self.node_specs = node_specs
        self.head_specs = head_specs
        self.grad_slots = grad_slots
        self.hg_present = hg_present
        self.leaf_arrays = leaf_arrays
        self.leaf_vals = leaf_vals
        self.rng_vals = rng_vals
        self.hg_vals = hg_vals
        self.order = order
        self.token = None
        # leaf slots of the mutated inputs (BatchNorm's running
        # statistics) whose new values the tape returns, in the order
        # it returns them — fixed by the tape's structure
        self.aux_slots = aux_slots
        self._gate = None       # execute_with_update .. release
        self._gated = self._todo = ()

    def execute(self):
        runner = _FUSED_CACHE.get(self.skey)
        if runner is None:
            runner = _build_fused(self.node_specs, self.head_specs,
                                  self.grad_slots, self.hg_present)
            _FUSED_CACHE[self.skey] = runner
        telemetry.count_launch("gluon")
        flat, grads = runner(self.leaf_vals, self.rng_vals, self.hg_vals)
        self._finish(flat, grads)

    def execute_with_update(self, upd_key, upd_math, state_vals, hp_vals,
                            owners=None):
        """Run fwd+bwd+update as one program. upd_key must uniquely name
        upd_math's math (cache key alongside the tape structure);
        returns (new_ws, new_states) in upd_math's row order for the
        caller to write back.

        ``owners`` = (leaf slot of each row's weight, the NDArrays
        behind ``state_vals``, each row's gradient NDArray) names the
        handles the caller rebinds to this step's outputs. Where each
        of their buffers, and each running statistic's, is held by its
        handle and this plan alone, the donating program runs and the
        outputs take those buffers; where any has another holder (a
        ``detach()``, a same-device copy, a serving session's capture)
        this step runs the program that donates nothing, whole — and
        leaves every handle on a buffer of its own making, so the next
        step donates again. Counted per step in
        ``mx_fused_step_total{donated=1|0}``."""
        gate = self._gate = _StepGate()
        # the nodes this launch executes: a reader that forces one on
        # another thread meanwhile waits for the fill below instead of
        # replaying the forward from inputs the launch may have taken
        todo = {n: n.force_cb for n in self.order if not n.executed}
        self._todo = todo
        for n in todo:
            n.force_cb = gate.force
        owned = None if owners is None \
            else self._owned(owners, state_vals, gate)
        donated = owned is not None
        key = (self.skey, upd_key, donated)
        entry = _FUSED_STEP_CACHE.get(key)
        built = entry is None
        if built:
            # with the program, the slots of the leaves it only reads
            rest_slots = _rest_slots(owners[0], self.aux_slots,
                                     len(self.leaf_vals)) if donated else None
            entry = _FUSED_STEP_CACHE[key] = (_build_fused_step(
                self.node_specs, self.head_specs, self.grad_slots,
                self.hg_present, upd_math,
                (owners[0], self.aux_slots, rest_slots)
                if donated else None), rest_slots)
        runner, rest_slots = entry
        telemetry.count_launch("gluon")
        telemetry.count_event(telemetry.FUSED_STEP_COUNTER,
                              donated="1" if donated else "0")
        # under the Trainer's step::update.launch: its .lookup / .call
        if donated:
            leaf_vals = self.leaf_vals
            rest = [leaf_vals[s] for s in rest_slots]
            grads, new_ws, new_states, flat = runner.call_phased(
                "update.launch", owned, rest, self.rng_vals, self.hg_vals,
                hp_vals)
            if built:
                _publish_aliasing(runner, len(grads) + len(new_ws)
                                  + len(new_states) + len(flat))
        else:
            flat, grads, new_ws, new_states = runner.call_phased(
                "update.launch", self.leaf_vals, self.rng_vals,
                self.hg_vals, state_vals, hp_vals)
        self._finish(flat, grads, todo=todo)
        return new_ws, new_states

    def release(self):
        """The caller has rebound its handles to the step's outputs
        (or the step failed): let waiting readers through. A handle
        still gated here was never rebound; it keeps what it has."""
        gate, self._gate = self._gate, None
        if gate is None:
            return
        for h in self._gated:
            p = h._pending
            if p is not None and p[0] is gate:
                h._pending = None
        for n, cb in self._todo.items():
            if not n.executed:          # the launch never got to it
                n.force_cb = cb
        self._gated = self._todo = ()
        gate.open()

    def _owned(self, owners, state_vals, gate):
        """The buffers this step overwrites, grouped as the donating
        program takes them — (last step's gradients, weights, optimizer
        states, running statistics) — or None where any of them could
        still be read after the step: donation deletes the array under
        every holder, so each must be the current value of the handle
        that is about to be rebound (no view, no engine reader pinned
        to it), and its reference count must be what that handle, this
        plan's own containers and the lists built here account for.
        Every Python-level alias — another NDArray over the same
        array, a raw value someone kept — shows up as one reference
        more. (``jax.device_put`` onto the array's own device makes an
        alias this cannot see: the repo's own such sites keep the
        source array beside the copy.)"""
        w_slots, state_arrs, grad_arrs = owners
        leaf_arrays, leaf_vals = self.leaf_arrays, self.leaf_vals
        aux_slots = self.aux_slots
        handles = list(grad_arrs)
        handles += [leaf_arrays[s] for s in w_slots]
        handles += state_arrs
        handles += [leaf_arrays[s] for s in aux_slots]
        owned = ([g._buf for g in grad_arrs],
                 [leaf_vals[s] for s in w_slots],
                 state_vals,
                 [leaf_vals[s] for s in aux_slots])
        # a gradient takes the old gradient's buffer only if it has its
        # shape and dtype; one that has not would be donated for nothing
        # (in a generator's own scope: a loop variable left bound here
        # would be a reference the count below does not expect)
        if any(g.shape != w.shape or g.dtype != w.dtype
               for g, w in zip(owned[0], owned[1])):
            return None
        vals = [v for group in owned for v in group]
        held = collections.Counter(map(id, vals))
        if len(held) != len(vals):
            return None             # one array under two of the handles
        for group in owned:
            held.update(map(id, group))
        held.update(map(id, leaf_vals))
        for n in self.order:
            if n.raw_inputs is not None:
                held.update(map(id, n.raw_inputs))
        first_aux = len(vals) - len(aux_slots)
        refs = sys.getrefcount
        gated = self._gated = []
        for i, h in enumerate(handles):
            v = vals[i]
            if h._buf is not v or h._base is not None or h._read_pins \
                    or h.stype != "default":
                break
            p = h._pending
            if i >= first_aux:
                # a statistic: pending on a node this launch fills
                # (and gates); one it will not refill stays as it is
                if p is None or \
                        getattr(p[0], "force_cb", None) != gate.force:
                    break
            elif p is not None:
                break
            else:
                # gate first, count after: whoever read the buffer
                # before the gate holds a reference the count sees,
                # whoever reads after it waits for the write-back
                h._pending = (gate, i, v.aval)      # no reference to v
                gated.append(h)
            # beside what `held` counted: the handle, the local `v`
            # and the call's own argument
            if refs(v) != held[id(v)] + 3:
                break
        else:
            return owned
        for h in gated:
            h._pending = None
        self._gated = ()
        return None

    def _finish(self, flat, grads, todo=()):
        # fill pending outputs of still-deferred nodes + stash replay
        # values (a node forced in the deferral window just skips its
        # fill — the replayed values are identical by construction).
        # `todo`: nodes a gated launch took on; a reader that forced
        # one meanwhile marked it executed and waits for this fill
        off = 0
        for n, sp in zip(self.order, self.node_specs):
            n_out = sp[3]
            if not n.executed or n in todo:
                n.executed = True
                n.force_cb = None
                _fill_pending(n, flat[off:off + n_out])
            off += n_out

        # leaf gradient write-back (same req semantics as the classic
        # walk); a var captured under two different values occupies two
        # slots — sum them into one cotangent like _acc does
        per_arr: Dict[int, list] = {}
        for pos, s in enumerate(self.grad_slots):
            arr = self.leaf_arrays[s]
            if not (arr._ag_var and arr._grad is not None):
                continue
            got = per_arr.get(id(arr))
            if got is None:
                per_arr[id(arr)] = [arr, grads[pos]]
            else:
                got[1] = got[1] + grads[pos]
        for arr, g in per_arr.values():
            tgt = arr._grad
            if arr._grad_req == "write":
                tgt._set_jax(g.astype(tgt.dtype))
            elif arr._grad_req == "add":
                tgt._set_jax(tgt._jax() + g.astype(tgt.dtype))

        # release replay memory
        for n in self.order:
            n.raw_inputs = None
            n.vjp_fn = None


def arm_fused_update(token, leaf_ids=None):
    """Arm deferral: the next eligible backward() whose grad leaves
    cover `leaf_ids` (ids of the Trainer's parameter data arrays — the
    token keeps them alive, so ids are stable) stashes its plan for
    `token` (the Trainer) to consume at step(). Tapes from other models
    execute immediately. One token at a time — arming replaces any
    previous owner."""
    _ARM_TOKEN[0] = token
    _ARM_LEAF_IDS[0] = frozenset(leaf_ids or ())


def disarm_fused_update(token=None):
    if token is None or _ARM_TOKEN[0] is token:
        _ARM_TOKEN[0] = None
        _ARM_LEAF_IDS[0] = frozenset()


def take_pending_step(token):
    """Claim the stashed plan if it belongs to `token`; None otherwise."""
    p = _PENDING[0]
    if p is not None and p.token is token:
        _PENDING[0] = None
        return p
    return None


def flush_pending_step():
    """Execute any stashed plan as a plain fused backward (grads written,
    pendings filled). Cheap no-op when nothing is pending — called from
    backward() entry and Parameter.grad()/list_grad()."""
    p = _PENDING[0]
    if p is not None:
        _PENDING[0] = None
        p.execute()


def _try_fused_backward(heads, head_grads, order):
    """Attempt the one-program fused backward. Returns True if it ran
    (grads written, pending arrays filled) or was stashed for an armed
    Trainer; False -> caller falls back to the classic per-node vjp
    walk."""
    if not _fused_enabled():
        return False
    any_deferred = False
    for n in order:
        if not n.fused_ok or n.fused_key is None or n.raw_inputs is None:
            return False
        if not n.executed:
            any_deferred = True
    if not any_deferred:
        # everything already ran eagerly — replaying the whole forward
        # would double-compute; classic walk is cheaper
        return False
    for h in heads:
        if h._ag_node is None:
            return False

    node_index = {id(n): i for i, n in enumerate(order)}
    leaf_slots: Dict[tuple, int] = {}
    leaf_arrays = []
    leaf_vals = []
    node_specs = []
    rng_vals = []
    aux_slots = []
    for n in order:
        ins = []
        for inp, ssa, rawv in zip(n.inputs, n.input_ssa, n.raw_inputs):
            pend = isinstance(rawv, tuple) and len(rawv) == 3 \
                and rawv[0] == "p"
            if pend:
                prod, slot = rawv[1], rawv[2]
                pi = node_index.get(id(prod))
                if pi is None:
                    # producer outside this tape slice — force it and
                    # feed the concrete value as a leaf
                    prod.force()
                    rawv = prod.out_values[slot]
                    pend = False
                else:
                    ins.append(("n", pi, slot))
                    continue
            if (not inp._ag_var) and ssa is not None \
                    and id(ssa[0]) in node_index:
                ins.append(("n", node_index[id(ssa[0])], ssa[1]))
            else:
                # dedup leaves by (object, captured value): the value
                # part separates an array mutated in place between two
                # recorded uses (two SSA values), the object part
                # separates a grad variable from its detach() copy
                # (same buffer, different differentiation identity)
                key = (id(inp), id(rawv))
                slot = leaf_slots.get(key)
                if slot is None:
                    slot = len(leaf_arrays)
                    leaf_slots[key] = slot
                    leaf_arrays.append(inp)
                    leaf_vals.append(rawv)
                ins.append(("l", slot))
        node_specs.append((n.fused_key, 1 if n.n_rng else 0, tuple(ins),
                           len(n.out_avals)))
        if n.n_rng:
            rng_vals.append(n.rng_key)
        aux_slots += [ins[j][1] for j in n.aux_in if ins[j][0] == "l"]

    head_specs = []
    for h in heads:
        ni = node_index.get(id(h._ag_node))
        if ni is None:
            return False
        head_specs.append((ni, h._ag_out_idx))
    hg_present = tuple(hg is not None for hg in head_grads)
    hg_vals = [hg._jax() for hg in head_grads if hg is not None]

    grad_slots = tuple(
        s for s, arr in enumerate(leaf_arrays)
        if arr._ag_var and jnp.issubdtype(jnp.result_type(leaf_vals[s]),
                                          jnp.inexact))
    skey = (tuple(node_specs), tuple(head_specs), grad_slots,
            len(leaf_arrays), hg_present)
    plan = _PendingStep(skey, tuple(node_specs), tuple(head_specs),
                        grad_slots, hg_present, leaf_arrays, leaf_vals,
                        rng_vals, hg_vals, list(order), tuple(aux_slots))
    if _ARM_TOKEN[0] is not None and _ARM_LEAF_IDS[0] and \
            _ARM_LEAF_IDS[0] <= {id(leaf_arrays[s]) for s in grad_slots}:
        # this tape IS the armed Trainer's loop (its parameters are the
        # grad leaves) — defer; step() runs fwd+bwd+update as one
        # program (MXNET_TRAINER_FUSED_UPDATE)
        plan.token = _ARM_TOKEN[0]
        _PENDING[0] = plan
        return True
    plan.execute()
    return True


def backward(heads, head_grads=None, retain_graph=False, train_mode=True):
    """Run reverse-mode from ``heads`` to every reachable variable's .grad."""
    # the step's backward on the host: the tape walk and either the
    # fused plan's launch, its stash for an armed Trainer, or the
    # classic per-node walk
    with telemetry.phase("backward"):
        _backward(heads, head_grads, retain_graph, train_mode)


def _backward(heads, head_grads, retain_graph, train_mode):
    from .ndarray.ndarray import NDArray

    # a plan stashed by a previous armed backward that was never
    # consumed (loop broke before step()) must run before new cotangents
    # are introduced — grads would otherwise silently stay stale
    flush_pending_step()

    heads = [heads] if isinstance(heads, NDArray) else list(heads)
    if head_grads is None:
        head_grads = [None] * len(heads)
    else:
        head_grads = [head_grads] if isinstance(head_grads, NDArray) else list(head_grads)

    # Cotangent accumulation is keyed by SSA value — (node, out_idx) for
    # op outputs, array identity for leaf variables. Keying node outputs
    # (not Python objects) keeps gradients correct when a mutation
    # rebinds an NDArray to a new node (recorded slice-assign, +=):
    # the pre-mutation snapshot and the live object then name different
    # SSA values even though one Python object was mutated.
    cot_node = {}   # (id(node), out_idx) -> cotangent
    cot_leaf = {}   # id(arr) -> (arr, cotangent)

    def _acc(arr, value):
        if arr._ag_var:
            key = id(arr)
            if key in cot_leaf:
                cot_leaf[key] = (arr, cot_leaf[key][1] + value)
            else:
                cot_leaf[key] = (arr, value)
        elif arr._ag_node is not None:
            key = (id(arr._ag_node), arr._ag_out_idx)
            prev = cot_node.get(key)
            cot_node[key] = value if prev is None else prev + value

    for h in heads:
        if h._ag_node is None and not h._ag_var:
            raise MXNetError(
                "cannot differentiate: output was not computed under "
                "autograd.record() from any array with attach_grad()")

    # topo order over RECORD-TIME producers (input_ssa), deps first —
    # computed once, shared by the fused attempt and the classic walk
    roots = []
    seen_roots = set()
    for h in heads:
        if h._ag_node is not None and id(h._ag_node) not in seen_roots:
            seen_roots.add(id(h._ag_node))
            roots.append(h._ag_node)
    order = _topo_nodes(roots)

    # one-program fused path (tape bulking): everything below becomes a
    # single cached XLA program when the tape allows it
    if order and not retain_graph and not is_recording() \
            and _try_fused_backward(heads, head_grads, order):
        return

    for h, hg in zip(heads, head_grads):
        g = hg._jax() if hg is not None else jnp.ones(h.shape, h.dtype)
        _acc(h, g)

    # reverse order = outputs before inputs
    for node in reversed(order):
        # gather output cotangents (zeros where nothing flowed). Zero
        # cotangents are immutable constants — cache them per
        # (shape, dtype) so a CachedOp node with many aux outputs
        # (ResNet-50: 106 BN moving stats) costs 0 dispatches instead of
        # one eager zeros-program per output per step.
        out_cots = []
        have_any = False
        n_visible = len(node.out_avals) - node.n_extra
        for i, aval in enumerate(node.out_avals):
            g = cot_node.get((id(node), i)) if i < n_visible else None
            if g is None:
                zkey = (aval.shape, str(aval.dtype))
                g = _ZERO_COTS.get(zkey)
                if g is None:
                    g = jnp.zeros(aval.shape, aval.dtype)
                    # cache only small constants (aux-stat sized): big
                    # activation zeros would pin HBM for process life
                    if int(np.prod(aval.shape) if aval.shape else 1) \
                            <= (1 << 16):
                        _ZERO_COTS[zkey] = g
            else:
                have_any = True
            out_cots.append(g)
        if not have_any:
            continue
        node.force()   # deferred node reached via the classic walk
        if len(node.out_avals) == 1:
            in_cots = node.vjp_fn(out_cots[0])
        else:
            in_cots = node.vjp_fn(tuple(out_cots))
        # first n_rng cotangents belong to the PRNG key — drop them
        in_cots = in_cots[node.n_rng:]
        for inp, ssa, g in zip(node.inputs, node.input_ssa, in_cots):
            if g is None or (hasattr(g, "dtype") and g.dtype == jax.dtypes.float0):
                continue
            if inp._ag_var:
                # live leaf claim wins (grad() marks intermediates)
                _acc(inp, g)
            elif ssa is not None:
                # route to the RECORD-TIME producer: a later mutation
                # rebinds inp._ag_node, and chasing the live pointer
                # would credit the mutation node for pre-mutation uses
                key = (id(ssa[0]), ssa[1])
                prev = cot_node.get(key)
                cot_node[key] = g if prev is None else prev + g
        if not retain_graph:
            node.vjp_fn = None

    # write/add into .grad on variables
    from .ndarray.sparse import RowSparseNDArray, _SparseCot
    for _, (arr, g) in cot_leaf.items():
        if not (arr._ag_var and arr._grad is not None):
            continue
        tgt = arr._grad
        if isinstance(g, _SparseCot):
            if isinstance(tgt, RowSparseNDArray):
                if arr._grad_req == "write":
                    tgt._coo_write(g)
                elif arr._grad_req == "add":
                    tgt._coo_add(g)
                continue
            g = g.dense()
        if arr._grad_req == "write":
            tgt._set_jax(g.astype(tgt.dtype))
        elif arr._grad_req == "add":
            tgt._set_jax(tgt._jax() + g.astype(tgt.dtype))
    return


def _topo_nodes(roots, skip_var_objects=None):
    """Deps-first topo order over tape nodes, following RECORD-TIME
    producers (node.input_ssa). Traversal stops at inputs that are live
    leaf variables or members of skip_var_objects (id set)."""
    skip = skip_var_objects or frozenset()
    order, seen = [], set()

    def children(n):
        return [ssa[0] for inp, ssa in zip(n.inputs, n.input_ssa)
                if ssa is not None and not inp._ag_var
                and id(inp) not in skip]

    for root in roots:
        if id(root) in seen:
            continue
        st = [(root, iter(children(root)))]
        seen.add(id(root))
        while st:
            n, it = st[-1]
            adv = False
            for child in it:
                if id(child) not in seen:
                    seen.add(id(child))
                    st.append((child, iter(children(child))))
                    adv = True
                    break
            if not adv:
                order.append(n)
                st.pop()
    return order


def _build_replay(heads, variables):
    """Rebuild the recorded subgraph as a PURE function of the given
    variables (everything else is a captured constant). The tape stores
    each node's attr-bound forward impl (fwd_fn) and its PRNG key, so
    the replay is deterministic and jax-transformable — which is what
    makes create_graph higher-order differentiation exact (SURVEY §3.2
    'supports create_graph').
    """
    var_ids = {id(v): i for i, v in enumerate(variables)}

    roots = [h._ag_node for h in heads if h._ag_node is not None]
    order = _topo_nodes(roots, skip_var_objects=frozenset(var_ids))
    for n in order:
        if n.fwd_fn is None:
            raise MXNetError(
                "create_graph=True: node %r has no replayable forward "
                "(custom autograd.Function nodes are first-order only)"
                % n.op_name)

    def replay(*var_vals):
        produced = {}   # id(node) -> tuple of raw outputs

        def value_of(arr, ssa):
            i = var_ids.get(id(arr))
            if i is not None:
                return var_vals[i]
            if ssa is not None and id(ssa[0]) in produced:
                return produced[id(ssa[0])][ssa[1]]
            return jax.lax.stop_gradient(arr._jax())

        for node in order:
            args = [value_of(a, s)
                    for a, s in zip(node.inputs, node.input_ssa)]
            if node.n_rng:
                args = [node.rng_key] + args
            out = node.fwd_fn(*args)
            produced[id(node)] = tuple(out) if isinstance(
                out, (tuple, list)) else (out,)

        outs = []
        for h in heads:
            if h._ag_node is not None:
                outs.append(produced[id(h._ag_node)][h._ag_out_idx])
            else:
                outs.append(value_of(h, None))
        return tuple(outs)

    return replay


def grad(heads, variables, head_grads=None, retain_graph=None,
         create_graph=False, train_mode=True):
    """Ref: autograd.grad — return grads instead of writing .grad.
    With create_graph=True the returned grads are themselves recorded
    on the tape, so they can be differentiated again (vjp-of-vjp)."""
    from .ndarray.ndarray import NDArray
    if create_graph:
        heads_l = [heads] if isinstance(heads, NDArray) else list(heads)
        vars_l = [variables] if isinstance(variables, NDArray) \
            else list(variables)
        if head_grads is None:
            hg_l = []
        else:
            hg_l = [head_grads] if isinstance(head_grads, NDArray) \
                else list(head_grads)
            if any(g is None for g in hg_l):
                # per-head None means ones (backward() semantics)
                from . import ndarray as _nd
                hg_l = [_nd.ones(h.shape, ctx=h.ctx, dtype=h.dtype)
                        if g is None else g
                        for g, h in zip(hg_l, heads_l)]
        replay = _build_replay(heads_l, vars_l)
        nvars = len(vars_l)

        def grad_fn(*args):
            var_vals = args[:nvars]
            hg_vals = args[nvars:]
            outs, vjp = jax.vjp(replay, *var_vals)
            if hg_vals:
                cots = tuple(hg_vals)
            else:
                cots = tuple(jnp.ones(o.shape, o.dtype) for o in outs)
            return vjp(cots)

        raw = [v._jax() for v in vars_l] + [g._jax() for g in hg_l]
        if is_recording():
            out_raw, vjp_fn = jax.vjp(grad_fn, *raw)
            out_arrays = [NDArray(b, vars_l[0]._ctx) for b in out_raw]

            class _GradOp:
                name = "_higher_order_grad"

            if len(out_raw) == 1:
                # the tape passes a bare cotangent for 1-output nodes;
                # jax.vjp wants the output pytree (a 1-tuple)
                node_vjp = lambda c, _f=vjp_fn: _f((c,))
            else:
                node_vjp = vjp_fn
            _record_node(_GradOp, vars_l + hg_l, out_arrays, node_vjp,
                         [jax.ShapeDtypeStruct(b.shape, b.dtype)
                          for b in out_raw],
                         fwd_fn=grad_fn)
        else:
            out_raw = grad_fn(*raw)
            out_arrays = [NDArray(b, vars_l[0]._ctx) for b in out_raw]
        return out_arrays
    variables = [variables] if isinstance(variables, NDArray) else list(variables)
    saved = [(v._grad, v._grad_req, v._ag_var) for v in variables]
    for v in variables:
        v.attach_grad()
    try:
        backward(heads, head_grads,
                 retain_graph=bool(retain_graph) if retain_graph is not None else False,
                 train_mode=train_mode)
        outs = [v.grad for v in variables]
    finally:
        for v, (g, req, var) in zip(variables, saved):
            v._grad, v._grad_req, v._ag_var = g, req, var
    return outs


_COP_SYMS: Dict = {}     # CachedOp uid -> (Symbol, input_names)


def _subst_symbol(sym, mapping):
    """Re-instantiate a Symbol graph with its variables replaced by the
    Symbols in `mapping` (name -> Symbol). Returns a dict
    (id(old node), out_idx) -> (new node, out_idx)."""
    from . import symbol as sym_mod
    order = sym._topo()
    ent: Dict = {}
    for node in order:
        if node.is_variable:
            rep = mapping.get(node.name)
            ent[(id(node), 0)] = rep._entries[0] if rep is not None \
                else (node, 0)
            continue
        ins = []
        for s in node.inputs:
            src, idx = s._entries[0]
            ins.append(sym_mod.Symbol([ent[(id(src), idx)]]))
        new = sym_mod._create(node.op.name, ins, dict(node.attrs))
        nn = new._entries[0][0]
        for i in range(node.num_outputs):
            ent[(id(node), i)] = (nn, i)
    return ent


def get_symbol(x):
    """Reconstruct the Symbol graph that produced `x` on the autograd
    tape (ref: autograd.py :: get_symbol / MXAutogradGetSymbol). Eager
    ops rebuild from their recorded (op, attrs); hybridized CachedOp
    segments splice in their traced Symbol subgraph."""
    from .ndarray.ndarray import NDArray
    from . import symbol as sym_mod
    if not isinstance(x, NDArray):
        raise TypeError("get_symbol expects an NDArray")
    if x._ag_node is None:
        if x._ag_var:
            return sym_mod.var("var0")
        raise MXNetError(
            "get_symbol: array was not computed under autograd.record()")

    order = _topo_nodes([x._ag_node])
    node_out: Dict = {}      # (id(node), out_idx) -> Symbol
    var_names: Dict[int, str] = {}

    def leaf_sym(arr):
        name = var_names.get(id(arr))
        if name is None:
            name = "var%d" % len(var_names)
            var_names[id(arr)] = name
        return sym_mod.var(name)

    for node in order:
        in_syms = []
        for inp, ssa in zip(node.inputs, node.input_ssa):
            if (not inp._ag_var) and ssa is not None \
                    and (id(ssa[0]), ssa[1]) in node_out:
                in_syms.append(node_out[(id(ssa[0]), ssa[1])])
            else:
                in_syms.append(leaf_sym(inp))
        fk = node.fused_key
        if fk is not None and fk[0] == "op":
            out = sym_mod._create(fk[1], in_syms, dict(fk[2]))
            new_node = out._entries[0][0]
            for i in range(len(node.out_avals) - node.n_extra):
                node_out[(id(node), i)] = sym_mod.Symbol([(new_node, i)])
        elif fk is not None and fk[0] == "cop" and fk[1] in _COP_SYMS:
            sub_sym, input_names = _COP_SYMS[fk[1]]
            mapping = dict(zip(input_names, in_syms))
            ent = _subst_symbol(sub_sym, mapping)
            for i, (n, idx) in enumerate(sub_sym._entries):
                node_out[(id(node), i)] = sym_mod.Symbol(
                    [ent[(id(n), idx)]])
        else:
            raise MXNetError(
                "get_symbol: node %r is not symbolically replayable"
                % node.op_name)
    key = (id(x._ag_node), x._ag_out_idx)
    if key not in node_out:
        raise MXNetError("get_symbol: output entry not reconstructed")
    return node_out[key]


# ---------------------------------------------------------------------------
# custom Function (ref: autograd.py :: class Function)
# ---------------------------------------------------------------------------
class Function:
    """User-defined differentiable function with explicit backward.

    Subclass and implement forward(self, *inputs) / backward(self, *out_grads),
    call save_for_backward or stash state on self, then use via __call__.
    """

    def __init__(self):
        self._saved = None

    def save_for_backward(self, *arrays):
        self._saved = arrays

    @property
    def saved_tensors(self):
        return self._saved

    def forward(self, *inputs):
        raise NotImplementedError

    def backward(self, *out_grads):
        raise NotImplementedError

    def __call__(self, *inputs):
        from .ndarray.ndarray import NDArray
        with pause():
            outputs = self.forward(*inputs)
        single = isinstance(outputs, NDArray)
        outs = [outputs] if single else list(outputs)
        if is_recording() and any(i._in_graph for i in inputs
                                  if isinstance(i, NDArray)):
            func = self

            def vjp_fn(cotangents):
                cots = cotangents if isinstance(cotangents, tuple) else (cotangents,)
                with pause():
                    in_grads = func.backward(
                        *[NDArray(c, inputs[0]._ctx) for c in cots])
                if isinstance(in_grads, NDArray):
                    in_grads = (in_grads,)
                return tuple(g._jax() if g is not None else None for g in in_grads)

            avals = [jax.ShapeDtypeStruct(o.shape, o.dtype) for o in outs]

            class _FnOp:  # minimal op-like shim for _record_node
                name = type(self).__name__

            _record_node(_FnOp, [i for i in inputs if isinstance(i, NDArray)],
                         outs, vjp_fn, avals)
        return outputs
