"""The five readers that ask the program which instruction belongs to
which scope (``layer_metrics/_program_scopes.py``): on hand-made
intervals and on the trace recorded on a v5e, with a hand-made table in
the program's place. And, at toy widths for the four configurations
that train through ``ShardedTrainStep``: every new scope stands on
instructions of the forward and of the backward; the scopes are names
only (the lowered step without debug info is the same text with
``jax.named_scope`` patched away); the program's table agrees with
``mxbench/scopes.py`` on every scope a configuration lists."""
import contextlib
import os
import re
import types

import jax
import numpy as np
import pytest

from mxbench import manifest, run as mxrun, scopes, trace as T

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
TINY = os.path.join(ROOT, "tests", "mxbench_tests", "data",
                    "tiny_v5e.xplane.pb")
CELL = "bert_base_pretrain_s512"
READERS = {"optimizer_ms.train": "mx.optimizer",
           "param_cast_ms.train": "mx.params.cast",
           "lm_head_ms.train": "mx.head.ce",
           "embed_ms.train": "mx.embed"}
REST = "unscoped_ms.train"
CELLS = {"bert_base": CELL,
         "nemotron_twotower_30b_a3b":
             "nemotron_twotower_30b_a3b_pretrain_s8192",
         "keye_vl2_30b_a3b": "keye_vl2_30b_a3b_midtrain_s8192",
         "mellum2_12b_a2_5b": "mellum2_12b_a2_5b_longctx_s16384"}


# ---------------------------------------------------------------------------
# the readers, with a hand-made table in the program's place
# ---------------------------------------------------------------------------
def _program(monkeypatch, tables):
    """Put ``tables`` (most recent launch first) where the readers ask
    the program for them."""
    from mxnet_tpu import telemetry
    entries = [types.SimpleNamespace(table=lambda t=t: t) for t in tables]
    monkeypatch.setattr(telemetry, "device_scope_tables", lambda: entries,
                        raising=False)


def _table(scope_of, module="jit_fused_step_mx1", stale=False):
    return {"program": "fused_step", "module": module, "launched": 1.0,
            "scopes": scope_of, "unscoped": {"copy.1": "residual/add"},
            "stale": stale}


def _run(trace):
    from mxbench.record import Run
    run = types.SimpleNamespace(trace=trace,
                                trace_window=T.window_of(trace))
    run.traced_steps = Run.traced_steps.fget(run)
    return run


def _hand_made():
    def ev(line, start, end):
        return T.Op(line, start, end)
    ops = [ev("%fusion.5 = f32[8]{0} fusion(%a), kind=kLoop", 0, 40),
           ev("%convert.2 = bf16[8]{0} convert(%a)", 40, 50),
           # a container beside what it runs: not counted itself
           ev("%while.4 = bf16[8]{0} while(%a), body=%b", 50, 80),
           ev("%fusion.9 = bf16[8]{0} fusion(%a), kind=kOutput", 55, 70),
           # made by the compiler inside the loop, no op_name of the
           # program's: its container's scope
           ev("%copy-start.1 = bf16[8]{0} copy-start(%a)", 70, 75),
           ev("%copy.1 = bf16[8]{0} copy(%a)", 80, 90),
           ev("%scatter.3 = f32[8]{0} scatter(%a, %i, %u)", 90, 96),
           ev("%fusion.5 = f32[8]{0} fusion(%a), kind=kLoop", 100, 140)]
    spans = [T.Op("mxbench/step", 0, 60), T.Op("mxbench/step", 60, 120)]
    modules = [T.Op("jit_fused_step_mx1(77)", 0, 140)]
    return T.Trace({0: T.Device(ops, [], modules)}, spans)


SCOPE_OF = {"fusion.5": "mx.optimizer", "convert.2": "mx.params.cast",
            "while.4": "mx.head.ce", "fusion.9": "mx.head.ce",
            "scatter.3": "mx.embed"}


def _read(name, run):
    return manifest.layer_metric(name).read(run)


def test_readers_on_hand_made_intervals(monkeypatch, capsys):
    _program(monkeypatch, [_table(SCOPE_OF)])
    run = _run(_hand_made())
    assert run.trace_window == (0, 120) and run.traced_steps == 2
    want = {"optimizer_ms.train": (40 + 20) / 2,     # the second cut at 120
            "param_cast_ms.train": 10 / 2,
            "lm_head_ms.train": (15 + 5) / 2,        # the loop's two, not it
            "embed_ms.train": 6 / 2}
    got = {name: _read(name, run) for name in READERS}
    assert got == pytest.approx({k: v * 1e-6 for k, v in want.items()})
    # the residue: copy.1 alone
    assert _read(REST, run) == pytest.approx(10 / 2 * 1e-6)
    # closure: what the five read is every event that runs no other
    leaf = scopes.leaf_seconds(run.trace, 0, run.trace_window)
    assert sum(got.values()) + _read(REST, run) \
        == pytest.approx(leaf * 1e3 / 2)
    # the table was taken once for the five; the free line names the
    # longest instruction under no scope by its label
    said = capsys.readouterr().out
    assert said.count("[mxbench] program fused_step") == 1
    assert '["copy.1", "residual/add", 0.0]' in said.replace("'", '"')


def test_readers_on_the_recorded_trace(monkeypatch):
    """``tiny_v5e.xplane.pb``: three launches of ``jit_f``, two inside
    the window, a ``pallas_layer_norm_fwd`` of 11.5 us in each."""
    trace = T.load(TINY)
    names = {T.op_name(o.name) for o in trace.devices[0].ops}
    kernel = "pallas_layer_norm_fwd.1"
    assert kernel in names
    copies = {n for n in names if n.startswith("copy-")}
    scope_of = {kernel: "mx.head.ce"}
    scope_of.update({n: "mx.params.cast" for n in copies})
    _program(monkeypatch, [_table(scope_of, module="jit_f")])
    run = _run(trace)
    assert run.traced_steps == 3
    head = _read("lm_head_ms.train", run)
    assert head == pytest.approx(2 * 11.49e-6 * 1e3 / 3, rel=2e-2)
    assert head == pytest.approx(
        T.seconds_where(trace, 0, run.trace_window, T.is_pallas) * 1e3 / 3)
    cast = _read("param_cast_ms.train", run)
    assert cast == pytest.approx(T.seconds_where(
        trace, 0, run.trace_window,
        lambda n: T.op_name(n) in copies) * 1e3 / 3)
    # the table names no instruction under the other two scopes
    assert _read("optimizer_ms.train", run) is None
    assert _read("embed_ms.train", run) is None
    leaf = scopes.leaf_seconds(trace, 0, run.trace_window) * 1e3 / 3
    assert head + cast + _read(REST, run) == pytest.approx(leaf)
    assert 0 < _read(REST, run) < leaf


@pytest.mark.parametrize("name", sorted(READERS) + [REST])
def test_readers_report_nothing_without_a_table(name, monkeypatch):
    from mxnet_tpu import telemetry
    # an untraced run
    assert _read(name, types.SimpleNamespace(trace=None)) is None
    # a program without the lookup: a commit before it
    monkeypatch.delattr(telemetry, "device_scope_tables", raising=False)
    assert _read(name, _run(_hand_made())) is None
    # no program has launched
    _program(monkeypatch, [])
    assert _read(name, _run(_hand_made())) is None
    # the executable came from a cache written with other scopes
    _program(monkeypatch, [_table(SCOPE_OF, stale=True)])
    assert _read(name, _run(_hand_made())) is None
    # the table is another program's: the trace's launches do not name it
    _program(monkeypatch, [_table(SCOPE_OF, module="jit_apply_step_mx1")])
    assert _read(name, _run(_hand_made())) is None
    # ... and the one that did run is found behind it
    _program(monkeypatch, [_table({}, module="jit_apply_step_mx1"),
                           _table(SCOPE_OF)])
    assert _read(name, _run(_hand_made())) is not None


def test_the_cell_lists_the_five_beside_the_s128_cells_nine():
    cell = manifest.workload(CELL)
    old = manifest.workload("bert_base_pretrain_s128")
    assert cell["layer_metrics"] == old["layer_metrics"] + [
        "optimizer_ms.train", "param_cast_ms.train", "lm_head_ms.train",
        "embed_ms.train", REST]
    assert cell["metrics"] == old["metrics"] and cell["chips"] == 1
    mix, gen = manifest.traffic(cell["traffic"])
    base, _ = manifest.traffic(old["traffic"])
    assert mix["kind"] == "train_stream" and gen.UNITS == {
        "train_samples_per_s": "samples/s", "train_images_per_s": "img/s",
        "train_routed_samples_per_s": "samples/s", "setup_s": "s"}
    assert mix["seq"] == 512 and base["seq"] == 128
    same = ("loop", "dropout", "optimizer", "feed", "inflight_steps",
            "warmup_steps", "trace_seconds", "toy")
    assert {k: mix[k] for k in same} == {k: base[k] for k in same}
    # the batch is the one ISSUE 38 named: the s128 cell's tokens a step.
    # The compiled step's bytes stand beside it as read on the chip: over
    # a quarter of the chip, and over the s128 file's 15 GB rule, which
    # the file says in so many words instead of repeating the rule
    assert mix["batch_per_chip"] == 64
    assert mix["batch_per_chip"] * mix["seq"] \
        == base["batch_per_chip"] * base["seq"]
    fits = mix["memory_analysis_b64"]
    assert 15e9 < fits["arguments_bytes"] + fits["temporaries_bytes"] < 16e9
    assert "15 GB" in mix["batch_rule"] and "NOT" in mix["batch_rule"]


# ---------------------------------------------------------------------------
# the four configurations' steps at toy widths
# ---------------------------------------------------------------------------
def _toy_step(config):
    """(the step, its abstract batch) of a configuration at the toy
    sizes of its cell's files."""
    import mxnet_tpu as mx
    from mxnet_tpu.parallel import MeshConfig, P, ShardedTrainStep, make_mesh
    # context() switches telemetry on and commwatch off through the
    # environment, for the process: not for the tests that run after
    # these in the same worker
    gates = {k: os.environ.get(k)
             for k in ("MXNET_TELEMETRY", "MXNET_COMMWATCH")}
    try:
        ctx, _, _ = mxrun.context(CELLS[config], seed=5, seconds=0.0,
                                  trace=False, rehearse=True)
    finally:
        for k, v in gates.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
    tr = ctx.traffic
    seq, batch = int(tr["seq"]), int(tr["batch_per_chip"])
    mx.random.seed(5)
    # a block without a prefix is numbered by a process-wide counter,
    # and the parameters' names end in the lowered text's result_info:
    # two builds that are to lower to the same text count from 0 both
    from mxnet_tpu.gluon import block
    counted, block._scope.counters = block._scope.counters, {}
    try:
        net, loss, n_in = ctx.cfgmod.sharded_parts(
            ctx.sizes, float(tr.get("dropout", 0.0)), seq)
    finally:
        block._scope.counters = counted
    mesh = make_mesh(MeshConfig(dp=1), devices=jax.devices()[:1])
    opt = dict(tr["optimizer"])
    step = ShardedTrainStep(
        net, loss, mesh, optimizer=opt.pop("name"),
        dtype=ctx.sizes["compute_dtype"], n_data_inputs=n_in,
        data_specs=[P()] * n_in, seed=5, **opt)
    data = [jax.ShapeDtypeStruct((batch, seq), np.int32)] * n_in
    return step, data, ctx.cfgmod


def _lower(step, data):
    return step._fused.lower(step.params, step.aux, step.states, step._t_dev,
                             step._rng_dev, *data)


@pytest.fixture(scope="module", params=sorted(CELLS))
def toy(request):
    step, data, cfgmod = _toy_step(request.param)
    lowered = _lower(step, data)
    text = lowered.compile().as_text()
    return types.SimpleNamespace(
        config=request.param, cfgmod=cfgmod, lowered=lowered, text=text,
        op_names=re.findall(r'op_name="([^"]*)"', text))


def test_new_scopes_stand_on_forward_and_backward_instructions(toy):
    from mxnet_tpu import telemetry
    _, found, _ = telemetry.hlo_scopes(toy.text)
    assert {"mx.optimizer", "mx.params.cast", "mx.head.ce", "mx.embed"} \
        <= set(found.values())

    def under(scope):
        return [n for n in toy.op_names
                if telemetry.innermost_scope(n) == scope]
    # the head: its forward under the call's scope; its backward under
    # the scope the custom_vjp's rule opens again, with the rule's two
    # products (d hidden, d weight) in its loop
    head = under("mx.head.ce")
    assert any("/jvp(mx.head.ce)/" in n for n in head)
    rule = [n for n in head
            if "transpose(jvp(mx.head.ce))/mx.head.ce/" in n]
    assert sum("dot_general" in n for n in rule) >= 2
    # the embedding: the gather, and the scatter-add into the table's
    # gradient
    embed = under("mx.embed")
    assert any(n.endswith("gather") and "transpose" not in n for n in embed)
    assert any("transpose(jvp(mx.embed))" in n and "scatter" in n
               for n in embed)
    # the masters' compute-dtype copies, and their gradients' way back
    cast = under("mx.params.cast")
    assert any("/jvp(mx.params.cast)/" in n for n in cast)
    # the update: never differentiated
    update = under("mx.optimizer")
    assert update and not any("jvp" in n for n in update)


def test_scopes_are_names_only(toy, monkeypatch):
    """With ``jax.named_scope`` a null context the step lowers to the
    same text, debug info left out: the optimized step is the parent's,
    instruction for instruction."""
    scoped = toy.lowered.as_text()
    assert "mx." not in scoped.replace("_mx1", "")
    assert "mx.optimizer" in toy.lowered.as_text(debug_info=True)
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    step, data, _ = _toy_step(toy.config)
    bare = _lower(step, data)
    assert "mx.optimizer" not in bare.as_text(debug_info=True)
    assert bare.as_text() == scoped


def test_program_table_agrees_with_the_configurations_list(toy):
    """Restricted to the scopes a configuration lists, the program's
    table is ``mxbench/scopes.py``'s map: the twelve accepted metrics
    and the five new ones cannot disagree."""
    from mxnet_tpu import telemetry
    _, found, _ = telemetry.hlo_scopes(toy.text)
    listed = getattr(toy.cfgmod, "SCOPES", None)
    if listed is None:      # bert_base: its encoder opens no scope
        assert set(found.values()) == {"mx.optimizer", "mx.params.cast",
                                       "mx.head.ce", "mx.embed"}
        return
    want = scopes.scope_map(toy.text, listed)
    assert want and set(want.values()) == set(listed)
    assert {k: v for k, v in found.items() if v in listed} == want
    # and an instruction the list puts under a scope is under no other
    # in the table
    assert all(found[k] == v for k, v in want.items())
