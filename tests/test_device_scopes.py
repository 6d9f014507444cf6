"""Device-side scopes of a compiled training step: the scopes
``ShardedTrainStep`` opens itself (``mx.optimizer``, ``mx.params.cast``)
in each of its five step functions, the table a step publishes
(``device_scopes()``, ``telemetry.device_scope_tables()``), the rule
that builds it (the innermost ``mx.*`` element of ``op_name``), and a
step served from a persistent compile cache written without the scopes.
The scopes of the head, the embedding and the decoder toys are checked
beside the benchmark's readers
(``tests/mxbench_tests/test_mxbench_program_scopes.py``)."""
import gc
import json
import os
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import gluon, nd, telemetry
from mxnet_tpu.gluon import nn
from mxnet_tpu.ops import get_op
from mxnet_tpu.parallel import MeshConfig, P, ShardedTrainStep, make_mesh

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _step(**kw):
    net = nn.HybridSequential()
    net.add(nn.Dense(16, activation="relu", in_units=8),
            nn.Dense(4, in_units=16))
    net.initialize(init=mx.initializer.Xavier())
    mesh = make_mesh(MeshConfig(dp=1), devices=jax.devices()[:1])
    return ShardedTrainStep(net, gluon.loss.L2Loss(), mesh, lr=1e-2,
                            dtype="bfloat16", data_specs=[P(), P()], **kw)


def _batch(rows=4):
    rng = np.random.default_rng(0)
    return (nd.array(rng.standard_normal((rows, 8)).astype(np.float32)),
            nd.array(rng.standard_normal((rows, 4)).astype(np.float32)))


# ---------------------------------------------------------------------------
# the rule
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("op_name, want", [
    ("jit(fused_step_mx1)/mx.optimizer/mul", "mx.optimizer"),
    ("jit(fused_step_mx1)/jit(main)/mx.mamba2/checkpoint/mx.mamba2.ssd/"
     "dot_general", "mx.mamba2.ssd"),
    ("jit(f)/transpose(jvp(mx.attn.rotary))/rematted_computation/"
     "mx.attn.window/exp", "mx.attn.window"),
    ("jit(f)/transpose(jvp(mx.embed))/jit(_take)/scatter-add", "mx.embed"),
    ("jit(f)/jvp(mx.head.ce)/while/body/dot_general", "mx.head.ce"),
    ("jit(f)/jvp(chunked_lm_head_ce)/while/body/dot_general", None),
    ("jit(f)/mxnet/amx.optimizer/add", None),
    ("", None),
])
def test_innermost_scope(op_name, want):
    assert telemetry.innermost_scope(op_name) == want


HLO = '''\
HloModule jit_fused_step_mx1, is_scheduled=true

%fused_computation.5 (p: bf16[8]) -> bf16[8] {
  %mul.1 = bf16[8]{0} multiply(%p, %p), metadata={op_name="jit(fused_step_mx1)/mx.optimizer/mul"}
}

ENTRY %main (a: bf16[8]) -> bf16[8] {
  %a = bf16[8]{0} parameter(0)
  %fusion.5 = bf16[8]{0} fusion(%a), kind=kLoop, calls=%fused_computation.5, metadata={op_name="jit(fused_step_mx1)/mx.optimizer/mul" source_file="x.py"}
  %convert.2 = bf16[8]{0} convert(%a), metadata={op_name="jit(fused_step_mx1)/jvp(mx.params.cast)/convert_element_type"}
  %while.4 = bf16[8]{0} while(%a), body=%b, metadata={op_name="jit(fused_step_mx1)/transpose(jvp(mx.head.ce))/while"}
  %add.7 = bf16[8]{0} add(%a, %a), metadata={op_name="jit(fused_step_mx1)/jvp(jit(main))/residual/add"}
  ROOT %copy.1 = bf16[8]{0} copy(%a)
}
'''


def test_hlo_scopes_reads_module_scopes_and_the_rest():
    module, scopes, unscoped = telemetry.hlo_scopes(HLO)
    assert module == "jit_fused_step_mx1"
    assert scopes == {"mul.1": "mx.optimizer", "fusion.5": "mx.optimizer",
                      "convert.2": "mx.params.cast",
                      "while.4": "mx.head.ce"}
    # an instruction with an op_name and no scope gets a label; one the
    # compiler made (no metadata) is in neither
    assert unscoped == {"add.7": "residual/add"}


# ---------------------------------------------------------------------------
# the step's own scopes, in all five step functions
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("kw, want", [
    ({"optimizer": "adamw"},
     {"fused_step": {"mx.optimizer", "mx.params.cast"}}),
    ({"optimizer": "lamb", "grad_accum": 2},
     {"micro_step": {"mx.params.cast"},
      "apply_step": {"mx.optimizer", "mx.params.cast"}}),
    ({"optimizer": "sgd", "split_update": True},
     {"grad_step": {"mx.params.cast"}, "update_step": {"mx.optimizer"}}),
], ids=["fused", "accumulate", "split"])
def test_optimizer_and_cast_scopes_in_every_step_function(kw, want):
    step = _step(**kw)
    assert step.device_scopes() == []           # nothing has launched
    for _ in range(2):
        step.step(*_batch())
    tables = step.device_scopes()
    assert {t["program"]: set(t["scopes"].values()) for t in tables} == want
    launches = [t["launched"] for t in tables]
    assert launches == sorted(launches, reverse=True)
    for t in tables:
        assert t["module"] == "jit_%s_mx1" % t["program"]
        assert t["stale"] is False
        assert not set(t["scopes"]) & set(t["unscoped"])
    # built once and kept: the second request parses nothing
    again = step.device_scopes()
    assert [t["scopes"] is u["scopes"] for t, u in zip(tables, again)] \
        == [True] * len(tables)


def test_optimizer_scope_holds_the_gradient_cast_and_every_parameter():
    """Every instruction the update lowers to stands under the scope:
    the lowered text's locations name no update op outside it."""
    step = _step(optimizer="adamw")
    x, y = _batch()
    text = step._fused.lower(
        step.params, step.aux, step.states, step._t_dev, step._rng_dev,
        x._jax(), y._jax()).as_text(debug_info=True)
    locs = [l for l in text.splitlines() if l.startswith("#loc")]
    update = [l for l in locs if "sqrt" in l or "rsqrt" in l]
    assert update and all("mx.optimizer" in l for l in update)


def test_a_new_data_shape_is_a_program_of_its_own():
    step = _step(optimizer="sgd")
    step.step(*_batch(4))
    step.step(*_batch(2))
    tables = step.device_scopes()
    assert [t["program"] for t in tables] == ["fused_step", "fused_step"]
    assert tables[0]["launched"] >= tables[1]["launched"]


# ---------------------------------------------------------------------------
# the process-wide lookup
# ---------------------------------------------------------------------------
def test_lookup_finds_the_step_that_launched_last_without_a_handle():
    telemetry.reset()
    first, second = _step(optimizer="sgd"), _step(optimizer="adamw")
    first.step(*_batch())
    second.step(*_batch())
    entries = telemetry.device_scope_tables()
    assert [e.label for e in entries[:2]] == ["fused_step", "fused_step"]
    assert entries[0].launched >= entries[1].launched
    assert entries[0].table()["scopes"] \
        == second.device_scopes()[0]["scopes"]
    first.step(*_batch())
    assert telemetry.device_scope_tables()[0].table()["scopes"] \
        == first.device_scopes()[0]["scopes"]


def test_lookup_holds_every_program_weakly_outside_a_trace():
    telemetry.reset()
    gc.collect()
    before = len(telemetry.device_scope_tables())
    older, newer = _step(optimizer="sgd"), _step(optimizer="sgd")
    older.step(*_batch())
    newer.step(*_batch())
    assert len(telemetry.device_scope_tables()) == before + 2
    del older
    gc.collect()
    assert len(telemetry.device_scope_tables()) == before + 1
    del newer
    gc.collect()
    # no trace was recording: nothing outlives its step
    assert len(telemetry.device_scope_tables()) == before
    assert telemetry._TRACED[0] is None


def test_a_traced_program_gives_its_table_after_its_step_is_gone(tmp_path):
    """What the benchmark's readers meet: the trace has stopped, the
    loop has returned, the step is collected, and the table of what the
    trace recorded is still to be had. The pin holds the executable
    only until the table is built, and goes with ``reset()``."""
    telemetry.reset()
    gc.collect()
    before = len(telemetry.device_scope_tables())
    step = _step(optimizer="adamw")
    step.step(*_batch())
    assert telemetry._TRACED[0] is None
    jax.profiler.start_trace(str(tmp_path))
    try:
        step.step(*_batch())
    finally:
        jax.profiler.stop_trace()
    del step
    gc.collect()
    (entry,) = telemetry.device_scope_tables()[:1]
    assert len(telemetry.device_scope_tables()) == before + 1
    assert entry._stages is not None
    table = entry.table()
    assert entry._stages is None
    assert set(table["scopes"].values()) == {"mx.optimizer",
                                             "mx.params.cast"}
    assert table["module"] == "jit_fused_step_mx1"
    assert table["missing"] == [] and table["stale"] is False
    del entry
    telemetry.reset()
    gc.collect()
    assert len(telemetry.device_scope_tables()) == before


def test_the_next_launch_outside_a_trace_lets_the_pin_go(tmp_path):
    telemetry.reset()
    traced, later = _step(optimizer="sgd"), _step(optimizer="sgd")
    jax.profiler.start_trace(str(tmp_path))
    try:
        traced.step(*_batch())
    finally:
        jax.profiler.stop_trace()
    assert telemetry._TRACED[0] is traced._programs[
        "fused_step", next(iter(traced._programs))[1]][1]
    later.step(*_batch())
    assert telemetry._TRACED[0] is None


def test_the_table_is_read_from_the_executable_that_launches():
    """One path: every step function is lowered and compiled once
    through the AOT stages, and a table costs no second compile."""
    step = _step(optimizer="lamb", grad_accum=2)
    compiles = []

    def heard(name, secs, **kw):
        if name.endswith("backend_compile_duration"):
            compiles.append(name)
    jax.monitoring.register_event_duration_secs_listener(heard)
    try:
        for _ in range(4):
            step.step(*_batch())
        ran = len(compiles)
        tables = step.device_scopes()
    finally:
        from jax._src import monitoring
        monitoring.unregister_event_duration_listener(heard)
    assert ran == 2 and len(compiles) == 2      # micro_step, apply_step
    assert sorted(t["program"] for t in tables) == ["apply_step",
                                                    "micro_step"]
    for label, key in step._programs:
        compiled, program = step._programs[label, key]
        assert program.table()["module"] in compiled.as_text()[:200]


def test_a_loop_over_one_program_stamps_its_launch_once():
    telemetry.reset()
    step = _step(optimizer="sgd")
    step.step(*_batch())
    (_, program), = step._programs.values()
    first = program.launched
    step.step(*_batch())
    assert program.launched == first
    other = _step(optimizer="sgd")
    other.step(*_batch())
    step.step(*_batch())
    assert program.launched > first
    assert telemetry.device_scope_tables()[0] is program


# ---------------------------------------------------------------------------
# a step served from a compile cache written without the scopes
# ---------------------------------------------------------------------------
_CACHE_SCRIPT = textwrap.dedent('''
    import contextlib, json, os, sys
    os.environ["JAX_PLATFORMS"] = "cpu"
    sys.path.insert(0, %r)
    import jax
    jax.config.update("jax_compilation_cache_dir", sys.argv[1])
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    scopes, token = sys.argv[2] == "scopes", sys.argv[3]
    if not scopes:
        jax.named_scope = lambda name: contextlib.nullcontext()
    import numpy as np
    import mxnet_tpu as mx
    from mxnet_tpu import gluon, nd
    from mxnet_tpu.gluon import nn
    from mxnet_tpu.parallel import (MeshConfig, P, ShardedTrainStep,
                                    make_mesh, sharded)
    sharded._SCOPE_SCHEMA = token
    mx.random.seed(0)
    net = nn.Dense(4, in_units=8)
    net.initialize(init=mx.initializer.Xavier())
    mesh = make_mesh(MeshConfig(dp=1), devices=jax.devices()[:1])
    step = ShardedTrainStep(net, gluon.loss.L2Loss(), mesh,
                            optimizer="adamw", dtype="bfloat16",
                            data_specs=[P(), P()])
    step.step(nd.array(np.ones((2, 8), np.float32)),
              nd.array(np.ones((2, 4), np.float32)))
    (table,) = step.device_scopes()
    print(json.dumps({"module": table["module"], "stale": table["stale"],
                      "scopes": sorted(set(table["scopes"].values()))}))
''') % ROOT


def _cached_step(cache, scopes, token):
    out = subprocess.run([sys.executable, "-c", _CACHE_SCRIPT, str(cache),
                          scopes, token], capture_output=True, text=True,
                         timeout=300)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_a_cache_written_without_the_scopes_does_not_hide_them(tmp_path):
    """An older tree (no scopes, its own module name) fills the cache;
    this tree's step has another name, misses, and reports its scopes."""
    old = _cached_step(tmp_path, "bare", "old")
    assert old["scopes"] == [] and old["stale"] is False
    new = _cached_step(tmp_path, "scopes", "mx1")
    assert new["module"] == "jit_fused_step_mx1" and new["stale"] is False
    assert new["scopes"] == ["mx.optimizer", "mx.params.cast"]


def test_a_served_executable_that_lacks_the_scopes_calls_itself_stale(
        tmp_path):
    """The fault the schema token is there for, with the token left
    alone: JAX's cache key leaves the scopes out, the cached executable
    is served, and its text carries the scopes of whoever compiled it.
    The table says so."""
    _cached_step(tmp_path, "bare", "mx1")
    served = _cached_step(tmp_path, "scopes", "mx1")
    assert served["scopes"] == [] and served["stale"] is True


class _Text:
    def __init__(self, text):
        self._text = text

    def as_text(self, **kw):
        return self._text


@pytest.mark.parametrize("compiled_scopes, missing, stale", [
    (["mx.optimizer", "mx.embed"], [], False),
    (["mx.optimizer"], ["mx.embed"], False),
    ([], ["mx.embed", "mx.optimizer"], True),
])
def test_a_scope_the_executable_lacks_is_listed(compiled_scopes, missing,
                                                stale):
    """A cache entry written with some of the scopes and not with a
    newer one reads as fresh but names the one it lacks."""
    lowered = _Text('#loc1 = loc("jit(f)/mx.optimizer/mul")\n'
                    '#loc2 = loc("jit(f)/jvp(mx.embed)/gather")\n')
    compiled = _Text("HloModule jit_f_mx1\n" + "".join(
        '  %%op.%d = f32[] add(), metadata={op_name="jit(f)/%s/add"}\n'
        % (i, scope) for i, scope in enumerate(compiled_scopes)))
    table = telemetry.DeviceProgram("f", lowered, compiled).table()
    assert table["missing"] == missing and table["stale"] is stale
    assert sorted(set(table["scopes"].values())) == sorted(compiled_scopes)


# ---------------------------------------------------------------------------
# the head keeps its name; only its scope is renamed
# ---------------------------------------------------------------------------
def test_the_head_op_keeps_its_name_and_its_autotune_key(monkeypatch):
    from mxnet_tpu import autotune
    from mxnet_tpu.ops import contrib_ops
    assert get_op("_contrib_chunked_lm_head_ce").impl \
        is contrib_ops.chunked_lm_head_ce
    assert contrib_ops.HEAD_SCOPE == "mx.head.ce"
    asked = []
    monkeypatch.setattr(
        autotune, "lookup",
        lambda op, key, default, **kw: asked.append(op) or default)
    contrib_ops._tuned_ce_chunk(8, 4, 16, 2, 8)
    assert asked == ["chunked_lm_head_ce"]


def test_no_scope_outside_mx_and_no_list_of_scopes_in_the_package():
    """Every ``jax.named_scope`` the package opens is named ``mx.*``
    (a literal, or a module constant that is one)."""
    import re
    opened = re.compile(r"jax\.named_scope\(([^)]*)\)")
    literal = re.compile(r'^"(mx\.[\w.]+)"$')
    for base, _, files in os.walk(os.path.join(ROOT, "mxnet_tpu")):
        for name in files:
            if not name.endswith(".py"):
                continue
            with open(os.path.join(base, name)) as f:
                text = f.read()
            for arg in opened.findall(text):
                for part in re.split(r"\s+if\s+.*?\s+else\s+", arg.strip()):
                    if part.startswith('"'):
                        assert literal.match(part), (name, arg)
                    else:       # a constant: defined as an mx.* literal
                        const = part.split(".")[-1]
                        assert re.search(
                            r'^%s = "mx\.[\w.]+"$' % const,
                            _source_of(part, text), re.M), (name, arg)


def _source_of(part, text):
    """The text of the module a constant such as
    ``pallas_sparse_gqa.SCOPE`` or ``SCOPE`` is defined in."""
    if "." not in part:
        return text
    with open(os.path.join(ROOT, "mxnet_tpu", "ops",
                           part.split(".")[0] + ".py")) as f:
        return f.read()
