"""Static-analysis subsystem tests (ISSUE 9; docs/STATICCHECK.md).

All three levels: Level 1 AST fixtures per rule (positive + negative +
suppression), Level 2 graph checks exercised both directly on jaxprs
and through the compilewatch hook (incl. the 8-device dryrun mesh),
Level 3 race-detector happens-before verification with the
``engine_dep_drop`` fault-injection acceptance, plus the baseline/
fingerprint model, the mxlint ``--gate`` exit-code contract, and the
tier-1 SELF-LINT of ``mxnet_tpu/`` against the checked-in baseline.
"""
import json
import os
import sys
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd, compilewatch, faultinject, nd, staticcheck, telemetry
from mxnet_tpu.base import MXNetError
from mxnet_tpu.staticcheck import ast_rules, findings as fmod, graph_rules
from mxnet_tpu.gluon import nn

pytestmark = pytest.mark.staticcheck

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean(monkeypatch):
    """Both gates off unless a test flips them; findings cleared; the
    hooks re-resolved on the way out so no state leaks to other
    suites."""
    monkeypatch.delenv("MXNET_STATICCHECK", raising=False)
    monkeypatch.delenv("MXNET_ENGINE_RACE_CHECK", raising=False)
    staticcheck.refresh()
    staticcheck.reset()
    compilewatch.reset()
    telemetry.refresh()
    telemetry.reset()
    yield
    faultinject.reset()
    staticcheck.reset()
    compilewatch.reset()
    # monkeypatch restored the env already; re-resolve the cached gates
    staticcheck.refresh()
    telemetry.refresh()
    telemetry.reset()


def _rules(fs):
    return [f.rule for f in fs]


def lint(src):
    return ast_rules.lint_source(src, "fixture.py")


# ===========================================================================
# Level 1 — AST rules (positive / negative / suppression per rule)
# ===========================================================================
class TestHostSyncInTrace:
    def test_asnumpy_in_hybrid_forward(self):
        fs = lint(
            "class B:\n"
            "    def hybrid_forward(self, F, x):\n"
            "        v = x.asnumpy()\n"
            "        return F.sum(x)\n")
        assert _rules(fs) == ["host-sync-in-trace"]
        assert fs[0].line == 3
        assert ".asnumpy()" in fs[0].message

    @pytest.mark.parametrize("expr", ["float(x)", "int(x)",
                                      "np.asarray(x)", "x.item()",
                                      "x.asscalar()", "x.wait_to_read()"])
    def test_sync_forms(self, expr):
        fs = lint(
            "class B:\n"
            "    def hybrid_forward(self, F, x):\n"
            "        v = %s\n"
            "        return x\n" % expr)
        assert "host-sync-in-trace" in _rules(fs)

    def test_sync_in_jitted_function(self):
        fs = lint(
            "import jax\n"
            "def f(x):\n"
            "    return float(x)\n"
            "g = jax.jit(f)\n")
        assert "host-sync-in-trace" in _rules(fs)

    def test_negative_clean_forward(self):
        fs = lint(
            "class B:\n"
            "    def hybrid_forward(self, F, x):\n"
            "        return F.relu(x) + 1\n")
        assert fs == []

    def test_negative_float_on_scalar_attr(self):
        # float() of a non-tensor (self attribute) is not a sync
        fs = lint(
            "class B:\n"
            "    def hybrid_forward(self, F, x):\n"
            "        s = float(self._scale)\n"
            "        return x * s\n")
        assert fs == []

    def test_suppression_inline(self):
        fs = lint(
            "class B:\n"
            "    def hybrid_forward(self, F, x):\n"
            "        v = x.asnumpy()  # mxlint: disable=host-sync-in-trace (debug probe)\n"
            "        return x\n")
        assert fs == []

    def test_suppression_file_level(self):
        fs = lint(
            "# mxlint: disable-file=host-sync-in-trace\n"
            "class B:\n"
            "    def hybrid_forward(self, F, x):\n"
            "        v = x.asnumpy()\n"
            "        return x\n")
        assert fs == []

    def test_suppression_is_per_rule(self):
        fs = lint(
            "class B:\n"
            "    def hybrid_forward(self, F, x):\n"
            "        v = x.asnumpy()  # mxlint: disable=tensor-branch-in-trace\n"
            "        return x\n")
        assert _rules(fs) == ["host-sync-in-trace"]


class TestStepLoopSync:
    SRC = (
        "def fit(data, net, trainer, loss_fn):\n"
        "    for batch in data:\n"
        "        l = loss_fn(net(batch))\n"
        "        l.backward()\n"
        "        trainer.step(1)\n"
        "        print(l.%s)\n")

    def test_positive(self):
        fs = lint(self.SRC % "asnumpy()")
        assert _rules(fs) == ["host-sync-in-step-loop"]
        assert fs[0].severity == "warn"

    def test_negative_outside_loop(self):
        fs = lint(
            "def evaluate(loss):\n"
            "    return loss.asnumpy()\n")
        assert fs == []

    def test_negative_plain_data_loop(self):
        fs = lint(
            "def show(batches):\n"
            "    for b in batches:\n"
            "        print(b.asnumpy())\n")
        assert fs == []

    def test_forward_backward_loop_counts(self):
        fs = lint(
            "def fit(mod, data):\n"
            "    for batch in data:\n"
            "        mod.forward_backward(batch)\n"
            "        mod.update()\n"
            "        x = batch.label.asnumpy()\n")
        assert _rules(fs) == ["host-sync-in-step-loop"]


class TestTensorBranch:
    def test_value_branch(self):
        fs = lint(
            "class B:\n"
            "    def hybrid_forward(self, F, x):\n"
            "        if x:\n"
            "            return x\n"
            "        return -x\n")
        assert _rules(fs) == ["tensor-branch-in-trace"]
        assert fs[0].severity == "error"

    def test_while_on_tensor(self):
        fs = lint(
            "class B:\n"
            "    def hybrid_forward(self, F, x):\n"
            "        while F.sum(x) > 0:\n"
            "            x = x - 1\n"
            "        return x\n")
        assert "tensor-branch-in-trace" in _rules(fs)

    def test_shape_branch_is_separate_warn(self):
        fs = lint(
            "class B:\n"
            "    def hybrid_forward(self, F, x):\n"
            "        if x.shape[0] > 1:\n"
            "            return F.sum(x)\n"
            "        return x\n")
        assert _rules(fs) == ["shape-branch-in-trace"]
        assert fs[0].severity == "warn"

    def test_len_branch_is_shape(self):
        fs = lint(
            "class B:\n"
            "    def hybrid_forward(self, F, x):\n"
            "        if len(x) > 2:\n"
            "            return x\n"
            "        return x\n")
        assert _rules(fs) == ["shape-branch-in-trace"]

    @pytest.mark.parametrize("test", [
        "bias is None", "bias is not None",
        "isinstance(x, NDArray)", "hasattr(x, 'stype')",
        "x is None or bias is None", "not isinstance(x, tuple)"])
    def test_static_tests_exempt(self, test):
        fs = lint(
            "class B:\n"
            "    def hybrid_forward(self, F, x, bias=None):\n"
            "        if %s:\n"
            "            return x\n"
            "        return x\n" % test)
        assert fs == []

    def test_branch_on_config_attr_ok(self):
        fs = lint(
            "class B:\n"
            "    def hybrid_forward(self, F, x):\n"
            "        if self._use_bias:\n"
            "            return x + 1\n"
            "        return x\n")
        assert fs == []


class TestScalarCapture:
    def test_jit_in_loop(self):
        fs = lint(
            "import jax\n"
            "def run(xs):\n"
            "    for x in xs:\n"
            "        f = jax.jit(lambda v: v * 2)\n"
            "        f(x)\n")
        assert "scalar-capture" in _rules(fs)

    def test_closure_over_loop_var(self):
        fs = lint(
            "import jax\n"
            "def run(xs):\n"
            "    for step in range(10):\n"
            "        def body(v):\n"
            "            return v * step\n"
            "        jax.jit(body)(xs)\n")
        rules = _rules(fs)
        assert rules.count("scalar-capture") >= 2  # in-loop + closure
        closure = [f for f in fs if "closes over" in f.message]
        assert closure and "'step'" in closure[0].message.replace(
            '"', "'")

    def test_module_level_jit_clean(self):
        fs = lint(
            "import jax\n"
            "def f(x):\n"
            "    return x * 2\n"
            "g = jax.jit(f)\n")
        assert fs == []

    def test_closure_over_stable_config_clean(self):
        fs = lint(
            "import jax\n"
            "def build(scale):\n"
            "    def body(v):\n"
            "        return v * scale\n"
            "    return jax.jit(body)\n")
        assert fs == []

    def test_method_name_not_confused_with_jitted_local(self):
        # a CLASS method sharing the name of a jitted local must not
        # become a trace context (the parallel/sharded.py false
        # positive this linter had to get right)
        fs = lint(
            "import jax\n"
            "class Runner:\n"
            "    def step(self, x):\n"
            "        return x.asnumpy()\n"
            "def make():\n"
            "    def step(params):\n"
            "        return params\n"
            "    return jax.jit(step)\n")
        assert fs == []


class TestGlobalRng:
    def test_np_random_in_forward(self):
        fs = lint(
            "import numpy as np\n"
            "class B:\n"
            "    def hybrid_forward(self, F, x):\n"
            "        noise = np.random.uniform(size=3)\n"
            "        return x + noise\n")
        assert _rules(fs) == ["global-rng-in-trace"]

    def test_stdlib_random_in_jitted(self):
        fs = lint(
            "import jax, random\n"
            "def f(x):\n"
            "    return x * random.random()\n"
            "g = jax.jit(f)\n")
        assert "global-rng-in-trace" in _rules(fs)

    def test_traced_rng_clean(self):
        fs = lint(
            "class B:\n"
            "    def hybrid_forward(self, F, x):\n"
            "        return x + F.random_normal(shape=(3,))\n")
        assert fs == []


class TestMutateCaptured:
    def test_slice_store_on_param(self):
        fs = lint(
            "class B:\n"
            "    def hybrid_forward(self, F, x):\n"
            "        x[:] = 0\n"
            "        return x\n")
        assert _rules(fs) == ["mutate-captured-in-trace"]

    def test_augassign_on_param(self):
        fs = lint(
            "class B:\n"
            "    def hybrid_forward(self, F, x):\n"
            "        x += 1\n"
            "        return x\n")
        assert _rules(fs) == ["mutate-captured-in-trace"]

    def test_mutating_free_var_in_jitted(self):
        fs = lint(
            "import jax\n"
            "def make(buf):\n"
            "    def f(x):\n"
            "        buf[0] = x\n"
            "        return x\n"
            "    return jax.jit(f)\n")
        assert "mutate-captured-in-trace" in _rules(fs)

    def test_local_rebind_clean(self):
        fs = lint(
            "class B:\n"
            "    def hybrid_forward(self, F, x):\n"
            "        y = x * 2\n"
            "        y = y + 1\n"
            "        return y\n")
        assert fs == []


def test_parse_error_is_a_finding():
    fs = lint("def broken(:\n")
    assert _rules(fs) == ["parse-error"]


# ===========================================================================
# fingerprints + baseline
# ===========================================================================
class TestBaseline:
    def _finding(self, line=3, text="v = x.asnumpy()"):
        return fmod.Finding(rule="host-sync-in-trace", level="ast",
                            severity="error", path="a.py", line=line,
                            message="m", text=text)

    def test_fingerprint_ignores_line_numbers(self):
        a, b = self._finding(line=3), self._finding(line=40)
        assert fmod.fingerprint(a) == fmod.fingerprint(b)

    def test_roundtrip_and_diff(self, tmp_path):
        path = str(tmp_path / "b.json")
        fmod.save_baseline(path, [self._finding(), self._finding()])
        base = fmod.load_baseline(path)
        # two accepted occurrences cover exactly two findings
        fresh, stale = fmod.diff_baseline(
            [self._finding(), self._finding()], base)
        assert fresh == [] and stale == []
        # a third identical finding is NEW
        fresh, _ = fmod.diff_baseline(
            [self._finding()] * 3, base)
        assert len(fresh) == 1
        # different text is NEW, and one accepted entry goes stale
        other = self._finding(text="w = y.asnumpy()")
        fresh, stale = fmod.diff_baseline(
            [self._finding(), other], base)
        assert len(fresh) == 1 and len(stale) == 1

    def test_no_baseline_means_everything_is_new(self):
        fresh, stale = fmod.diff_baseline([self._finding()], None)
        assert len(fresh) == 1 and stale == []


# ===========================================================================
# the CLI gate (exit codes — the ISSUE 9 satellite contract)
# ===========================================================================
def _mxlint_main():
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "_mxlint_cli", os.path.join(REPO, "tools", "mxlint.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.main


class TestCliGate:
    HAZARD = ("class B:\n"
              "    def hybrid_forward(self, F, x):\n"
              "        return float(x)\n")

    def test_gate_fails_on_unbaselined_finding(self, tmp_path, capsys):
        src = tmp_path / "bad.py"
        src.write_text(self.HAZARD)
        main = _mxlint_main()
        rc = main(["--gate", "--baseline",
                   str(tmp_path / "none.json"), str(src)])
        assert rc == 1
        assert "GATE FAILED" in capsys.readouterr().out

    def test_gate_passes_after_write_baseline(self, tmp_path, capsys):
        src = tmp_path / "bad.py"
        src.write_text(self.HAZARD)
        base = str(tmp_path / "base.json")
        main = _mxlint_main()
        assert main(["--write-baseline", "--baseline", base,
                     str(src)]) == 0
        assert main(["--gate", "--baseline", base, str(src)]) == 0
        assert "gate OK" in capsys.readouterr().out

    def test_json_output(self, tmp_path, capsys):
        src = tmp_path / "bad.py"
        src.write_text(self.HAZARD)
        main = _mxlint_main()
        rc = main(["--json", "--gate", "--baseline",
                   str(tmp_path / "none.json"), str(src)])
        assert rc == 1
        blob = json.loads(capsys.readouterr().out)
        assert blob["new"] and \
            blob["new"][0]["rule"] == "host-sync-in-trace"

    def test_clean_file_gates_zero(self, tmp_path):
        src = tmp_path / "ok.py"
        src.write_text("def f(x):\n    return x\n")
        assert _mxlint_main()(["--gate", "--baseline",
                               str(tmp_path / "none.json"),
                               str(src)]) == 0


# ===========================================================================
# the tier-1 SELF-LINT: mxnet_tpu/ vs the checked-in baseline
# ===========================================================================
def test_self_lint_against_checked_in_baseline():
    """The repo lints itself (ISSUE 9 tentpole): Level 1 over
    mxnet_tpu/ must produce NO finding that isn't in
    tools/mxlint_baseline.json — a new trace hazard fails CI here.
    Fix the hazard, or (intentional only) add an inline
    `# mxlint: disable=<rule> (reason)`, or re-run
    `python tools/mxlint.py --write-baseline mxnet_tpu/`."""
    found = ast_rules.lint_paths(
        [os.path.join(REPO, "mxnet_tpu")], root=REPO)
    baseline = fmod.load_baseline(
        os.path.join(REPO, "tools", "mxlint_baseline.json"))
    fresh, _stale = fmod.diff_baseline(found, baseline)
    assert fresh == [], \
        "new static-analysis findings in mxnet_tpu/:\n%s" \
        % fmod.render_findings(fresh)


# ===========================================================================
# Level 2 — graph rules
# ===========================================================================
class TestGraphRulesDirect:
    def _trace(self, fn, *args):
        import jax
        return jax.jit(fn).trace(*args).jaxpr

    def test_explicit_upcast_flagged_with_input_name(self):
        import jax.numpy as jnp
        cj = self._trace(
            lambda x, w: (x.astype(jnp.float32) * w).astype(jnp.bfloat16),
            jnp.ones((8, 8), jnp.bfloat16), jnp.ones((8, 8), jnp.float32))
        fs = graph_rules.check_closed_jaxpr(cj, "prog",
                                            arg_names=["x", "w"])
        assert _rules(fs) == ["graph-f32-promotion"]
        assert "'x'" in fs[0].message

    def test_mixed_precision_dot_flagged(self):
        import jax.numpy as jnp
        cj = self._trace(lambda x, w: jnp.dot(x, w),
                         jnp.ones((4, 16), jnp.bfloat16),
                         jnp.ones((16, 8), jnp.float32))
        fs = graph_rules.check_closed_jaxpr(cj, "prog")
        assert _rules(fs) == ["graph-f32-promotion"]
        assert "dot_general" in fs[0].message

    def test_all_bf16_dot_clean(self):
        # bf16 x bf16 with f32 ACCUMULATION is the idiomatic MXU form
        import jax.numpy as jnp
        cj = self._trace(lambda x, w: jnp.dot(x, w),
                         jnp.ones((4, 16), jnp.bfloat16),
                         jnp.ones((16, 8), jnp.bfloat16))
        assert graph_rules.check_closed_jaxpr(cj, "prog") == []

    def test_f32_program_not_a_bf16_program(self):
        import jax.numpy as jnp
        cj = self._trace(lambda x: x.astype(jnp.float64).sum(),
                         jnp.ones((4,), jnp.float32))
        assert graph_rules.check_closed_jaxpr(cj, "prog") == []

    def test_host_callback_flagged(self):
        import jax
        import jax.numpy as jnp

        def probe(x):
            return x * 2

        def fn(x):
            y = jax.pure_callback(
                probe, jax.ShapeDtypeStruct(x.shape, x.dtype), x)
            return y + 1

        cj = self._trace(fn, jnp.ones((4,), jnp.float32))
        fs = graph_rules.check_closed_jaxpr(cj, "prog")
        assert "graph-host-callback" in _rules(fs)
        assert any(f.severity == "error" for f in fs)

    def test_degenerate_broadcast_flagged(self):
        import jax.numpy as jnp
        cj = self._trace(
            lambda r: jnp.broadcast_to(r, (4096, 4096)) * 1.5,
            jnp.ones((1, 4096), jnp.float32))
        fs = graph_rules.check_closed_jaxpr(cj, "prog")
        assert "graph-degenerate-broadcast" in _rules(fs)

    def test_scalar_broadcast_clean(self):
        import jax.numpy as jnp
        cj = self._trace(lambda: jnp.zeros((4096, 4096), jnp.float32))
        assert graph_rules.check_closed_jaxpr(cj, "prog") == []

    def test_nondonated_update_program(self):
        import jax.numpy as jnp

        def update(w, g):
            return w - 0.1 * g

        cj = self._trace(update, jnp.ones((32, 32), jnp.float32),
                         jnp.ones((32, 32), jnp.float32))
        fs = graph_rules.check_closed_jaxpr(cj, "autograd.fused_step")
        assert _rules(fs) == ["graph-nondonated-update-param"]
        # declaring the donation clears it
        assert graph_rules.check_closed_jaxpr(
            cj, "autograd.fused_step", donated=(0,)) == []
        # non-update programs aren't held to donation
        assert graph_rules.check_closed_jaxpr(cj, "CachedOp.forward") == []

    def test_collective_in_eval_on_8dev_dryrun(self):
        """Graph check over the 8-virtual-device mesh (the dryrun the
        whole suite runs on): a psum-carrying program is an error
        under an */eval instance, clean under */train."""
        import jax
        import jax.numpy as jnp
        from jax.sharding import Mesh, PartitionSpec as P
        from mxnet_tpu.parallel import shard_map
        devs = np.array(jax.devices()[:8]).reshape(8)
        mesh = Mesh(devs, ("dp",))

        def allreduce(x):
            return jax.lax.psum(x, "dp")

        fn = shard_map(allreduce, mesh=mesh, in_specs=P("dp"),
                       out_specs=P())
        cj = jax.jit(fn).trace(
            jnp.ones((8, 4), jnp.float32)).jaxpr
        fs = graph_rules.check_closed_jaxpr(cj, "CachedOp.forward",
                                            instance="cop1/eval")
        assert "graph-collective-in-eval" in _rules(fs)
        assert "psum" in fs[0].message
        assert graph_rules.check_closed_jaxpr(
            cj, "CachedOp.forward", instance="cop1/train") == []


class TestGraphHook:
    @pytest.fixture(autouse=True)
    def _gates(self, monkeypatch):
        monkeypatch.setenv("MXNET_TELEMETRY", "1")
        monkeypatch.setenv("MXNET_STATICCHECK", "1")
        telemetry.refresh()
        staticcheck.refresh()
        telemetry.reset()
        staticcheck.reset()
        compilewatch.reset()
        yield

    def _bf16_net(self):
        net = nn.HybridSequential()
        net.add(nn.Dense(16))
        net.initialize()
        x = nd.ones((2, 8)).astype("bfloat16")
        net(x)
        net.hybridize()
        return net, x

    def test_hook_flags_mixed_precision_cachedop(self):
        net, x = self._bf16_net()
        net(x)              # compile: bf16 data through f32 params
        fs = staticcheck.graph_findings()
        assert any(f.rule == "graph-f32-promotion" and
                   "CachedOp.forward" in f.path for f in fs), fs
        # the finding carries the program instance + signature names
        # that recompile attribution produces
        hit = [f for f in fs if f.rule == "graph-f32-promotion"
               and "CachedOp.forward" in f.path][0]
        assert "cop" in hit.path and hit.extra.get("signature")

    def test_checked_once_per_signature(self):
        net, x = self._bf16_net()
        x2 = x * 2          # materialize BEFORE sampling counters:
        #                     the eager _mul_scalar program is itself
        #                     a (checked) compile
        net(x)
        n = len(staticcheck.graph_findings())
        checked = graph_rules.programs_checked()
        net(x2)             # same signature: cache hit, no re-check
        assert graph_rules.programs_checked() == checked
        assert len(staticcheck.graph_findings()) == n
        net(nd.ones((5, 8)).astype("bfloat16"))   # recompile: checked
        assert graph_rules.programs_checked() > checked

    def test_gate_off_records_nothing(self, monkeypatch):
        monkeypatch.setenv("MXNET_STATICCHECK", "0")
        staticcheck.refresh()
        net, x = self._bf16_net()
        net(x)
        assert staticcheck.graph_findings() == []

    def test_clean_f32_program_no_findings(self):
        net = nn.HybridSequential()
        net.add(nn.Dense(16))
        net.initialize()
        x = nd.ones((2, 8))
        net(x)
        net.hybridize()
        net(x)
        assert [f for f in staticcheck.graph_findings()
                if f.rule == "graph-f32-promotion"] == []

    def test_findings_counted_in_telemetry(self):
        net, x = self._bf16_net()
        net(x)
        assert telemetry.counter("mx_staticcheck_findings_total",
                                 rule="graph-f32-promotion").get() > 0

    def test_real_fused_step_program_donates_what_it_overwrites(self):
        """The Gluon loop's own ``autograd.fused_step`` program, as the
        hook receives it: the variant that donates its weights, momenta
        and gradients is clean; the one a held alias falls back to is
        what the rule is written for."""
        from mxnet_tpu import gluon
        net = nn.HybridSequential()
        net.add(nn.Dense(16, activation="relu"), nn.Dense(4))
        net.initialize()
        net.hybridize()
        lf = gluon.loss.SoftmaxCrossEntropyLoss()
        lf.hybridize()
        tr = gluon.Trainer(net.collect_params(), "sgd",
                           {"learning_rate": 0.1, "momentum": 0.9})
        x = nd.ones((8, 12))
        y = nd.array(np.arange(8) % 4, dtype="int32")

        def step():
            with autograd.record():
                loss = lf(net(x), y)
            loss.backward()
            tr.step(8)

        def fused_step_findings():
            return [f for f in staticcheck.graph_findings()
                    if f.rule == "graph-nondonated-update-param"
                    and "autograd.fused_step" in f.path]

        try:
            for _ in range(3):
                step()
            ran = [p for p in compilewatch.programs()
                   if p["fn"] == "autograd.fused_step"]
            assert [p["instance"] for p in ran] == \
                ["tape[2 nodes]+update/donating"]
            assert fused_step_findings() == []
            held = next(iter(net.collect_params().values())).data().detach()
            step()
            assert held.asnumpy().shape == held.shape
            assert [f.path for f in fused_step_findings()] == \
                ["autograd.fused_step (tape[2 nodes]+update)"]
        finally:
            autograd.disarm_fused_update()
            autograd.flush_pending_step()


# ===========================================================================
# Level 3 — engine race detector
# ===========================================================================
def _native_available():
    from mxnet_tpu.engine import native_or_none
    return native_or_none() is not None


_needs_native = pytest.mark.skipif(
    not _native_available(), reason="native dependency engine unavailable")


def _register_probe(name, delay=0.0):
    class _Prop(mx.operator.CustomOpProp):
        def list_arguments(self):
            return ["data"]

        def list_outputs(self):
            return ["out"]

        def infer_shape(self, in_shape):
            return in_shape, [in_shape[0]]

        def create_operator(self, ctx, shapes, dtypes):
            class _Op(mx.operator.CustomOp):
                def forward(self, is_train, req, in_data, out_data, aux):
                    if delay:
                        time.sleep(delay)
                    self.assign(out_data[0], req[0], in_data[0] * 2)

                def backward(self, req, out_grad, in_data, out_data,
                             in_grad, aux):
                    self.assign(in_grad[0], req[0], out_grad[0] * 2)
            return _Op()
    try:
        mx.operator.register(name)(_Prop)
    except Exception:
        pass     # already registered by an earlier test in the session
    return name


@_needs_native
class TestRaceChecker:
    @pytest.fixture(autouse=True)
    def _arm(self, monkeypatch):
        monkeypatch.setenv("MXNET_ENGINE_RACE_CHECK", "1")
        staticcheck.refresh()
        staticcheck.reset()
        yield

    def test_declared_chain_is_clean(self):
        op = _register_probe("_sc_probe_slow", delay=0.2)
        x = nd.ones((8,))
        y = nd.Custom(x, op_type=op)
        z = nd.Custom(y, op_type=op)      # declared edge y -> z
        np.testing.assert_allclose(z.asnumpy(), np.full((8,), 4.0))
        nd.waitall()
        assert staticcheck.race_findings() == []

    def test_dropped_edge_names_both_ops_and_handle(self):
        """Acceptance (ISSUE 9 satellite): the engine_dep_drop fault
        site removes one declared read edge; the checker must name the
        two ops and the shared NDArray handle."""
        op = _register_probe("_sc_probe_slow2", delay=0.3)
        x = nd.ones((8,))
        faultinject.set_fault("engine_dep_drop", prob=1.0, max_fires=1)
        try:
            a = nd.Custom(x, op_type=op)
            assert a._pending is not None   # producer still in flight
            b = nd.Custom(a, op_type=op)
            fired = faultinject.fires("engine_dep_drop")
            b.wait_to_read()
        finally:
            faultinject.clear()
        nd.waitall()
        assert fired == 1
        fs = staticcheck.race_findings()
        assert len(fs) == 1, fs
        f = fs[0]
        assert f.rule == "race-undeclared-read"
        assert f.severity == "error"
        # names the two ops...
        assert f.message.count("custom_op:_sc_probe_slow2") == 2
        assert "operator.py" in f.message       # ...their enqueue sites
        # ...and the shared NDArray handle (dtype+shape, engine var)
        assert "float32(8,)" in f.message
        assert "engine var" in f.message

    def test_dropped_edge_detection_is_deterministic(self):
        """Three consecutive injected drops, three findings — the
        detection must not depend on the thread schedule (the binding
        persists past gate clearing)."""
        op = _register_probe("_sc_probe_slow3", delay=0.15)
        for i in range(3):
            staticcheck.reset()
            faultinject.reset()
            x = nd.ones((4,))
            faultinject.set_fault("engine_dep_drop", prob=1.0,
                                  max_fires=1)
            try:
                a = nd.Custom(x, op_type=op)
                b = nd.Custom(a, op_type=op)
                b.wait_to_read()
            finally:
                faultinject.clear()
            nd.waitall()
            assert len(staticcheck.race_findings()) == 1, \
                "round %d missed the dropped edge" % i

    def test_raise_mode_surfaces_at_wait(self, monkeypatch):
        monkeypatch.setenv("MXNET_ENGINE_RACE_CHECK", "raise")
        staticcheck.refresh()
        op = _register_probe("_sc_probe_slow4", delay=0.3)
        x = nd.ones((8,))
        faultinject.set_fault("engine_dep_drop", prob=1.0, max_fires=1)
        try:
            a = nd.Custom(x, op_type=op)
            b = nd.Custom(a, op_type=op)
            with pytest.raises(MXNetError,
                               match="MXNET_ENGINE_RACE_CHECK"):
                b.wait_to_read()
        finally:
            faultinject.clear()
            try:
                nd.waitall()
            except MXNetError:
                pass

    def test_undeclared_write_flagged(self):
        """An op rebinding an array gated by ANOTHER op's var, without
        declaring it, is an undeclared write."""
        import jax
        import jax.numpy as jnp
        from mxnet_tpu import engine as eng
        ne = eng.native_engine()
        arr = nd.ones((4,))
        aval = jax.ShapeDtypeStruct((4,), jnp.float32)
        var_a, _gate = eng.gate_arrays([arr], [aval])

        def own_write():
            arr._set_jax(jnp.zeros((4,), jnp.float32))
        eng.push_gated(own_write, var_a, label="owner")
        ne.wait_for_all()
        assert staticcheck.race_findings() == []

        out = nd.zeros((2,))
        var_b, _gate_b = eng.gate_arrays([out], [
            jax.ShapeDtypeStruct((2,), jnp.float32)])

        def rogue():
            arr._set_jax(jnp.full((4,), 9.0))   # not declared!
            out._set_jax(jnp.zeros((2,), jnp.float32))
        eng.push_gated(rogue, var_b, label="rogue_op")
        ne.wait_for_all()
        fs = [f for f in staticcheck.race_findings()
              if f.rule == "race-undeclared-write"]
        assert len(fs) == 1, staticcheck.race_findings()
        assert "rogue_op" in fs[0].message
        assert "'owner'" in fs[0].message

    def test_private_temp_mutation_not_flagged(self, monkeypatch):
        """Review regression: in-place mutation of an op's OWN
        never-gated temporary is private — no finding, and raise mode
        must not poison the (correct) op."""
        monkeypatch.setenv("MXNET_ENGINE_RACE_CHECK", "raise")
        staticcheck.refresh()

        class _TmpProp(mx.operator.CustomOpProp):
            def list_arguments(self):
                return ["data"]

            def list_outputs(self):
                return ["out"]

            def infer_shape(self, in_shape):
                return in_shape, [in_shape[0]]

            def create_operator(self, ctx, shapes, dtypes):
                class _Op(mx.operator.CustomOp):
                    def forward(self, is_train, req, in_data, out_data,
                                aux):
                        tmp = in_data[0] + 0
                        tmp[0] = 99.0          # private in-place write
                        self.assign(out_data[0], req[0], tmp)

                    def backward(self, *a):
                        pass
                return _Op()
        try:
            mx.operator.register("_sc_tmp_probe")(_TmpProp)
        except Exception:
            pass
        y = nd.Custom(nd.ones((4,)), op_type="_sc_tmp_probe")
        got = y.asnumpy()
        nd.waitall()
        assert got[0] == 99.0 and got[1] == 1.0
        assert staticcheck.race_findings() == []

    def test_custom_op_aux_write_is_declared(self):
        """Regression for the Level-3 self-check fix (ISSUE 9
        satellite): nd.Custom mutates aux states on the worker — they
        are gated into the op's write set now, so the checker stays
        quiet AND a post-call aux read is ordered after the op."""
        class _AuxProp(mx.operator.CustomOpProp):
            def list_arguments(self):
                return ["data"]

            def list_outputs(self):
                return ["out"]

            def list_auxiliary_states(self):
                return ["counter"]

            def infer_shape(self, in_shape):
                return in_shape, [in_shape[0]], [[1]]

            def create_operator(self, ctx, shapes, dtypes):
                class _Op(mx.operator.CustomOp):
                    def forward(self, is_train, req, in_data, out_data,
                                aux):
                        time.sleep(0.2)
                        aux[0][:] = aux[0] + 1      # worker-side write
                        self.assign(out_data[0], req[0], in_data[0])

                    def backward(self, *a):
                        pass
                return _Op()
        try:
            mx.operator.register("_sc_aux_probe")(_AuxProp)
        except Exception:
            pass
        x = nd.ones((4,))
        counter = nd.zeros((1,))
        out = nd.Custom(x, counter, op_type="_sc_aux_probe")
        # reading aux right after the call is ordered AFTER the op
        assert counter.asnumpy()[0] == 1.0
        out.wait_to_read()
        nd.waitall()
        assert [f for f in staticcheck.race_findings()
                if f.rule == "race-undeclared-write"] == []

    def test_disabled_gate_installs_no_hook(self, monkeypatch):
        from mxnet_tpu import engine as eng
        monkeypatch.setenv("MXNET_ENGINE_RACE_CHECK", "0")
        staticcheck.refresh()
        assert eng._RACE_HOOK[0] is None
        op = _register_probe("_sc_probe_off")
        y = nd.Custom(nd.ones((4,)), op_type=op)
        y.wait_to_read()
        assert staticcheck.race_findings() == []


# ===========================================================================
# rule catalog sanity
# ===========================================================================
def test_every_rule_registered_once_with_level_and_severity():
    rules = staticcheck.all_rules()
    ids = [r.id for r in rules]
    assert len(ids) == len(set(ids))
    levels = {r.level for r in rules}
    assert levels == {"ast", "graph", "spmd", "race"}
    for r in rules:
        assert r.severity in ("warn", "error")
        assert r.doc


# ===========================================================================
# ISSUE 15 satellites: stale suppressions, graph-level suppression,
# CLI path-spelling stability, SARIF export
# ===========================================================================
class TestStaleSuppressions:
    def test_unused_disable_reported(self):
        stale = []
        fs = ast_rules.lint_source(
            "def clean(x):\n"
            "    return x  # mxlint: disable=host-sync-in-trace (was fixed)\n",
            "fixture.py", stale_out=stale)
        assert fs == []
        assert stale == [{"path": "fixture.py", "line": 2,
                          "rule": "host-sync-in-trace"}]

    def test_used_disable_not_reported(self):
        stale = []
        fs = ast_rules.lint_source(
            "class B:\n"
            "    def hybrid_forward(self, F, x):\n"
            "        v = x.asnumpy()  # mxlint: disable=host-sync-in-trace (probe)\n"
            "        return x\n",
            "fixture.py", stale_out=stale)
        assert fs == [] and stale == []

    def test_non_ast_rule_ids_exempt(self):
        # graph/spmd rule ids in comments are honored at RUNTIME by
        # other levels — the static pass cannot judge them stale
        stale = []
        ast_rules.lint_source(
            "def f(x):\n"
            "    return x  # mxlint: disable=graph-degenerate-sharding (runtime)\n",
            "fixture.py", stale_out=stale)
        assert stale == []

    def test_docstring_example_not_a_suppression(self):
        # the syntax shown inside a docstring is documentation — it
        # must neither suppress nor read as stale (the findings.py
        # module docstring is the real-world case)
        stale = []
        fs = ast_rules.lint_source(
            '"""Example:\n'
            "    v = x.asnumpy()  # mxlint: disable=host-sync-in-trace (reason)\n"
            '"""\n'
            "class B:\n"
            "    def hybrid_forward(self, F, x):\n"
            "        return float(x)\n",
            "fixture.py", stale_out=stale)
        assert _rules(fs) == ["host-sync-in-trace"]
        assert stale == []

    def test_docstring_disable_file_not_a_suppression(self):
        # review fix: a disable-file EXAMPLE inside a docstring must
        # not opt the whole file out of the rule
        fs = ast_rules.lint_source(
            '"""Syntax:\n'
            "    # mxlint: disable-file=host-sync-in-trace\n"
            '"""\n'
            "class B:\n"
            "    def hybrid_forward(self, F, x):\n"
            "        return float(x)\n",
            "fixture.py")
        assert _rules(fs) == ["host-sync-in-trace"]

    def test_suppression_on_multiline_string_closing_line(self):
        # review fix: a GENUINE disable comment on the line where a
        # multiline string ends must keep working (only interior
        # lines are scrubbed)
        stale = []
        fs = ast_rules.lint_source(
            "class B:\n"
            "    def hybrid_forward(self, F, x):\n"
            '        msg = """\n'
            "banner\n"
            '"""; v = x.asnumpy()  # mxlint: disable=host-sync-in-trace (probe)\n'
            "        return x\n",
            "fixture.py", stale_out=stale)
        assert fs == [] and stale == []

    def test_cli_reports_stale(self, tmp_path, capsys):
        src = tmp_path / "s.py"
        src.write_text("def f(x):\n"
                       "    return x  # mxlint: disable=scalar-capture\n")
        main = _mxlint_main()
        rc = main(["--json", str(src)])
        assert rc == 0
        blob = json.loads(capsys.readouterr().out)
        assert blob["stale_suppressions"] and \
            blob["stale_suppressions"][0]["rule"] == "scalar-capture"


class TestGraphLevelSuppression:
    """ISSUE 15 satellite: the SAME inline disable syntax silences a
    graph-level finding at the source line that bound the offending
    op (jaxpr eqns carry source info)."""

    def _mod(self, tmp_path, suppress: bool):
        comment = ("  # mxlint: disable=graph-host-callback (probe by "
                   "contract)" if suppress else "")
        src = (
            "import jax\n"
            "def probe(x):\n"
            "    return x\n"
            "def fn(x):\n"
            "    y = jax.pure_callback(probe, "
            "jax.ShapeDtypeStruct(x.shape, x.dtype), x)%s\n"
            "    return y + 1\n" % comment)
        p = tmp_path / ("supp_%d.py" % suppress)
        p.write_text(src)
        import importlib.util
        spec = importlib.util.spec_from_file_location(
            "_supp_fixture_%d" % suppress, str(p))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    def test_suppressed_vs_unsuppressed(self, tmp_path):
        import jax
        import jax.numpy as jnp
        loud = self._mod(tmp_path, suppress=False)
        cj = jax.jit(loud.fn).trace(jnp.ones((4,), jnp.float32)).jaxpr
        fs = graph_rules.check_closed_jaxpr(cj, "prog")
        assert "graph-host-callback" in _rules(fs)

        quiet = self._mod(tmp_path, suppress=True)
        cj = jax.jit(quiet.fn).trace(jnp.ones((4,), jnp.float32)).jaxpr
        assert graph_rules.check_closed_jaxpr(cj, "prog") == []


class TestPathSpellingStability:
    """ISSUE 15 satellite: fingerprints are repo-relative POSIX real
    paths — `mxlint pkg` and `mxlint ./pkg/` agree byte-for-byte, and
    a baseline written with one spelling gates clean with the other."""

    HAZARD = ("class B:\n"
              "    def hybrid_forward(self, F, x):\n"
              "        return float(x)\n")

    def _tree(self, tmp_path):
        pkg = tmp_path / "pkg"
        pkg.mkdir()
        (pkg / "bad.py").write_text(self.HAZARD)
        return pkg

    def test_json_bytes_stable_across_spellings(self, tmp_path,
                                                capsys, monkeypatch):
        self._tree(tmp_path)
        monkeypatch.chdir(tmp_path)
        main = _mxlint_main()
        outs = []
        for spelling in ("pkg", "./pkg/", str(tmp_path / "pkg")):
            assert main(["--json", spelling]) == 0
            outs.append(capsys.readouterr().out)
        assert outs[0] == outs[1] == outs[2]

    def test_baseline_spelling_roundtrip(self, tmp_path, monkeypatch):
        self._tree(tmp_path)
        monkeypatch.chdir(tmp_path)
        base = str(tmp_path / "base.json")
        main = _mxlint_main()
        assert main(["--write-baseline", "--baseline", base,
                     "pkg"]) == 0
        assert main(["--gate", "--baseline", base, "./pkg/"]) == 0
        assert main(["--gate", "--baseline", base,
                     str(tmp_path / "pkg")]) == 0

    def test_overlapping_spellings_lint_once(self, tmp_path,
                                             monkeypatch):
        pkg = self._tree(tmp_path)
        monkeypatch.chdir(tmp_path)
        found = ast_rules.lint_paths(["pkg", "./pkg"],
                                     root=str(tmp_path))
        assert len(found) == 1                 # deduped by real path


class TestSarifOutput:
    HAZARD = ("class B:\n"
              "    def hybrid_forward(self, F, x):\n"
              "        return float(x)\n")

    def test_sarif_rules_results_fingerprints(self, tmp_path):
        src = tmp_path / "bad.py"
        src.write_text(self.HAZARD)
        out = str(tmp_path / "out.sarif")
        main = _mxlint_main()
        assert main(["--sarif", out, "--baseline",
                     str(tmp_path / "none.json"), str(src)]) == 0
        blob = json.loads(open(out).read())
        assert blob["version"] == "2.1.0"
        run = blob["runs"][0]
        ids = [r["id"] for r in run["tool"]["driver"]["rules"]]
        assert "host-sync-in-trace" in ids
        res = run["results"]
        assert len(res) == 1
        assert res[0]["ruleId"] == "host-sync-in-trace"
        assert res[0]["level"] == "error"
        assert res[0]["locations"][0]["physicalLocation"][
            "artifactLocation"]["uri"].endswith("bad.py")
        fp = res[0]["partialFingerprints"]["mxlint/v1"]
        assert len(fp) == 40 and "suppressions" not in res[0]

    def test_baselined_findings_marked_suppressed(self, tmp_path):
        src = tmp_path / "bad.py"
        src.write_text(self.HAZARD)
        base = str(tmp_path / "base.json")
        out = str(tmp_path / "out.sarif")
        main = _mxlint_main()
        assert main(["--write-baseline", "--baseline", base,
                     str(src)]) == 0
        assert main(["--gate", "--sarif", out, "--baseline", base,
                     str(src)]) == 0
        blob = json.loads(open(out).read())
        res = blob["runs"][0]["results"]
        assert len(res) == 1
        assert res[0]["suppressions"] == [{"kind": "external"}]

    def test_sarif_fingerprint_stable_across_line_moves(self, tmp_path):
        src = tmp_path / "bad.py"
        src.write_text(self.HAZARD)
        out1, out2 = str(tmp_path / "a.sarif"), str(tmp_path / "b.sarif")
        main = _mxlint_main()
        assert main(["--sarif", out1, str(src)]) == 0
        src.write_text("# a comment pushed everything down\n"
                       + self.HAZARD)
        assert main(["--sarif", out2, str(src)]) == 0
        fp = [json.loads(open(p).read())["runs"][0]["results"][0]
              ["partialFingerprints"]["mxlint/v1"] for p in (out1, out2)]
        assert fp[0] == fp[1]
