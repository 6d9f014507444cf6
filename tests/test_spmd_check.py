"""mxlint Level 4 — SPMD shardcheck tests (ISSUE 15;
docs/STATICCHECK.md "Level 4").

Covers: the three graph-side rules direct and through the compilewatch
hook (implicit all-gather with arg attribution, reshard thrash,
degenerate sharding, the manual-layout exemption), pre-compile serve
``param_specs`` validation, the collective-issuing mark + the Level-3
``collective-interleave`` hazard (checker-level and end-to-end on the
serve scheduler via the ``engine_collective_overlap`` fault site), and
the SELF-LINT: the ZeRO, quantized-kvstore and pjit-serving programs
all compile clean under the new rules.
"""
import re
import threading
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import (autograd, compilewatch, faultinject, gluon, nd,
                       staticcheck, telemetry)
from mxnet_tpu.base import MXNetError
from mxnet_tpu.staticcheck import graph_rules, race, spmd_rules
from mxnet_tpu.gluon import nn

pytestmark = pytest.mark.staticcheck


def _ndev(n):
    if jax.device_count() < n:
        pytest.skip("needs %d devices" % n)
    return jax.devices()[:n]


def _mesh(n=8, names=("dp",)):
    from mxnet_tpu.kvstore import device_mesh
    return device_mesh(_ndev(n), names)


@pytest.fixture(autouse=True)
def _clean(monkeypatch):
    for var in ("MXNET_STATICCHECK", "MXNET_STATICCHECK_SPMD",
                "MXNET_ENGINE_RACE_CHECK", "MXNET_ZERO",
                "MXNET_KVSTORE_QUANTIZE"):
        monkeypatch.delenv(var, raising=False)
    staticcheck.refresh()
    staticcheck.reset()
    compilewatch.reset()
    telemetry.refresh()
    telemetry.reset()
    yield
    faultinject.reset()
    staticcheck.reset()
    compilewatch.reset()
    staticcheck.refresh()
    telemetry.refresh()
    telemetry.reset()


def _rules(fs):
    return [f.rule for f in fs]


def _compile(fn, *args, out_shardings=None):
    j = jax.jit(fn, out_shardings=out_shardings) \
        if out_shardings is not None else jax.jit(fn)
    traced = j.trace(*args)
    return traced.jaxpr, traced.lower().compile()


def _sharded(shape, mesh, spec, dtype=jnp.float32):
    from jax.sharding import NamedSharding
    return jax.device_put(jnp.ones(shape, dtype),
                          NamedSharding(mesh, spec))


def _shard_map(body, mesh, in_specs, out_specs):
    from mxnet_tpu.parallel import shard_map
    return shard_map(body, mesh=mesh, in_specs=in_specs,
                     out_specs=out_specs, check_vma=False)


def _first_weight_spec(net, spec):
    """(param_specs rule pinned to this net's FIRST weight, its name)
    — exact-name match, immune to the gluon global name counter (a
    second test's net is dense2/dense3...)."""
    wname = [n for n in net.collect_params()
             if n.endswith("weight")][0]
    return [(re.escape(wname) + "$", spec)], wname


# ===========================================================================
# param_specs pre-compile validation
# ===========================================================================
class TestValidateParamSpecs:
    def _rules_of(self, *pairs):
        from jax.sharding import PartitionSpec as P  # noqa: F401
        return [(re.compile(pat), spec) for pat, spec in pairs]

    def test_valid_specs_pass(self):
        from jax.sharding import PartitionSpec as P
        mesh = _mesh(8, ("mp",))
        spmd_rules.validate_param_specs(
            mesh, self._rules_of((r".*weight", P("mp", None))),
            [("dense0_weight", (16, 16)), ("dense0_bias", (16,))])

    def test_unknown_axis_named(self):
        from jax.sharding import PartitionSpec as P
        mesh = _mesh(8, ("mp",))
        with pytest.raises(MXNetError, match=r"'tp'.*not a mesh axis"):
            spmd_rules.validate_param_specs(
                mesh, self._rules_of((r".*weight", P("tp"))),
                [("dense0_weight", (16, 16))])

    def test_rank_overflow(self):
        from jax.sharding import PartitionSpec as P
        mesh = _mesh(8, ("mp",))
        with pytest.raises(MXNetError, match="rank"):
            spmd_rules.validate_param_specs(
                mesh, self._rules_of((r".*bias", P(None, "mp"))),
                [("dense0_bias", (16,))])

    def test_divisibility_named(self):
        from jax.sharding import PartitionSpec as P
        mesh = _mesh(8, ("mp",))
        with pytest.raises(MXNetError,
                           match=r"dim 0 \(size 12\).*'mp' \(size 8\)"):
            spmd_rules.validate_param_specs(
                mesh, self._rules_of((r".*weight", P("mp", None))),
                [("dense0_weight", (12, 16))])

    def test_duplicate_axis(self):
        from jax.sharding import PartitionSpec as P
        mesh = _mesh(8, ("mp",))
        with pytest.raises(MXNetError, match="more than once"):
            spmd_rules.validate_param_specs(
                mesh, self._rules_of((r".*weight", P("mp", "mp"))),
                [("dense0_weight", (16, 16))])

    def test_first_match_wins(self):
        from jax.sharding import PartitionSpec as P
        mesh = _mesh(8, ("mp",))
        # first rule replicates; the second (bad) rule never applies
        spmd_rules.validate_param_specs(
            mesh, self._rules_of((r".*weight", P()),
                                 (r".*", P("nope"))),
            [("dense0_weight", (16, 16))])

    def test_serve_session_rejects_bad_spec_before_compile(self):
        from jax.sharding import PartitionSpec as P
        mesh = _mesh(8, ("mp",))
        net = nn.HybridSequential()
        net.add(nn.Dense(16, in_units=16, activation="relu"),
                nn.Dense(8))
        net.initialize()
        x = nd.ones((2, 16))
        with pytest.raises(MXNetError,
                           match=r"spmd-invalid-partition-spec.*'tp'"):
            net.serve_session(x, max_batch=2, mesh=mesh,
                              param_specs=[(r".*weight", P("tp"))])
        # nothing was AOT-built for serving (the raise came first)
        assert not [r for r in compilewatch.programs()
                    if r["site"] == "serve"]

    def test_serve_session_divisibility_before_compile(self):
        from jax.sharding import PartitionSpec as P
        mesh = _mesh(8, ("mp",))
        net = nn.HybridSequential()
        net.add(nn.Dense(12, in_units=16))      # 12 % 8 != 0
        net.initialize()
        with pytest.raises(MXNetError, match=r"size 12.*'mp'"):
            net.serve_session(nd.ones((2, 16)), max_batch=2, mesh=mesh,
                              param_specs=[(r".*weight",
                                            P("mp", None))])


# ===========================================================================
# graph-side rules, direct
# ===========================================================================
class TestImplicitAllgather:
    def test_large_materialization_flagged_with_arg_and_axis(self):
        from jax.sharding import NamedSharding, PartitionSpec as P
        mesh = _mesh(8, ("dp",))

        def f(x):
            return jax.lax.with_sharding_constraint(
                x * 2.0, NamedSharding(mesh, P()))

        x = _sharded((1024, 512), mesh, P("dp"))   # 2 MiB gathered
        cj, compiled = _compile(f, x)
        fs, issues = spmd_rules.check_compiled(cj, compiled, "prog",
                                               arg_names=["x"])
        assert issues
        assert _rules(fs) == ["graph-implicit-allgather"]
        assert "'dp'" in fs[0].message and "'x'" in fs[0].message
        assert fs[0].severity == "warn"

    def test_below_threshold_clean(self):
        from jax.sharding import NamedSharding, PartitionSpec as P
        mesh = _mesh(8, ("dp",))

        def f(x):
            return jax.lax.with_sharding_constraint(
                x * 2.0, NamedSharding(mesh, P()))

        x = _sharded((64, 64), mesh, P("dp"))      # 16 KiB: noise
        cj, compiled = _compile(f, x)
        fs, issues = spmd_rules.check_compiled(cj, compiled, "prog")
        assert issues and fs == []

    def test_manual_layout_exempt(self):
        """A program that issues its collectives EXPLICITLY (the ZeRO
        weight all-gather shape) is not second-guessed."""
        from jax.sharding import PartitionSpec as P
        mesh = _mesh(8, ("dp",))

        def gather(x):
            return jax.lax.all_gather(x, "dp", tiled=True)

        fn = _shard_map(gather, mesh, P("dp"), P())
        x = _sharded((1024, 512), mesh, P("dp"))
        cj, compiled = _compile(fn, x)
        fs, issues = spmd_rules.check_compiled(cj, compiled, "prog")
        assert issues
        assert "graph-implicit-allgather" not in _rules(fs)

    def test_single_device_program_untouched(self):
        cj, compiled = _compile(lambda x: x * 2,
                                jnp.ones((1024, 512), jnp.float32))
        fs, issues = spmd_rules.check_compiled(cj, compiled, "prog")
        assert fs == [] and not issues


class TestReshardThrash:
    def test_chained_constraints_flagged(self):
        from jax.sharding import NamedSharding, PartitionSpec as P
        mesh = _mesh(8, ("dp",))

        def f(x):
            y = jax.lax.with_sharding_constraint(
                x, NamedSharding(mesh, P(None, "dp")))
            return jax.lax.with_sharding_constraint(
                y * 1.0, NamedSharding(mesh, P("dp", None)))

        x = _sharded((1024, 512), mesh, P("dp"))
        cj, compiled = _compile(f, x)
        fs, _issues = spmd_rules.check_compiled(cj, compiled, "prog")
        assert "graph-reshard-thrash" in _rules(fs)
        hit = [f for f in fs if f.rule == "graph-reshard-thrash"][0]
        assert "feeds" in hit.message

    def test_single_reshard_clean(self):
        from jax.sharding import NamedSharding, PartitionSpec as P
        mesh = _mesh(8, ("dp",))

        def f(x):
            return jax.lax.with_sharding_constraint(
                x * 1.0, NamedSharding(mesh, P(None, "dp")))

        x = _sharded((1024, 512), mesh, P("dp"))
        cj, compiled = _compile(f, x)
        fs, _issues = spmd_rules.check_compiled(cj, compiled, "prog")
        assert "graph-reshard-thrash" not in _rules(fs)

    def test_generic_fusion_blocks_the_walk(self):
        """Review fix: a fusion name must carry a LAYOUT token to pass
        through — 'fusion.3' may hide compute (the ZeRO update) and
        must not chain two reshards into a false thrash."""
        assert not spmd_rules._layout_only_fusion("fusion.3")
        assert not spmd_rules._layout_only_fusion("fused_computation.7")
        assert not spmd_rules._layout_only_fusion(
            "loop_multiply_fusion")
        assert spmd_rules._layout_only_fusion("copy_slice_fusion.2")
        assert spmd_rules._layout_only_fusion("bitcast_slice_fusion")
        # end to end: a generic fusion between two reshards = no chain
        hlo = ("ENTRY %main (p: f32[8]) -> f32[8] {\n"
               "  %p = f32[8]{0} parameter(0)\n"
               "  %a2a.1 = f32[8]{0} all-to-all(f32[8]{0} %p), "
               "replica_groups={{0,1,2,3,4,5,6,7}}\n"
               "  %fusion.3 = f32[8]{0} fusion(f32[8]{0} %a2a.1), "
               "kind=kLoop, calls=%fused_computation\n"
               "  ROOT %a2a.2 = f32[8]{0} all-to-all(f32[8]{0} "
               "%fusion.3), replica_groups={{0,1,2,3,4,5,6,7}}\n"
               "}\n")
        assert spmd_rules._reshard_chains(hlo) == []
        layout = hlo.replace("fusion.3", "copy_slice_fusion.3")
        assert len(spmd_rules._reshard_chains(layout)) == 1

    def test_quantized_wire_shape_exempt(self):
        """all_to_all -> accumulate -> all_gather written BY HAND (the
        EQuARX wire composition) is the algorithm, not thrash."""
        from jax.sharding import PartitionSpec as P
        mesh = _mesh(8, ("kv",))

        def wire(x):
            parts = jax.lax.all_to_all(
                x.reshape(8, -1), "kv", split_axis=0, concat_axis=0,
                tiled=False)
            acc = parts.sum(axis=0)
            return jax.lax.all_gather(acc, "kv", tiled=True)

        fn = _shard_map(wire, mesh, P("kv"), P())
        x = _sharded((1024, 512), mesh, P("kv"))
        cj, compiled = _compile(fn, x)
        fs, issues = spmd_rules.check_compiled(cj, compiled, "prog")
        assert issues
        assert "graph-reshard-thrash" not in _rules(fs)


class TestDegenerateSharding:
    def _big_dot(self):
        def f(x, w):
            return x @ w
        return f

    def test_idle_axis_with_big_dot_flagged(self):
        from jax.sharding import PartitionSpec as P
        mesh = _mesh(8, ("mp",))
        x = _sharded((1024, 1024), mesh, P())       # replicated
        w = _sharded((1024, 1024), mesh, P())
        cj, compiled = _compile(self._big_dot(), x, w)
        fs, _issues = spmd_rules.check_compiled(cj, compiled, "prog",
                                                arg_names=["x", "w"])
        assert _rules(fs) == ["graph-degenerate-sharding"]
        assert "'mp'" in fs[0].message and "size 8" in fs[0].message

    def test_partitioned_input_clean(self):
        from jax.sharding import PartitionSpec as P
        mesh = _mesh(8, ("mp",))
        x = _sharded((1024, 1024), mesh, P("mp"))   # axis in use
        w = _sharded((1024, 1024), mesh, P())
        cj, compiled = _compile(self._big_dot(), x, w)
        fs, _issues = spmd_rules.check_compiled(cj, compiled, "prog")
        assert "graph-degenerate-sharding" not in _rules(fs)

    def test_small_dot_clean(self):
        from jax.sharding import PartitionSpec as P
        mesh = _mesh(8, ("mp",))
        x = _sharded((64, 64), mesh, P())
        w = _sharded((64, 64), mesh, P())
        cj, compiled = _compile(self._big_dot(), x, w)
        fs, _issues = spmd_rules.check_compiled(cj, compiled, "prog")
        assert fs == []

    def test_inline_suppression(self, tmp_path):
        """ISSUE 15 satellite: the inline disable comment silences an
        spmd-level finding at the line that built the dot."""
        import importlib.util
        src = (
            "def dot(x, w):\n"
            "    return x @ w  # mxlint: disable="
            "graph-degenerate-sharding (warmup probe runs replicated "
            "by design)\n")
        p = tmp_path / "spmd_supp.py"
        p.write_text(src)
        spec = importlib.util.spec_from_file_location("_spmd_supp",
                                                      str(p))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        from jax.sharding import PartitionSpec as P
        mesh = _mesh(8, ("mp",))
        x = _sharded((1024, 1024), mesh, P())
        w = _sharded((1024, 1024), mesh, P())
        cj, compiled = _compile(mod.dot, x, w)
        fs, _issues = spmd_rules.check_compiled(cj, compiled, "prog")
        assert fs == []


# ===========================================================================
# the compilewatch hook + collective-issuing mark
# ===========================================================================
class TestSpmdHook:
    @pytest.fixture(autouse=True)
    def _gates(self, monkeypatch):
        monkeypatch.setenv("MXNET_TELEMETRY", "1")
        monkeypatch.setenv("MXNET_STATICCHECK_SPMD", "1")
        telemetry.refresh()
        staticcheck.refresh()
        telemetry.reset()
        staticcheck.reset()
        compilewatch.reset()
        yield

    def _watched_ag(self, mesh):
        from jax.sharding import NamedSharding, PartitionSpec as P

        def f(x):
            return jax.lax.with_sharding_constraint(
                x * 2.0, NamedSharding(mesh, P()))

        return compilewatch.watched_jit(f, "spmd_probe", site="test",
                                        arg_names=["x"])

    def test_hook_records_and_marks(self):
        from jax.sharding import PartitionSpec as P
        mesh = _mesh(8, ("dp",))
        w = self._watched_ag(mesh)
        assert not w.issues_collectives
        x = _sharded((1024, 512), mesh, P("dp"))
        jax.block_until_ready(w(x))
        fs = staticcheck.spmd_findings()
        assert any(f.rule == "graph-implicit-allgather"
                   and "spmd_probe" in f.path for f in fs), fs
        assert w.issues_collectives
        assert telemetry.counter(
            "mx_staticcheck_findings_total",
            rule="graph-implicit-allgather").get() > 0
        hit = [f for f in fs
               if f.rule == "graph-implicit-allgather"][0]
        assert hit.extra.get("signature")

    def test_checked_once_per_signature(self):
        from jax.sharding import PartitionSpec as P
        mesh = _mesh(8, ("dp",))
        w = self._watched_ag(mesh)
        x = _sharded((1024, 512), mesh, P("dp"))
        jax.block_until_ready(w(x))
        n = spmd_rules.programs_checked()
        assert n > 0
        jax.block_until_ready(w(x))        # cache hit: no re-check
        assert spmd_rules.programs_checked() == n
        x2 = _sharded((2048, 512), mesh, P("dp"))
        jax.block_until_ready(w(x2))       # recompile: checked again
        assert spmd_rules.programs_checked() > n

    def test_gate_off_records_nothing(self, monkeypatch):
        monkeypatch.setenv("MXNET_STATICCHECK_SPMD", "0")
        staticcheck.refresh()
        from jax.sharding import PartitionSpec as P
        mesh = _mesh(8, ("dp",))
        w = self._watched_ag(mesh)
        x = _sharded((1024, 512), mesh, P("dp"))
        jax.block_until_ready(w(x))
        assert staticcheck.spmd_findings() == []
        assert not w.issues_collectives

    def test_level2_gate_does_not_enable_level4(self, monkeypatch):
        monkeypatch.setenv("MXNET_STATICCHECK", "1")
        monkeypatch.setenv("MXNET_STATICCHECK_SPMD", "0")
        staticcheck.refresh()
        from jax.sharding import PartitionSpec as P
        mesh = _mesh(8, ("dp",))
        w = self._watched_ag(mesh)
        jax.block_until_ready(w(_sharded((1024, 512), mesh, P("dp"))))
        assert staticcheck.spmd_findings() == []


# ===========================================================================
# collective-interleave (Level 3 x Level 4)
# ===========================================================================
class TestInterleaveChecker:
    def _checker(self):
        return race.RaceChecker()

    def test_two_unsanctioned_collectives_flagged(self):
        ck = self._checker()
        ck.on_push(1, "serve.batch", "a.py:1", (), (),
                   collective={"program": "serve.forward (A)",
                               "lock": None})
        ck.on_push(2, "serve.batch", "b.py:2", (), (),
                   collective={"program": "serve.forward (B)",
                               "lock": None})
        fs = ck.findings()
        assert _rules(fs) == ["collective-interleave"]
        assert "serve.forward (A)" in fs[0].message
        assert "serve.forward (B)" in fs[0].message
        assert "a.py:1" in fs[0].message and "b.py:2" in fs[0].message

    def test_shared_lock_sanctioned(self):
        ck = self._checker()
        tag = {"program": "serve.forward (A)", "lock": 42}
        ck.on_push(1, "serve.batch", "a.py:1", (), (), collective=tag)
        ck.on_push(2, "serve.batch", "a.py:1", (), (), collective=tag)
        assert ck.findings() == []

    def test_different_locks_flagged(self):
        ck = self._checker()
        ck.on_push(1, "serve.batch", "a.py:1", (), (),
                   collective={"program": "A", "lock": 1})
        ck.on_push(2, "serve.batch", "b.py:2", (), (),
                   collective={"program": "B", "lock": 2})
        assert _rules(ck.findings()) == ["collective-interleave"]

    def test_declared_edge_orders_them(self):
        ck = self._checker()
        ck.on_push(1, "p1", "a.py:1", (), (101,),
                   collective={"program": "A", "lock": None})
        # reads what op 1 writes: a declared happens-before edge
        ck.on_push(2, "p2", "b.py:2", (101,), (),
                   collective={"program": "B", "lock": None})
        assert ck.findings() == []

    def test_completed_op_not_in_flight(self):
        ck = self._checker()
        ck.on_push(1, "p1", "a.py:1", (), (),
                   collective={"program": "A", "lock": None})
        ck.on_done(1)
        ck.on_push(2, "p2", "b.py:2", (), (),
                   collective={"program": "B", "lock": None})
        assert ck.findings() == []

    def test_non_collective_pushes_ignored(self):
        ck = self._checker()
        ck.on_push(1, "p1", "a.py:1", (), ())
        ck.on_push(2, "p2", "b.py:2", (), (),
                   collective={"program": "B", "lock": None})
        assert ck.findings() == []

    def test_evicted_op_still_clears_on_done(self, monkeypatch):
        """Review fix: an op whose happens-before record was
        FIFO-evicted (watching() False) must still clear its in-flight
        collective mark at completion — the engine calls on_done for
        EVERY op while the hook is installed, so a long-lived batch
        never becomes a phantom that false-positives forever."""
        monkeypatch.setattr(race, "_OPS_CAP", 4)
        ck = self._checker()
        ck.on_push(1, "long_batch", "a.py:1", (), (),
                   collective={"program": "A", "lock": None})
        for t in range(2, 10):          # evict token 1's record
            ck.on_push(t, "filler", "f.py:1", (), ())
        assert not ck.watching(1)
        ck.on_done(1)                   # completes AFTER eviction
        ck.on_push(99, "next_batch", "b.py:2", (), (),
                   collective={"program": "B", "lock": None})
        assert ck.findings() == []


def _native_available():
    from mxnet_tpu.engine import native_or_none
    return native_or_none() is not None


_needs_native = pytest.mark.skipif(
    not _native_available(), reason="native dependency engine unavailable")


@_needs_native
class TestServeInterleaveEndToEnd:
    """Acceptance (ISSUE 15): the collective-interleave rule flags the
    PR-12 serve scenario when the exec-lock sanction is removed
    (deterministic via the engine_collective_overlap fault site) and
    stays SILENT with the lock in place."""

    @pytest.fixture(autouse=True)
    def _gates(self, monkeypatch):
        monkeypatch.setenv("MXNET_TELEMETRY", "1")
        monkeypatch.setenv("MXNET_STATICCHECK_SPMD", "1")
        monkeypatch.setenv("MXNET_ENGINE_RACE_CHECK", "1")
        telemetry.refresh()
        staticcheck.refresh()
        telemetry.reset()
        staticcheck.reset()
        compilewatch.reset()
        yield

    def _session(self):
        from jax.sharding import PartitionSpec as P
        mesh = _mesh(8, ("mp",))
        mx.random.seed(7)
        net = nn.HybridSequential()
        net.add(nn.Dense(16, in_units=16, activation="relu"),
                nn.Dense(8))
        net.initialize()
        x = nd.ones((2, 16))
        # shard the first weight over the CONTRACTION dim: GSPMD must
        # insert an all-reduce, so the program IS collective-issuing
        specs, _w = _first_weight_spec(net, P(None, "mp"))
        sess = net.serve_session(x, max_batch=2, mesh=mesh,
                                 param_specs=specs)
        sess.warmup()
        return sess

    def _two_inflight_batches(self, sess):
        from mxnet_tpu.serve.scheduler import Scheduler
        sched = Scheduler(sess, max_wait_ms=1, inflight=2)
        xs = np.random.rand(1, 16).astype(np.float32)
        futs = []
        # hold the session's exec lock so batch 1 BLOCKS inside the
        # engine op; batch 2 is then pushed while batch 1 is still in
        # flight — the overlap is deterministic, not a thread race
        assert sess._exec_lock is not None
        assert sess._exec_lock.acquire(timeout=30)
        try:
            futs.append(sched.submit(xs, tenant="a"))
            deadline = time.time() + 10
            while sched.inflight < 1 and time.time() < deadline:
                time.sleep(0.01)
            assert sched.inflight >= 1
            futs.append(sched.submit(xs, tenant="b"))
            deadline = time.time() + 10
            while sched.inflight < 2 and time.time() < deadline:
                time.sleep(0.01)
            assert sched.inflight == 2
        finally:
            sess._exec_lock.release()
        for f in futs:
            f.result(timeout=30)
        sched.close()

    def test_lock_stripped_names_both_programs(self):
        sess = self._session()
        tag = sess.collective_tag()
        assert tag is not None and tag["lock"] is not None
        assert "serve.forward" in tag["program"]
        faultinject.set_fault("engine_collective_overlap", prob=1.0)
        try:
            self._two_inflight_batches(sess)
            fired = faultinject.fires("engine_collective_overlap")
        finally:
            faultinject.clear()
        assert fired >= 2
        fs = [f for f in staticcheck.race_findings()
              if f.rule == "collective-interleave"]
        assert len(fs) == 1, staticcheck.race_findings()
        assert fs[0].message.count("serve.forward") == 2
        assert "serve.batch" in fs[0].message
        assert "deadlock" in fs[0].message

    def test_lock_in_place_stays_silent(self):
        sess = self._session()
        self._two_inflight_batches(sess)
        assert [f for f in staticcheck.race_findings()
                if f.rule == "collective-interleave"] == []

    def test_single_device_session_has_no_tag(self):
        net = nn.HybridSequential()
        net.add(nn.Dense(8, in_units=16))
        net.initialize()
        sess = net.serve_session(nd.ones((2, 16)), max_batch=2)
        sess.warmup()
        assert sess.collective_tag() is None


# ===========================================================================
# SELF-LINT: the stack's own SPMD programs compile clean under Level 4
# ===========================================================================
class TestSelfLintClean:
    @pytest.fixture(autouse=True)
    def _gates(self, monkeypatch):
        monkeypatch.setenv("MXNET_TELEMETRY", "1")
        monkeypatch.setenv("MXNET_STATICCHECK_SPMD", "1")
        telemetry.refresh()
        staticcheck.refresh()
        telemetry.reset()
        staticcheck.reset()
        compilewatch.reset()
        yield

    def _train_steps(self, ctxs, steps=2):
        mx.random.seed(5)
        np.random.seed(5)
        net = nn.HybridSequential()
        net.add(nn.Dense(5, in_units=7), nn.Dense(3))
        net.initialize(ctx=ctxs, init=mx.initializer.Xavier())
        net(nd.ones((2, 7), ctx=ctxs[0]))
        tr = gluon.Trainer(net.collect_params(), "sgd",
                           {"learning_rate": 0.05}, kvstore="device")
        rng = np.random.RandomState(11)
        for _ in range(steps):
            x = rng.rand(8, 7).astype(np.float32)
            y = rng.rand(8, 3).astype(np.float32)
            xs = gluon.utils.split_and_load(nd.array(x), ctxs)
            ys = gluon.utils.split_and_load(nd.array(y), ctxs)
            with autograd.record():
                losses = [((net(a) - b) ** 2).sum()
                          for a, b in zip(xs, ys)]
            for l in losses:
                l.backward()
            tr.step(8)
        nd.waitall()

    def test_zero_programs_clean(self, monkeypatch):
        monkeypatch.setenv("MXNET_ZERO", "1")
        _ndev(8)
        self._train_steps([mx.tpu(i) for i in range(8)])
        assert spmd_rules.programs_checked() > 0
        assert staticcheck.spmd_findings() == [], \
            staticcheck.spmd_findings()

    def test_quantized_kvstore_programs_clean(self, monkeypatch):
        monkeypatch.setenv("MXNET_KVSTORE_QUANTIZE", "int8")
        _ndev(8)
        self._train_steps([mx.tpu(i) for i in range(8)])
        assert spmd_rules.programs_checked() > 0
        assert staticcheck.spmd_findings() == [], \
            staticcheck.spmd_findings()

    def test_reshard_transition_programs_clean(self):
        """ISSUE 16: the elastic-topology transition programs (flat
        fragment stack + general NamedSharding redistribute) are
        statically validated by shardcheck before first run and
        compile clean."""
        from jax.sharding import NamedSharding, PartitionSpec as P
        from mxnet_tpu.parallel import reshard as rs
        devs = _ndev(8)
        n0 = spmd_rules.programs_checked()
        data = np.random.rand(131).astype(np.float32)
        src = rs.FragLayout.build(131, 8, 2)
        dst = rs.FragLayout.build(131, 4)
        bufs = rs.place_from_host([(data, src)], 8, src.frag, devs,
                                  np.float32)
        out = rs.reshard_fragments(bufs, rs.plan_moves(src, dst), 4,
                                   dst.frag, devs[:4])
        np.testing.assert_array_equal(
            rs.gather_to_host(out, [dst])[0], data)
        x = jax.device_put(np.random.rand(24, 3).astype(np.float32),
                           NamedSharding(_mesh(8), P("dp")))
        rs.redistribute(x, NamedSharding(_mesh(4), P("dp")))
        assert spmd_rules.programs_checked() > n0
        assert staticcheck.spmd_findings() == [], \
            staticcheck.spmd_findings()

    def test_sharded_serving_clean(self):
        from jax.sharding import PartitionSpec as P
        mesh = _mesh(8, ("mp",))
        mx.random.seed(3)
        net = nn.HybridSequential()
        net.add(nn.Dense(16, in_units=16, activation="relu"),
                nn.Dense(8))
        net.initialize()
        x = nd.ones((2, 16))
        specs, _w = _first_weight_spec(net, P(None, "mp"))
        sess = net.serve_session(x, max_batch=2, mesh=mesh,
                                 param_specs=specs)
        sess.warmup()
        sess.infer(np.random.rand(2, 16).astype(np.float32))
        assert spmd_rules.programs_checked() > 0
        assert staticcheck.spmd_findings() == [], \
            staticcheck.spmd_findings()
