"""Kernel auto-tuner (round 7, ISSUE 14; mxnet_tpu/autotune.py):
cost-mode determinism, VMEM feasibility, cache round-trip, off-path
identity, measured-gate discipline, bogus-cache fallback."""
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mxnet_tpu import autotune


@pytest.fixture(autouse=True)
def _isolated(monkeypatch):
    """Each test starts from an empty in-memory table, off mode and no
    cache file."""
    monkeypatch.delenv("MXNET_AUTOTUNE", raising=False)
    monkeypatch.delenv("MXNET_AUTOTUNE_CACHE", raising=False)
    monkeypatch.setenv("MXNET_PALLAS_INTERPRET", "1")
    autotune.clear()
    yield
    autotune.clear()


def _cands(vmems, builds=None):
    out = []
    for i, vm in enumerate(vmems):
        out.append(autotune.Candidate(
            {"block": 8 << i}, flops=1e6, hbm_bytes=1e6 * (i + 1),
            vmem_bytes=vm,
            build=None if builds is None else builds[i]))
    return out


def test_off_mode_returns_default_untouched():
    default = {"block": 123}
    out = autotune.lookup("k", {"M": 4}, default,
                          candidates=lambda: _cands([1, 1, 1]))
    assert out == default
    assert autotune.table() == {}            # nothing consulted/stored


def test_cost_mode_deterministic_and_vmem_feasible(monkeypatch):
    monkeypatch.setenv("MXNET_AUTOTUNE", "cost")
    # candidates 0/1 blow the VMEM budget; 2 is the only feasible one
    big = autotune._VMEM_BUDGET + 1
    out = autotune.lookup("k", {"M": 4}, {"block": 999},
                          candidates=lambda: _cands([big, big, 64]))
    assert out == {"block": 32}
    # the same signature answers from the table (candidates not
    # re-enumerated: a raising enumerator proves it)
    out2 = autotune.lookup("k", {"M": 4}, {"block": 999},
                           candidates=lambda: 1 / 0)
    assert out2 == {"block": 32}
    # a second process-equivalent (cleared table) re-derives the same
    # answer — the cost ranking is deterministic
    autotune.clear()
    out3 = autotune.lookup("k", {"M": 4}, {"block": 999},
                           candidates=lambda: _cands([big, big, 64]))
    assert out3 == out


def test_cost_mode_ranks_on_roofline(monkeypatch):
    monkeypatch.setenv("MXNET_AUTOTUNE", "cost")
    # equal FLOPs, increasing HBM bytes -> the first (lowest-traffic)
    # candidate wins; ties break on candidate order
    out = autotune.lookup("k2", {"M": 4}, {"block": 999},
                          candidates=lambda: _cands([1, 1, 1]))
    assert out == {"block": 8}


def test_all_infeasible_falls_back_to_default(monkeypatch):
    monkeypatch.setenv("MXNET_AUTOTUNE", "cost")
    big = autotune._VMEM_BUDGET + 1
    default = {"block": 42}
    out = autotune.lookup("k3", {"M": 4}, default,
                          candidates=lambda: _cands([big, big, big]))
    assert out == default


def test_cache_round_trip(tmp_path, monkeypatch):
    cache = str(tmp_path / "tune.json")
    monkeypatch.setenv("MXNET_AUTOTUNE", "cost")
    monkeypatch.setenv("MXNET_AUTOTUNE_CACHE", cache)
    out = autotune.lookup("k4", {"M": 8}, {"block": 999},
                          candidates=lambda: _cands([1, 1, 1]))
    assert os.path.exists(cache)
    with open(cache) as f:
        data = json.load(f)
    key = autotune.entry_key("k4", {"M": 8})
    assert data[key]["params"] == out
    # a fresh process (cleared table) serves from the file WITHOUT
    # re-tuning
    autotune.clear()
    out2 = autotune.lookup("k4", {"M": 8}, {"block": 999},
                           candidates=lambda: 1 / 0)
    assert out2 == out


def test_bogus_cache_entry_falls_back(tmp_path, monkeypatch):
    """A stale/hand-edited table entry that fails the consumer's
    validation degrades to the default — never crashes the kernel
    build."""
    cache = str(tmp_path / "tune.json")
    key = autotune.entry_key("k5", {"M": 8})
    with open(cache, "w") as f:
        json.dump({key: {"params": {"block": -7}, "mode": "cost",
                         "score": 0.0}}, f)
    monkeypatch.setenv("MXNET_AUTOTUNE", "cost")
    monkeypatch.setenv("MXNET_AUTOTUNE_CACHE", cache)
    default = {"block": 64}
    out = autotune.lookup(
        "k5", {"M": 8}, default,
        candidates=lambda: _cands([1]),
        validate=lambda p: isinstance(p.get("block"), int)
        and p["block"] > 0)
    assert out == default
    # unreadable file: same degradation
    with open(cache, "w") as f:
        f.write("{not json")
    autotune.clear()
    out2 = autotune.lookup("k6", {"M": 8}, default)
    assert out2 == default


def test_measure_mode_keeps_default_unless_beaten(monkeypatch):
    """EQuARX-style measured gate: the tuned candidate must beat the
    incumbent default on the paired median or the table keeps the
    default."""
    monkeypatch.setenv("MXNET_AUTOTUNE", "measure")

    def fake_build():
        x = jnp.zeros((8,), jnp.float32)
        return (lambda x: x + 1.0), (x,)

    default = {"block": 8}
    cands = [autotune.Candidate(default, vmem_bytes=1,
                                build=fake_build),
             autotune.Candidate({"block": 16}, vmem_bytes=1,
                                build=fake_build)]
    # candidate loses the measurement -> default kept
    monkeypatch.setattr(autotune, "_measure", lambda c, b, **kw: 1.5)
    out = autotune.lookup("k7", {"M": 1}, default,
                          candidates=lambda: list(cands))
    assert out == default
    # candidate wins -> candidate recorded
    autotune.clear()
    monkeypatch.setattr(autotune, "_measure", lambda c, b, **kw: 0.5)
    out2 = autotune.lookup("k7", {"M": 1}, default,
                           candidates=lambda: list(cands))
    assert out2 == {"block": 16}
    assert autotune.table()[autotune.entry_key(
        "k7", {"M": 1})]["mode"] == "measure"


def test_measure_mode_default_absent_keeps_default(monkeypatch):
    """When the grid does not carry the incumbent default there is
    nothing to measure against — the gate keeps the default instead of
    adopting the cost winner unvetted (review fix)."""
    monkeypatch.setenv("MXNET_AUTOTUNE", "measure")
    default = {"block": 999}               # not in the grid
    out = autotune.lookup("k7b", {"M": 1}, default,
                          candidates=lambda: _cands([1, 1]))
    assert out == default


def test_probe_compile_failure_disqualifies(monkeypatch):
    """A candidate whose probe program cannot compile must never be
    selected — the consumer would hit the same failure on the real
    kernel build (review fix)."""
    monkeypatch.setenv("MXNET_AUTOTUNE", "cost")

    def boom():
        raise RuntimeError("mosaic says no")

    cands = [autotune.Candidate({"block": 8}, flops=1, hbm_bytes=1,
                                vmem_bytes=1, build=boom),
             autotune.Candidate({"block": 16}, flops=1, hbm_bytes=2,
                                vmem_bytes=1)]
    out = autotune.lookup("k9", {"M": 1}, {"block": 99},
                          candidates=lambda: list(cands))
    assert out == {"block": 16}
    # every candidate failing -> default
    autotune.clear()
    out2 = autotune.lookup(
        "k9", {"M": 1}, {"block": 99},
        candidates=lambda: [autotune.Candidate(
            {"block": 8}, vmem_bytes=1, build=boom)])
    assert out2 == {"block": 99}


def test_tuned_rows_rejects_bogus_cache_entry(tmp_path, monkeypatch):
    """The shared row-block consult re-validates cache entries against
    the SAME sublane-floor/VMEM rules as a fresh pick — a stale entry
    can degrade perf but never crash a kernel build (review fix)."""
    M, C, esize = 256, 64, 2               # bf16: floor is 16 rows
    for bogus in (8,                       # below the bf16 floor
                  10 ** 6):                # blows the VMEM budget
        cache = str(tmp_path / ("tune_%d.json" % bogus))
        key = autotune.entry_key("rb", {"M": M, "C": C,
                                        "esize": esize})
        with open(cache, "w") as f:
            json.dump({key: {"params": {"block_rows": bogus},
                             "mode": "cost", "score": 0.0}}, f)
        monkeypatch.setenv("MXNET_AUTOTUNE", "cost")
        monkeypatch.setenv("MXNET_AUTOTUNE_CACHE", cache)
        autotune.clear()
        bm = autotune.tuned_rows("rb", M, C, esize, 64,
                                 C * (3 * esize + 16))
        assert bm == 64


def test_attention_cost_mode_prefers_large_head_blocks(monkeypatch):
    """All divisor candidates share the same analytic roofline, so the
    tie must break toward FEWER grid steps — cost mode picking
    block_heads=1 would be the pessimal choice (review fix)."""
    monkeypatch.setenv("MXNET_AUTOTUNE", "cost")
    from mxnet_tpu.ops.pallas_attention import selfatt_plan
    plan = selfatt_plan(16, 12, 2, 0.0)
    assert plan is not None
    assert plan["bbh"] >= 6                # 12 or a padded 16 — not 1


def test_attention_cost_mode_above_the_default_vmem_limit(monkeypatch):
    """A block that states its own VMEM limit (ISSUE 39) is gated by
    the kernel's budget, not the tuner's: at 256 positions of 12 x 64
    the twelve heads a step reckon over the tuner's 10 MB, and cost
    mode must not settle for the two that reckon under it."""
    monkeypatch.setenv("MXNET_AUTOTUNE", "cost")
    from mxnet_tpu.ops import pallas_attention
    plan = pallas_attention.selfatt_plan(256, 12, 1, 0.0)
    assert pallas_attention._block_bytes(
        plan["bbh"], 256, 64, 2, 5) * 2 > autotune._VMEM_BUDGET
    assert plan["bbh"] == 12


def test_bad_mode_string_is_off(monkeypatch):
    monkeypatch.setenv("MXNET_AUTOTUNE", "turbo")
    assert autotune.mode() == "off"
    out = autotune.lookup("k8", {}, {"block": 1},
                          candidates=lambda: 1 / 0)
    assert out == {"block": 1}


def test_layer_norm_consult_off_path_bitwise(monkeypatch):
    """The LN kernel consults the tuner; off mode is byte-identical to
    the explicit-default call, and cost mode picks a block that still
    divides the rows (validation holds on a poisoned table)."""
    from mxnet_tpu.ops.pallas_norm import _pick_rows, pallas_layer_norm
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(256, 64).astype(np.float32))
    g = jnp.asarray(rng.rand(64).astype(np.float32) + 0.5)
    b = jnp.asarray(rng.randn(64).astype(np.float32))
    o_off = pallas_layer_norm(x, g, b)
    bm_default = _pick_rows(256, 64, 4, 2)
    o_explicit = pallas_layer_norm(x, g, b, block_rows=bm_default)
    assert bool(jnp.all(o_off == o_explicit))
    assert autotune.table() == {}
    monkeypatch.setenv("MXNET_AUTOTUNE", "cost")
    o_cost = pallas_layer_norm(x, g, b)
    t = autotune.table()
    assert any("pallas_layer_norm" in k for k in t)
    for k, v in t.items():
        if "pallas_layer_norm" in k:
            assert 256 % v["params"]["block_rows"] == 0
    np.testing.assert_allclose(np.asarray(o_cost), np.asarray(o_off),
                               rtol=1e-6, atol=1e-6)


def test_ce_chunk_consult(monkeypatch):
    """chunked CE consults the tuner for its chunk size; off mode uses
    the env default, cost mode records a valid chunk and the losses
    agree (chunking is value-preserving by construction)."""
    from mxnet_tpu.ops.contrib_ops import chunked_lm_head_ce
    rng = np.random.RandomState(1)
    T, U, V = 32, 16, 3000
    h = jnp.asarray(rng.randn(T, U).astype(np.float32))
    w = jnp.asarray((rng.randn(V, U) * 0.05).astype(np.float32))
    b = jnp.asarray(np.zeros(V, np.float32))
    lab = jnp.asarray(rng.randint(0, V, (T,)).astype(np.int32))
    loss_off = chunked_lm_head_ce(h, w, b, lab)
    assert autotune.table() == {}
    monkeypatch.setenv("MXNET_AUTOTUNE", "cost")
    loss_cost = chunked_lm_head_ce(h, w, b, lab)
    t = autotune.table()
    assert any("chunked_lm_head_ce" in k for k in t)
    for k, v in t.items():
        if "chunked_lm_head_ce" in k:
            assert v["params"]["chunk"] >= 1
    np.testing.assert_allclose(np.asarray(loss_cost),
                               np.asarray(loss_off),
                               rtol=2e-5, atol=2e-5)


def test_attention_plan_consult_stable(monkeypatch):
    """selfatt_plan consults the tuner in cost mode; repeated calls
    answer from the table with the same geometry (the zero-recompile
    invariant: a signature's constants never flip mid-process)."""
    monkeypatch.setenv("MXNET_AUTOTUNE", "cost")
    from mxnet_tpu.ops.pallas_attention import selfatt_plan
    p1 = selfatt_plan(16, 4, 4, 0.0)
    p2 = selfatt_plan(16, 4, 4, 0.0)
    assert p1 == p2 and p1 is not None
    key = autotune.entry_key(
        "pallas_selfatt_packed",
        {"L": 16, "heads": 4, "batch": 4, "esize": 2, "hd": 64})
    assert key in autotune.table()
