"""The nemotron_twotower_30b_a3b configuration's benchmark files: the
cell's own check in float32 at toy widths (that it catches a wrong
scaling factor, a missing skip term and a wrong optimizer, that what it
checked is the object that goes on into the window, and that its
control, the reference with bf16 masters, comes out wrong at the cell's
own limits), the ``token_rows`` feed, the
model's and the scopes' FLOP counts, the configuration file against
the catalog row, and ``mxbench/scopes.py`` with its seven readers."""
import json
import os
import types

import numpy as np
import pytest

from mxbench import manifest, run as mxrun, scopes, trace as T

CELL = "nemotron_twotower_30b_a3b_pretrain_s8192"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW_READERS = ["ssd_scan_ms.train", "moe_experts_ms.train",
               "causal_attn_ms.train", "ssd_scan_roofline_pct.train",
               "moe_experts_roofline_pct.train",
               "causal_attn_roofline_pct.train",
               "moe_load_max_over_mean.train"]


SCOPES = manifest.config("nemotron_twotower_30b_a3b")[1].SCOPES


def _ctx(loss_rtol=1e-5, seed=5):
    ctx, gen, _ = mxrun.context(CELL, seed=seed, seconds=0.0, trace=False,
                                rehearse=True)
    ctx.sizes = dict(ctx.sizes, compute_dtype="float32")
    ctx.sizes["check"] = dict(ctx.sizes["check"], loss_rtol=loss_rtol,
                              drop_rtol=2e-3)
    return ctx, gen


def _checked(ctx, gen):
    batch = ctx.traffic["batch_per_chip"] * len(ctx.devices)
    return gen.checked_loop(ctx, batch, ctx.traffic["seq"])


def test_losses_after_one_and_two_updates_match():
    """The cell's own check in float32 with tight tolerances: the loss
    before any update and after one AdamW update (backward + the
    optimizer, weight decay and the frozen router bias included)."""
    ctx, gen = _ctx()
    loop, ok = _checked(ctx, gen)
    assert ok
    # what was checked is what goes on: the instance has taken the
    # check's steps, and its compiled step is the window's
    assert int(loop.step_obj._t) == ctx.sizes["check"]["steps"] == 2
    assert loop.weights is None


def _skew(ctx, name, wrong):
    real = getattr(ctx.refmod, name)
    setattr(ctx.refmod, name, lambda *a, **k: wrong(real, *a, **k))


def test_a_wrong_scaling_factor_fails_the_check():
    ctx, gen = _ctx()
    _skew(ctx, "route", lambda real, w, p, x, cfg: real(
        w, p, x, dict(cfg, routed_scaling_factor=1.0)))
    assert not _checked(ctx, gen)[1]


def test_a_missing_skip_term_fails_the_check():
    ctx, gen = _ctx()
    _skew(ctx, "recurrence", lambda real, x, dt, a, bm, cm, d: real(
        x, dt, a, bm, cm, 0.0 * d))
    assert not _checked(ctx, gen)[1]


@pytest.mark.parametrize("key, wrong", [("epsilon", 1e-3), ("wd", 0.01),
                                        ("lr", 6e-4)])
def test_a_wrong_optimizer_fails_the_check(key, wrong):
    ctx, gen = _ctx()
    _skew(ctx, "train_losses", lambda real, w, b, s, o, n: real(
        w, b, s, dict(o, **{key: wrong}), n))
    assert not _checked(ctx, gen)[1]


@pytest.mark.parametrize("seed", [5, 3_000_000_019])
def test_the_check_passes_the_system_and_fails_its_control(seed):
    """The system at the configuration's precision (bf16 compute on
    float32 masters) and the control (the reference with bf16 masters)
    through the same comparison, at the limits the configuration's file
    gives: the system passes, the control fails, by the change over an
    update (toy widths: under 0.3% against about 2.5%; published
    widths on the chip: at most 0.62% against 1.5-1.95%) and not by the
    first loss."""
    ctx, gen, _ = mxrun.context(CELL, seed=seed, seconds=0.0, trace=False,
                                rehearse=True)
    assert ctx.sizes["compute_dtype"] == "bfloat16"
    assert ctx.sizes["check"]["drop_rtol"] == 0.01
    assert _checked(ctx, gen)[1]
    batch = ctx.traffic["batch_per_chip"] * len(ctx.devices)
    ok, first, drop = gen.control(ctx, batch, ctx.traffic["seq"])
    assert not ok
    assert first <= ctx.sizes["check"]["loss_rtol"]
    assert drop > ctx.sizes["check"]["drop_rtol"]


@pytest.mark.parametrize("got, ok", [
    ([10.0, 6.0], True), ([10.009, 6.009], True), ([10.011, 6.011], False),
    ([10.0, 6.19], True), ([10.0, 6.21], False), ([10.0, 5.79], False),
    ([10.0, float("nan")], False)])
def test_agree_holds_the_first_loss_and_the_change(got, ok):
    gen = manifest.traffic("pretrain_clm_s8192")[1]
    chk = {"loss_rtol": 1e-3, "drop_rtol": 0.05}
    assert gen.agree(got, [10.0, 6.0], chk)[0] is ok


# ---------------------------------------------------------------------------
@pytest.mark.parametrize("seed", [0, 7, 3_000_000_019])
def test_token_rows_are_a_function_of_the_seed(seed):
    traffic, gen = manifest.traffic("pretrain_clm_s8192")
    traffic = dict(traffic, **traffic["toy"])
    ctx = types.SimpleNamespace(traffic=traffic, sizes={"vocab_size": 512},
                                seed=seed)
    a, b = gen.TokenRowsFeed(ctx, 2, 32), gen.TokenRowsFeed(ctx, 2, 32)
    seen = []
    for _ in range(5):      # more than one pass over the pool of 8
        (ids, labels), other = a.host_batch(), b.host_batch()
        np.testing.assert_array_equal(ids, other[0])
        np.testing.assert_array_equal(labels, other[1])
        assert ids.shape == labels.shape == (2, 32) and ids.dtype == np.int32
        assert 0 <= min(ids.min(), labels.min())
        assert max(ids.max(), labels.max()) < 512
        np.testing.assert_array_equal(ids[:, 1:], labels[:, :-1])  # shifted
        seen.append(ids.copy())
    assert not np.array_equal(seen[0], seen[1])         # a fresh batch
    np.testing.assert_array_equal(seen[0], seen[4])     # wraps at 8 / 2
    ctx.seed = seed + 1
    assert not np.array_equal(seen[0],
                              gen.TokenRowsFeed(ctx, 2, 32).host_batch()[0])
    check = gen.TokenRowsFeed(ctx, 2, 32, check=True)
    assert check.rows.shape == (2, 33)


# ---------------------------------------------------------------------------
def test_model_flops_and_what_the_scopes_execute():
    sizes, cfgmod, _ = manifest.config("nemotron_twotower_30b_a3b")
    # multiply-adds a token: Mamba-2 4 x 40,435,712, experts 4 x
    # 24,041,472 (shared 19,955,712, routed 6 x 8/128 x 9,977,856),
    # attention 56,950,784 (causal half), head 44,040,192; x 2 x 3 x 8192
    assert cfgmod._mamba_macs(sizes) == 40_435_712
    assert cfgmod._moe_macs(sizes) == 24_041_472
    assert cfgmod._attn_macs(sizes, 8192) == 56_950_784
    assert cfgmod.train_flops_per_sample(sizes, 8192) == 358_899_712 * 6 * 8192
    assert cfgmod.expert_capacity(sizes, 8192) == 20 * 512
    assert cfgmod.expert_capacity(sizes, 64) == 14 * 8
    costs = cfgmod.scope_costs(sizes, 8192, 1)
    assert set(costs) == {"mx.mamba2.ssd", "mx.moe.experts", "mx.attn.causal"}
    # 7 products of the buffer's 20 blocks of 512 rows x 2688 x 1856,
    # 4 layers
    assert costs["mx.moe.experts"][0] == 4 * 7 * 2 * 10240 * 2688 * 1856
    # 7 products over sum_i 512 x 512 i pairs, 32 heads of 128
    assert costs["mx.attn.causal"][0] == 7 * 2 * 512 * 512 * 136 * 32 * 128
    for flops, nbytes in costs.values():
        assert flops > 0 and nbytes > 0
    twice = cfgmod.scope_costs(sizes, 8192, 2)
    assert twice["mx.attn.causal"][0] == 2 * costs["mx.attn.causal"][0]


def test_configuration_keeps_every_published_key():
    sizes = manifest.load_json("configs", "nemotron_twotower_30b_a3b.json")
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog here")
    with open(CATALOG) as f:
        (row,) = [r for r in map(json.loads, f)
                  if r["source_url"] == sizes["source"]]
    for key, value in row["config"].items():
        if key not in sizes["reduced"]:
            assert sizes[key] == value, key
    assert sizes["reduced"] == ["num_hidden_layers", "n_routed_experts",
                                "vocab_size", "towers"]
    assert sizes["published"]["n_routed_experts"] \
        == row["config"]["n_routed_experts"] \
        == sizes["deployment"]["router_experts"]
    assert sizes["vocab_size"] * 8 == row["config"]["vocab_size"]
    assert set(sizes["reduced"]) == set(sizes["reduced_why"])
    # a whole period and four layers, all three kinds
    pattern = sizes["hybrid_override_pattern"][:sizes["num_hidden_layers"]]
    assert pattern == "MEMEM*EME"


# ---------------------------------------------------------------------------
@pytest.mark.parametrize("op_name, want", [
    ("jit(fused_step)/jit(main)/mx.mamba2/checkpoint/mx.mamba2.ssd/"
     "dot_general", "mx.mamba2.ssd"),
    ("jit(fused_step)/transpose(jvp(mx.mamba2))/rematted_computation/mul",
     "mx.mamba2"),
    ("jit(fused_step)/transpose(jvp(mx.moe))/checkpoint/mx.moe.experts/"
     "gather", "mx.moe.experts"),
    ("jit(fused_step)/jvp(mx.moe)/top_k", "mx.moe"),
    ("jit(fused_step)/transpose(jvp(mx.attn.causal))/checkpoint/exp",
     "mx.attn.causal"),
    ("jit(fused_step)/jvp(chunked_lm_head_ce)/while/body/dot_general", None),
    ("jit(fused_step)/mx.moe.experts_of_another_kind/add", None),
])
def test_scope_of_an_op_name(op_name, want):
    assert scopes.scope_of(op_name, SCOPES) == want


HLO = '''
HloModule jit_fused_step
%fused_computation.5 (p: bf16[8]) -> bf16[8] {
  %dot.9 = bf16[8] dot(%p, %p), metadata={op_name="jit(fused_step)/mx.mamba2/mx.mamba2.ssd/dot_general"}
}
ENTRY %main {
  %fusion.5 = bf16[8]{0} fusion(%a), kind=kOutput, calls=%fused_computation.5, metadata={op_name="jit(fused_step)/transpose(jvp(mx.mamba2))/mx.mamba2.ssd/dot_general" source_file="x.py"}
  %fusion.6 = bf16[8]{0} fusion(%a), kind=kLoop, metadata={op_name="jit(fused_step)/jvp(mx.mamba2)/mul"}
  %conditional.2 = bf16[8]{0} conditional(%p, %a, %a), metadata={op_name="jit(fused_step)/jvp(mx.moe)/mx.moe.experts/cond"}
  %gather.3 = bf16[8]{0} gather(%a, %i), metadata={op_name="jit(fused_step)/jvp(mx.moe)/mx.moe.experts/cond/branch_0_fun/gather"}
  ROOT %copy.1 = bf16[8]{0} copy(%a), metadata={op_name="jit(fused_step)/adamw"}
  %while.4 = bf16[8]{0} while(%a), body=%b, metadata={op_name="jit(fused_step)/chunked_lm_head_ce/while"}
}
'''


def _trace():
    def ev(line, start, end):
        return T.Op(line, start, end)
    ops = [ev("%fusion.5 = bf16[8]{0} fusion(%a), kind=kOutput", 0, 40),
           ev("%fusion.6 = bf16[8]{0} fusion(%a), kind=kLoop", 40, 50),
           # a container beside what it runs: not counted
           ev("%conditional.2 = bf16[8]{0} conditional(%p, %a, %a)", 50, 80),
           ev("%gather.3 = bf16[8]{0} gather(%a, %i)", 55, 70),
           # made by the compiler, no op_name of the program's: its
           # container's scope
           ev("%ragged-dot-none.1 = f32[8]{0} custom-call(%a)", 70, 75),
           ev("%copy.1 = bf16[8]{0} copy(%a)", 80, 90),
           ev("%fusion.5 = bf16[8]{0} fusion(%a), kind=kOutput", 95, 135)]
    spans = [T.Op("mxbench/step", 0, 60), T.Op("mxbench/step", 60, 120)]
    return T.Trace({0: T.Device(ops, [], [])}, spans)


def test_scope_map_and_seconds_by_scope():
    found = scopes.scope_map(HLO, SCOPES)
    assert found == {"dot.9": "mx.mamba2.ssd", "fusion.5": "mx.mamba2.ssd",
                     "fusion.6": "mx.mamba2", "conditional.2": "mx.moe.experts",
                     "gather.3": "mx.moe.experts"}
    trace = _trace()
    window = T.window_of(trace)
    assert window == (0, 120)
    seconds = scopes.seconds_by_scope(trace, 0, window, found)
    assert seconds == pytest.approx({"mx.mamba2.ssd": 65e-9,   # 40 + 25 cut
                                     "mx.mamba2": 10e-9,
                                     "mx.moe.experts": 20e-9})
    assert scopes.with_parents(seconds, SCOPES) == pytest.approx(
        {"mx.mamba2.ssd": 65e-9, "mx.mamba2": 75e-9, "mx.moe.experts": 20e-9,
         "mx.moe": 20e-9})
    assert scopes.leaf_seconds(trace, 0, window) == pytest.approx(105e-9)
    assert scopes.seconds_by_scope(trace, 0, window, {}) == {}
    labels = scopes.label_map(HLO)
    assert labels["fusion.6"] == "jvp(mx.mamba2)/mul"
    top = scopes.top_by_label(trace, 0, window, found, labels, 2, n=1)
    assert top["mx.mamba2.ssd"] == [["fusion.5", "mx.mamba2.ssd/dot_general",
                                     pytest.approx(65e-6 / 2, abs=1e-6)]]
    assert scopes.scope_map("ENTRY %main {\n  %add.1 = f32[] add(%a, %b)\n}",
                            SCOPES) == {}


def test_a_compiled_step_names_its_scopes():
    """The toy step compiled here carries all five scopes in its
    instructions' metadata, forward and backward."""
    import jax
    ctx, _ = _ctx()
    net, loss, n_in = ctx.cfgmod.sharded_parts(ctx.sizes, 0.0, 32)
    from mxnet_tpu.parallel import MeshConfig, P, ShardedTrainStep, make_mesh
    mesh = make_mesh(MeshConfig(dp=1), devices=jax.devices()[:1])
    step = ShardedTrainStep(net, loss, mesh, optimizer="adamw",
                            n_data_inputs=n_in, data_specs=[P()] * n_in)
    ids = np.zeros((2, 32), np.int32)
    text = step._fused.lower(step.params, step.aux, step.states, step._t_dev,
                             step._rng_dev, ids, ids).compile().as_text()
    found = scopes.scope_map(text, SCOPES)
    assert set(found.values()) == set(SCOPES)
    backward = [line for line in text.splitlines()
                if "transpose(jvp(mx.mamba2))" in line
                and "mx.mamba2.ssd" in line]
    assert backward


@pytest.mark.parametrize("name", NEW_READERS)
def test_new_readers_report_nothing_without_their_source(name):
    """On a program without the scopes or the counts (a parent commit),
    and in an untraced run."""
    reader = manifest.layer_metric(name)
    bare = types.SimpleNamespace(traced_steps=3, device_kind="TPU v5 lite")
    assert reader.read(bare) is None
    empty = types.SimpleNamespace(traced_steps=3, device_kind="TPU v5 lite",
                                  scope_seconds={}, scope_costs={},
                                  expert_rows={}, expert_even=384.0)
    assert reader.read(empty) is None


def test_new_readers_read_their_source():
    sizes, cfgmod, _ = manifest.config("nemotron_twotower_30b_a3b")
    costs = cfgmod.scope_costs(sizes, 8192, 1)
    run = types.SimpleNamespace(
        traced_steps=4, device_kind="TPU v5 lite", scope_costs=costs,
        scope_seconds={"mx.mamba2.ssd": 0.2, "mx.moe.experts": 0.1,
                       "mx.attn.causal": 0.08},
        expert_rows={"layers1": np.array([300.0, 500, 400, 400]),
                     "layers3": np.array([400.0, 400, 400, 400])},
        expert_even=cfgmod.expert_even_share(sizes, 8192) + 16)
    assert run.expert_even == 8192 * 6 / 128 + 16 == 400
    read = {n: manifest.layer_metric(n).read(run) for n in NEW_READERS}
    assert read["ssd_scan_ms.train"] == pytest.approx(50.0)
    assert read["moe_experts_ms.train"] == pytest.approx(25.0)
    assert read["causal_attn_ms.train"] == pytest.approx(20.0)
    assert read["moe_load_max_over_mean.train"] == pytest.approx(1.25)
    flops, nbytes = costs["mx.attn.causal"]
    assert read["causal_attn_roofline_pct.train"] == pytest.approx(
        100 * max(flops / 197e12, nbytes / 819e9) / 0.02)
    for n in NEW_READERS:
        if "roofline" in n:
            assert 0 < read[n] < 100
    # routing that leaves the held experts idle still reads a number
    run.expert_rows = {"layers1": np.zeros(4)}
    assert manifest.layer_metric(
        "moe_load_max_over_mean.train").read(run) == 0.0
