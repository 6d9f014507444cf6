"""The lfm2_24b_a2b configuration's benchmark files: the cell's own check
in float32 at toy widths (that it catches a gate taken out, the filter
reduced to its last tap, router weights left unnormalised and a wrong
optimizer, and that its control, the reference with bf16 masters, comes
out wrong at the cell's own limits), the model's and the scopes' counts
beside what each op executes when compiled alone, the configuration
file against the catalog row and its parameter sum, the scopes in a
compiled step, and the three new readers. Everything in
``BENCHMARK.json`` is found by name, never by its place or by a count
of entries. The toy's ``--rehearse`` run is ``test_mxbench_rehearse.py``'s,
which takes every cell it finds; the mixers' terms one by one are
``tests/test_lfm2.py``'s."""
import copy
import json
import os
import re
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mxbench import manifest, run as mxrun, scopes

CELL = "lfm2_24b_a2b_midtrain_s8192"
CONFIG = "lfm2_24b_a2b"
TRAFFIC = "midtrain_expert_load_clm_s8192"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW_READERS = {"short_conv_ms.train": "mx.conv",
               "short_conv_gate_ms.train": "mx.conv.gate",
               "short_conv_gate_roofline_pct.train": "mx.conv.gate"}
CONV, FULL = "conv", "full_attention"


def _ctx(loss_rtol=1e-5, seed=5):
    ctx, gen, _ = mxrun.context(CELL, seed=seed, seconds=0.0, trace=False,
                                rehearse=True)
    # float32, and smaller than the toy
    ctx.sizes = dict(ctx.sizes, compute_dtype="float32", hidden_size=64)
    ctx.traffic = dict(ctx.traffic, seq=32, batch_per_chip=2)
    ctx.sizes["check"] = dict(ctx.sizes["check"], loss_rtol=loss_rtol,
                              drop_rtol=2e-3)
    return ctx, gen


@pytest.fixture(scope="module")
def checked():
    """The cell's own check once, in float32 with tight tolerances:
    (context, generator, the instance, its verdict, the system's
    losses as the check printed them)."""
    ctx, gen = _ctx()
    said = []
    ctx.say = said.append
    batch = ctx.traffic["batch_per_chip"] * len(ctx.devices)
    loop, ok = gen.checked_loop(ctx, batch, ctx.traffic["seq"])
    (line,) = [m for m in said if "check: system losses" in m]
    got = json.loads(re.search(r"system losses (\[[^\]]*\])", line).group(1))
    return ctx, gen, loop, ok, got


def test_losses_after_one_and_two_updates_match(checked):
    """The loss before any update and after one AdamW update; what was
    checked is what goes on into the window; the layers built are the
    published ones the deployment names."""
    ctx, _, loop, ok, got = checked
    assert ok and len(got) == 2 and got[1] < got[0]
    assert int(loop.step_obj._t) == ctx.sizes["check"]["steps"] == 2
    assert loop.weights is None
    assert ctx.cfgmod.layer_kinds(ctx.sizes) == [CONV, FULL, CONV, CONV, CONV]
    assert list(loop.step_obj.params).count("embed_weight") == 1
    assert "head_weight" not in loop.step_obj.params


def _reference_losses(model=None, patch=None, optimizer=None):
    """The reference's losses on the check's own weights and batch,
    given another model or optimizer than the program's."""
    ctx, gen = _ctx()
    if model is not None:
        real = ctx.refmod.model_cfg

        def wrong(sizes):
            cfg = copy.deepcopy(real(sizes))
            cfg.update(model)
            return cfg

        ctx.refmod.model_cfg = wrong
    if patch is not None:
        # (the context's reference module is its own copy)
        name, make = patch
        setattr(ctx.refmod, name, make(ctx.refmod))
    if optimizer is not None:
        ctx.traffic = dict(ctx.traffic, optimizer=dict(
            ctx.traffic["optimizer"], **optimizer))
    ctx.say = lambda msg: None
    batch = ctx.traffic["batch_per_chip"] * len(ctx.devices)
    _, _, (want,) = gen.reference_first(ctx, batch, ctx.traffic["seq"])
    return want


def _no_c_gate(ref):
    def short_conv(w, p, x, cfg=None):
        b, _, u = jnp.split(x @ w[p + "in_weight"].T, 3, axis=-1)
        return ref.causal_filter(b * u, w[p + "conv_weight"]) \
            @ w[p + "out_weight"].T
    return short_conv


def _last_tap(ref):
    real = ref.causal_filter
    return lambda z, taps: real(z, taps[:, -1:])


FAULTS = {
    "the_c_gate_taken_out": dict(patch=("short_conv", _no_c_gate)),
    "the_filter_reduced_to_its_last_tap": dict(
        patch=("causal_filter", _last_tap)),
    "the_router_s_weights_not_normalised": dict(
        model=dict(norm_topk_prob=False)),
    "another_epsilon": dict(optimizer={"epsilon": 1e-3}),
    "another_decay": dict(optimizer={"wd": 0.01}),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_wrong_model_or_optimizer_fails_the_check(fault, checked):
    """The system's losses held against the reference of another model
    through the check's own comparison; against the right one they
    pass."""
    ctx, gen, _, _, got = checked
    chk = ctx.sizes["check"]
    if fault == sorted(FAULTS)[0]:
        assert gen.agree(got, _reference_losses(), chk)[0]
    assert not gen.agree(got, _reference_losses(**FAULTS[fault]), chk)[0]


def test_the_check_tells_its_control_apart():
    """The control (the reference with bf16 masters) through the same
    comparison at the limits the configuration's file gives and the
    cell's own rate of 1e-5, where an update is under a bf16 master's
    resolution: wrong by the change over an update, not by the first
    loss. (That the system passes at those limits in bf16 is the toy's
    rehearsal, ``test_mxbench_rehearse.py``.)"""
    ctx, gen, _ = mxrun.context(CELL, seed=3_000_000_019, seconds=0.0,
                                trace=False, rehearse=True)
    assert ctx.sizes["compute_dtype"] == "bfloat16"
    chk = manifest.load_json("configs", CONFIG + ".json")["check"]
    assert ctx.sizes["check"] == chk
    assert 0 < chk["loss_rtol"] < chk["drop_rtol"] <= 0.01
    batch = ctx.traffic["batch_per_chip"] * len(ctx.devices)
    ctx.traffic = dict(ctx.traffic, optimizer=manifest.traffic(
        TRAFFIC)[0]["optimizer"])
    assert ctx.traffic["optimizer"]["lr"] == 1e-5
    ok, first, drop = gen.control(ctx, batch, ctx.traffic["seq"])
    assert not ok and first <= chk["loss_rtol"] and drop > chk["drop_rtol"]


def test_the_traffic_file_is_the_issues():
    traffic, gen = manifest.traffic(TRAFFIC)
    assert traffic["kind"] == "train_lm_stream"
    assert (traffic["seq"], traffic["batch_per_chip"], traffic["loop"]) \
        == (8192, 4, "sharded_step")
    opt = traffic["optimizer"]
    assert (opt["name"], opt["lr"], opt["beta1"], opt["beta2"],
            opt["epsilon"]) == ("adamw", 1e-5, 0.9, 0.95, 1e-8)
    assert opt["wd"] == pytest.approx(0.1 * opt["lr"])
    assert traffic["feed"] == {"type": "token_rows", "pool_sequences": 256}
    assert (traffic["inflight_steps"], traffic["warmup_steps"],
            traffic["trace_seconds"], traffic["dropout"]) == (2, 3, 6, 0.0)
    toy = traffic["toy"]
    assert (toy["seq"], toy["batch_per_chip"],
            toy["feed"]["pool_sequences"], toy["optimizer"]["lr"]) \
        == (128, 2, 8, 1e-3)
    # the batch rule: 4 under 15 GB, and the next power of two over it
    b4, b2 = traffic["memory_analysis_b4"], traffic["memory_analysis_b2"]
    assert b4["arguments_bytes"] == pytest.approx(b2["arguments_bytes"],
                                                  rel=1e-3)
    at4 = b4["arguments_bytes"] + b4["temporaries_bytes"]
    at2 = b2["arguments_bytes"] + b2["temporaries_bytes"]
    assert at2 < at4 < 15e9 < at4 + 2 * (at4 - at2)
    cell = manifest.workload(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == (CONFIG, TRAFFIC, 1)
    assert cell["metrics"] == ["train_samples_per_s", "setup_s"]
    assert len(cell["why"]) <= 200
    assert set(NEW_READERS) <= set(cell["layer_metrics"])
    assert {"causal_attn_ms.train", "causal_attn_roofline_pct.train",
            "moe_experts_ms.train", "moe_experts_roofline_pct.train",
            "moe_load_max_over_mean.train", "dense_mlp_ms.train",
            "attn_rotary_ms.train", "unscoped_ms.train"} \
        <= set(cell["layer_metrics"])
    assert not [m for m in cell["layer_metrics"]
                if m.startswith(("window_attn", "attn_gate"))]
    # each held expert is routed half of what its deployment sends it
    sizes, cfgmod, _ = manifest.config(CONFIG)
    tokens = traffic["seq"] * traffic["batch_per_chip"]
    assert cfgmod.expert_even_share(sizes, tokens) == 2048
    assert cfgmod.expert_even_share(sizes, 8 * traffic["seq"]) == 4096
    assert cfgmod.expert_capacity(sizes, tokens) == 72 * 512


# ---------------------------------------------------------------------------
def test_model_flops_and_the_scopes_counts():
    sizes, cfgmod, _ = manifest.config(CONFIG)
    assert cfgmod.causal_pairs(8192) == 33_558_528
    assert cfgmod.tile_pairs(8192, 512) == 35_651_584
    assert cfgmod.head_dim(sizes) == 64
    assert cfgmod.layer_kinds(sizes) == [CONV, FULL, CONV, CONV, CONV]
    # multiply-adds a token (ISSUE 47's arithmetic, in FLOPs there)
    macs = cfgmod.macs_per_token(sizes, 8192)
    assert macs["conv"] == 4 * (2048 * 8192 + 2048 * 5)
    assert macs["attn_proj"] == 10_485_760
    assert macs["attn_pairs"] == pytest.approx(2 * 4096.5 * 2048)
    assert macs["dense_mlp"] == 72_351_744
    assert macs["experts"] == 4 * (131_072 + 0.5 * 9_437_184)
    assert macs["head"] == 2048 * 8192
    flops = cfgmod.train_flops_per_sample(sizes, 8192)
    assert flops == pytest.approx(sum(macs.values()) * 6 * 8192, rel=1e-12)
    # 10.0 TFLOP a sequence (the ISSUE's 9.97 leaves the routers and
    # the taps out); the conv mixers a third, the 64-lane attention 13%,
    # the dense layer 36%
    assert 9.97e12 < flops < 10.03e12
    total = sum(macs.values())
    assert 0.32 < macs["conv"] / total < 0.34
    assert 0.13 < (macs["attn_proj"] + macs["attn_pairs"]) / total < 0.14
    assert 0.35 < macs["dense_mlp"] / total < 0.36
    costs = cfgmod.scope_costs(sizes, 8192, 4)
    assert set(costs) == {"mx.conv.gate", "mx.attn.causal", "mx.moe.experts"}
    assert set(costs) < set(cfgmod.SCOPES)
    # the gates: three passes of 6,144 + 2,048 bf16 values a token a layer
    assert costs["mx.conv.gate"][1] == 4 * 32768 * 3 * 8192 * 2
    assert costs["mx.conv.gate"][0] / 197e12 < costs["mx.conv.gate"][1] / 819e9
    # the attention at the published 64 lanes, whatever the kernel's tile
    assert costs["mx.attn.causal"][0] == 2 * 7 * 4 * 35_651_584 * 32 * 64
    assert costs["mx.attn.causal"][1] == 32768 * (2 * 32 + 2 * 8) * 64 * 2 * 3
    # by the rows routed: 16,384 at even routing, not the buffer's 36,864
    assert costs["mx.moe.experts"][0] == 4 * 11 * 2 * 16384 * 2048 * 1536
    once = cfgmod.scope_costs(sizes, 8192, 1)
    for scope in ("mx.conv.gate", "mx.attn.causal"):
        assert costs[scope] == tuple(4 * n for n in once[scope])


def _cost_alone(fn, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt) for s, dt in shapes]
    return jax.jit(fn).lower(*args).compile().cost_analysis()


def test_scope_costs_stay_under_what_the_ops_execute_alone():
    """``scope_costs`` beside ``cost_analysis()`` of each op's gradient
    compiled alone (here, for the CPU, the compositions: no chip is
    needed to count) at the published widths, 2,048 tokens: the
    attention's count is what its blocks execute at 64 lanes; the
    gates' bytes are under what the compiled gates and taps move; the
    experts' count, by the rows routed, is under what the buffer's
    blocks execute."""
    from mxnet_tpu.ops import decoder_ops as D
    sizes, cfgmod, _ = manifest.config(CONFIG)
    seq, bf = 2048, jnp.bfloat16
    one = cfgmod.scope_costs(dict(
        sizes, num_hidden_layers=1, num_dense_layers=0, deployment=dict(
            sizes["deployment"], layers_built=[2])), seq, 1)
    assert one["mx.conv.gate"] == (0, 0) and one["mx.moe.experts"][0] > 0
    attn = _cost_alone(jax.grad(
        lambda *a: jnp.sum(D._causal_gqa(*a, D.QUERY_BLOCK)
                           .astype(jnp.float32)), argnums=(0, 1, 2)),
        ((1, seq, 32, 64), bf), ((1, seq, 8, 64), bf), ((1, seq, 8, 64), bf))
    # XLA runs 5 of the 7 products under this plain sum of the context
    assert 0.9 < one["mx.attn.causal"][0] / attn["flops"] * 5 / 7 < 1.05
    conv = cfgmod.scope_costs(dict(
        sizes, num_hidden_layers=1, num_dense_layers=1, deployment=dict(
            sizes["deployment"], layers_built=[0])), seq, 1)
    assert conv["mx.attn.causal"] == (0, 0) == conv["mx.moe.experts"]

    def gates(bcu, taps):
        b, c, u = jnp.split(bcu, 3, axis=-1)
        return c * D._causal_conv1d(b * u, taps, dtype=bcu.dtype)

    ran = _cost_alone(
        lambda bcu, taps, cot: jax.vjp(gates, bcu, taps)[1](cot),
        ((1, seq, 6144), bf), ((2048, 3), bf), ((1, seq, 2048), bf))
    # forward + backward alone; the count has the recomputation too
    assert conv["mx.conv.gate"][1] * 2 / 3 <= ran["bytes accessed"]
    routed = cfgmod.scope_costs(sizes, 8192, 4)["mx.moe.experts"][0]
    buffer = 4 * 11 * 2 * cfgmod.expert_capacity(sizes, 32768) * 2048 * 1536
    assert routed / buffer == pytest.approx(16384 / 36864)


def test_configuration_keeps_every_published_key():
    sizes = manifest.load_json("configs", CONFIG + ".json")
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog here")
    with open(CATALOG) as f:
        (row,) = [r for r in map(json.loads, f)
                  if r["source_url"] == sizes["source"]]
    assert row["name"] == "LFM2-24B-A2B"
    for key, value in row["config"].items():
        if key not in sizes["reduced"]:
            assert sizes[key] == value, key
    assert sizes["reduced"] == ["num_hidden_layers", "num_experts",
                                "vocab_size", "num_dense_layers"]
    assert set(sizes["reduced"]) == set(sizes["reduced_why"]) \
        == set(sizes["published"]) - {"layer_kinds"}
    for key in sizes["reduced"]:
        assert sizes["published"][key] == row["config"][key]
    dep = sizes["deployment"]
    assert dep["router_experts"] == row["config"]["num_experts"] == 64
    assert dep["chips_sharing_a_layer"] * sizes["num_experts"] == 64
    assert sizes["vocab_size"] * dep["chips_sharing_a_layer"] \
        == row["config"]["vocab_size"]
    # the floors: the leading dense layers counted once and a whole
    # period of four after them, eight experts, an eighth of the
    # vocabulary
    kinds = sizes["layer_types"]
    assert kinds == [CONV, CONV] + [FULL, CONV, CONV, CONV] * 9 + [FULL, CONV]
    assert kinds.count(CONV) == 30 and kinds.count(FULL) == 10
    built = dep["layers_built"]
    assert built == [0, 2, 3, 4, 5] and len(built) == sizes[
        "num_hidden_layers"]
    assert built[0] < sizes["published"]["num_dense_layers"] <= built[1]
    assert [kinds[i] for i in built[1:]] == [FULL, CONV, CONV, CONV]
    assert sorted(kinds[i] for i in built[1:]) == sorted(kinds[6:10])
    assert sizes["num_dense_layers"] == 1
    assert sizes["num_experts"] >= 8
    assert sizes["vocab_size"] * 8 >= row["config"]["vocab_size"]
    assert {"equations", "assumed", "left_out", "check", "toy"} <= set(sizes)
    assert {"tied_head", "conv_order", "final_norm", "qk_norm",
            "rotary_pairing", "dense_width", "router", "optimizer", "init",
            "documents", "expert_layout", "gate_precision"} \
        <= set(sizes["assumed"])
    # no width among the cuts
    for key in ("hidden_size", "intermediate_size", "moe_intermediate_size",
                "num_experts_per_tok", "num_attention_heads",
                "num_key_value_heads", "conv_L_cache", "rope_parameters",
                "norm_eps", "routed_scaling_factor", "use_expert_bias",
                "norm_topk_prob"):
        assert sizes[key] == row["config"][key] and key not in sizes["reduced"]


def test_parameters_as_the_file_states_them():
    sizes, cfgmod, _ = manifest.config(CONFIG)
    u, w, wd = (sizes["hidden_size"], sizes["moe_intermediate_size"],
                sizes["intermediate_size"])
    kv, d = sizes["num_key_value_heads"], cfgmod.head_dim(sizes)
    conv = u * 3 * u + u * sizes["conv_L_cache"] + u * u
    attn = 2 * u * u + 2 * u * kv * d + 2 * d
    moe = 8 * 3 * u * w + 64 * u + 64
    assert (conv, attn, 3 * u * wd, 3 * u * w, moe) == (
        16_783_360, 10_485_888, 72_351_744, 9_437_184, 75_628_608)
    layers = [conv + 2 * u + 3 * u * wd, attn + 2 * u + moe] \
        + 3 * [conv + 2 * u + moe]
    assert layers == [89_139_200, 86_118_592] + 3 * [92_416_064]
    total = sum(layers) + sizes["vocab_size"] * u + u
    assert total == 469_285_248
    assert "469,285,248" in sizes["deployment"]["parameters_here"]
    # and the blocks the builder makes hold that many, the head none
    toy = dict(sizes, **sizes["toy"])
    net, loss, _ = cfgmod.sharded_parts(toy, 0.0, 32)
    names = cfgmod.named_weights(net, loss)
    count = sum(v.size for k, v in names.items()
                if not k.endswith("expert_rows"))
    u, w, wd = (toy["hidden_size"], toy["moe_intermediate_size"],
                toy["intermediate_size"])
    kv, d = toy["num_key_value_heads"], cfgmod.head_dim(toy)
    routed = toy["deployment"]["router_experts"]
    want = toy["vocab_size"] * u + u
    for i, kind in enumerate(cfgmod.layer_kinds(toy)):
        want += 2 * u + (u * 3 * u + u * 3 + u * u if kind == CONV
                         else 2 * u * u + 2 * u * kv * d + 2 * d)
        want += 3 * u * wd if i < toy["num_dense_layers"] \
            else toy["num_experts"] * 3 * u * w + routed * u + routed
    assert count == want


# ---------------------------------------------------------------------------
def test_a_compiled_step_names_its_scopes():
    """The toy step compiled here carries all seven scopes in its
    instructions' metadata, the gates' in the backward too, and the
    program's own table (``telemetry.hlo_scopes``) names both new
    scopes."""
    from mxnet_tpu import telemetry
    from mxnet_tpu.parallel import MeshConfig, P, ShardedTrainStep, make_mesh
    ctx, _ = _ctx()
    names = ctx.cfgmod.SCOPES
    net, loss, n_in = ctx.cfgmod.sharded_parts(ctx.sizes, 0.0, 32)
    mesh = make_mesh(MeshConfig(dp=1), devices=jax.devices()[:1])
    step = ShardedTrainStep(net, loss, mesh, optimizer="adamw",
                            n_data_inputs=n_in, data_specs=[P()] * n_in)
    ids = np.zeros((2, 32), np.int32)
    text = step._fused.lower(step.params, step.aux, step.states, step._t_dev,
                             step._rng_dev, ids, ids).compile().as_text()
    found = scopes.scope_map(text, names)
    assert set(found.values()) == set(names)
    assert [line for line in text.splitlines()
            if "transpose(jvp(mx.conv))" in line and "mx.conv.gate" in line]
    assert {"mx.conv", "mx.conv.gate"} \
        <= set(telemetry.hlo_scopes(text)[1].values())
    assert scopes.scope_of("jit(f)/mx.conv/checkpoint/mx.conv.gate/mul",
                           names) == "mx.conv.gate"
    assert scopes.scope_of("jit(f)/transpose(jvp(mx.conv))/"
                           "rematted_computation/dot_general", names) \
        == "mx.conv"


@pytest.mark.parametrize("name", sorted(NEW_READERS))
def test_new_readers_report_nothing_without_their_source(name):
    """On a program without the scope (a parent commit), and in an
    untraced run."""
    reader = manifest.layer_metric(name)
    bare = types.SimpleNamespace(traced_steps=3, device_kind="TPU v5 lite")
    assert reader.read(bare) is None
    empty = types.SimpleNamespace(traced_steps=3, device_kind="TPU v5 lite",
                                  scope_seconds={"mx.attn.causal": 0.3},
                                  scope_costs={})
    assert reader.read(empty) is None
    untraced = types.SimpleNamespace(traced_steps=0, device_kind="TPU v5 lite",
                                     scope_seconds={}, scope_costs={})
    assert reader.read(untraced) is None


@pytest.mark.parametrize("name", sorted(NEW_READERS))
def test_scope_readers_read_a_run(name):
    """The mixers whole hold their gates; the gates' share is their
    least bytes over the peak bytes a second over their seconds."""
    run = types.SimpleNamespace(
        traced_steps=6, device_kind="TPU v5 lite",
        scope_costs={"mx.conv.gate": (1e9, 819e9 * 0.005)},
        scope_seconds={"mx.conv.gate": 0.06, "mx.conv": 0.36,
                       "mx.attn.causal": 0.66})
    reader = manifest.layer_metric(name)
    assert reader.SCOPE == NEW_READERS[name]
    assert reader.UNIT == ("%" if "roofline" in name else "ms/step")
    assert reader.read(run) == pytest.approx({
        "short_conv_ms.train": 70.0, "short_conv_gate_ms.train": 10.0,
        "short_conv_gate_roofline_pct.train": 50.0}[name])


def test_benchmark_json_names_the_configuration_the_cell_and_its_metrics():
    """By name: nothing here counts entries or looks at a place in a
    list, so the next cell does not break it."""
    with open(os.path.join(os.path.dirname(manifest.ROOT),
                           "BENCHMARK.json")) as f:
        bench = json.load(f)
    (config,) = [c for c in bench["configs"] if c["name"] == CONFIG]
    sizes = manifest.load_json("configs", CONFIG + ".json")
    assert config["source"] == sizes["source"]
    assert config["reduced"] == sizes["reduced"]
    assert config["file"] == "mxbench/configs/%s.json" % CONFIG
    (cell,) = [w for w in bench["workloads"] if w["name"] == CELL]
    assert cell == {"name": CELL, "config": CONFIG, "chips": 1,
                    "traffic": TRAFFIC, "why": manifest.workload(CELL)["why"]}
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW_READERS:
        m = by_name[name]
        # (a later cell that reads the scope may join the list)
        assert CELL in m["workloads"] and m["layer"] == "kernels"
        assert (m["moves"], m["source"]) == ("train_samples_per_s",
                                             "device_trace")
        assert m["unit"] == manifest.layer_metric(name).UNIT
    listed = manifest.workload(CELL)["layer_metrics"]
    for name in listed:
        assert CELL in by_name[name].get("workloads", [CELL]), name
        assert by_name[name]["moves"] in ("train_samples_per_s", "setup_s")
    # and in no list of a metric the cell does not read
    for name, m in by_name.items():
        if name not in listed:
            assert CELL not in m.get("workloads", []), name
    (rate,) = [m for m in bench["end_to_end"]
               if m["name"] == "train_samples_per_s"]
    assert CELL in rate["workloads"]
    four = [w for w in bench["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(bench["workloads"]) // 4)
