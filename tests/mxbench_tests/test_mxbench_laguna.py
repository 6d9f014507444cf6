"""The laguna_xs2_33b_a3b configuration's benchmark files: the cell's own
check in float32 at toy widths (that it catches the gate taken out, the
full layers turned over the whole head, every layer made full, unscaled
router weights and a wrong optimizer, and that its control, the
reference with bf16 masters, comes out wrong at the cell's own limits),
the model's and the scopes' counts beside what each op executes when
compiled alone, the configuration file against the catalog row and its
parameter sum, the scopes in a compiled step, and the two new readers.
The toy's ``--rehearse`` run is ``test_mxbench_rehearse.py``'s, which
takes every cell it finds; the mixer's terms one by one are
``tests/test_laguna.py``'s."""
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

CELL = "laguna_xs2_33b_a3b_longctx_s8192"
CONFIG = "laguna_xs2_33b_a3b"
TRAFFIC = "longctx_gated_clm_s8192"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW_READERS = {"attn_gate_ms.train": "mx.attn.gate",
               "attn_rotary_ms.train": "mx.attn.rotary"}
SLIDING, FULL = "sliding_attention", "full_attention"


def _ctx(loss_rtol=1e-5, seed=5):
    ctx, gen, _ = mxrun.context(CELL, seed=seed, seconds=0.0, trace=False,
                                rehearse=True)
    # float32, and smaller than the toy: twice the window
    ctx.sizes = dict(ctx.sizes, compute_dtype="float32", hidden_size=64,
                     sliding_window=16)
    ctx.traffic = dict(ctx.traffic, seq=32, batch_per_chip=2)
    ctx.sizes["check"] = dict(ctx.sizes["check"], loss_rtol=loss_rtol,
                              drop_rtol=2e-3)
    return ctx, gen


def _checked(ctx, gen):
    batch = ctx.traffic["batch_per_chip"] * len(ctx.devices)
    return gen.checked_loop(ctx, batch, ctx.traffic["seq"])


@pytest.fixture(scope="module")
def checked():
    """The cell's own check once, in float32 with tight tolerances:
    (context, generator, the instance, its verdict, the system's
    losses as the check printed them)."""
    ctx, gen = _ctx()
    said = []
    ctx.say = said.append
    loop, ok = _checked(ctx, gen)
    (line,) = [m for m in said if "check: system losses" in m]
    got = json.loads(re.search(r"system losses (\[[^\]]*\])", line).group(1))
    return ctx, gen, loop, ok, got


def test_losses_after_one_and_two_updates_match(checked):
    """The loss before any update and after one AdamW update; what was
    checked is what goes on into the window."""
    ctx, _, loop, ok, got = checked
    assert ok and len(got) == 2 and got[1] < got[0]
    assert int(loop.step_obj._t) == ctx.sizes["check"]["steps"] == 2
    assert loop.weights is None
    built = ctx.cfgmod.layers_built(ctx.sizes)
    assert built == [(FULL, 6, "dense"), (SLIDING, 8, "sparse"),
                     (SLIDING, 8, "sparse"), (SLIDING, 8, "sparse"),
                     (FULL, 6, "sparse")]


def _reference_losses(model=None, optimizer=None):
    """The reference's losses on the check's own weights and batch,
    given another model or optimizer than the program's."""
    ctx, gen = _ctx()
    if model is not None:
        real = ctx.refmod.model_cfg

        def wrong(sizes):
            cfg = copy.deepcopy(real(sizes))
            model(cfg)
            return cfg

        ctx.refmod.model_cfg = wrong
    if optimizer is not None:
        ctx.traffic = dict(ctx.traffic, optimizer=dict(
            ctx.traffic["optimizer"], **optimizer))
    ctx.say = lambda msg: None
    batch = ctx.traffic["batch_per_chip"] * len(ctx.devices)
    _, _, (want,) = gen.reference_first(ctx, batch, ctx.traffic["seq"])
    return want


FAULTS = {
    "the_gate_taken_out": dict(model=lambda cfg: cfg.update(gating=False)),
    "every_layer_full": dict(model=lambda cfg: cfg.update(
        layer_types=[FULL] * 5)),
    "the_full_layers_turned_over_the_whole_head": dict(
        model=lambda cfg: cfg["rope_parameters"][FULL].update(
            partial_rotary_factor=1)),
    "the_router_s_weights_unscaled": dict(model=lambda cfg: cfg.update(
        moe_routed_scaling_factor=1.0)),
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
        == (8192, 1, "sharded_step")
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
    mem = traffic["memory_analysis_b1"]
    assert 11e9 < mem["arguments_bytes"] + mem["temporaries_bytes"] < 15e9
    cell = manifest.workload(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == (CONFIG, TRAFFIC, 1)
    assert len(cell["why"]) <= 200 and len(cell["layer_metrics"]) == 24
    assert set(NEW_READERS) <= set(cell["layer_metrics"])
    # twice the length YaRN extends from, sixteen windows, a window of
    # one query block
    from mxnet_tpu.ops.decoder_ops import QUERY_BLOCK
    sizes = manifest.load_json("configs", CONFIG + ".json")
    rope = sizes["rope_parameters"][FULL]
    assert traffic["seq"] == 2 * rope["original_max_position_embeddings"] \
        == 16 * sizes["sliding_window"]
    assert sizes["sliding_window"] == QUERY_BLOCK
    assert sizes["toy"]["sliding_window"] == 32


# ---------------------------------------------------------------------------
def test_model_flops_and_the_scopes_counts():
    sizes, cfgmod, _ = manifest.config(CONFIG)
    # ISSUE 42's arithmetic: 4,063,488 pairs a head of a window layer at
    # 8,192 (496 a query), 33,558,528 causal (4,096.5), and what whole
    # 512-wide tiles make of them
    assert cfgmod.window_pairs(8192, 512) == 4_063_488
    assert cfgmod.causal_pairs(8192) == 33_558_528
    assert cfgmod.tile_pairs(8192, 512) == 35_651_584
    assert cfgmod.window_pairs(100, 512) == cfgmod.causal_pairs(100)
    assert cfgmod.heads_by_kind(sizes) == {SLIDING: 192, FULL: 96}
    # multiply-adds a token: a full layer's projections 29,458,432, a
    # sliding one's 37,879,808 (the gate's rows with them); router
    # 524,288 + 8 x 32/256 experts + the shared one, 3,145,728 each
    assert cfgmod._proj_macs(sizes, 48) == 29_458_432
    assert cfgmod._proj_macs(sizes, 64) == 37_879_808
    assert cfgmod._moe_macs(sizes) == 524_288 + 2 * 3_145_728
    per_tok = (2 * 29_458_432 + 3 * 37_879_808
               + 3 * 2 * 4_063_488 / 8192 * 8192
               + 2 * 2 * 33_558_528 / 8192 * 6144
               + 50_331_648 + 4 * 6_815_744 + 2048 * 12_544)
    assert cfgmod.train_flops_per_sample(sizes, 8192) \
        == pytest.approx(per_tok * 6 * 8192, rel=1e-12)
    flops = cfgmod.train_flops_per_sample(sizes, 8192)
    assert 19.6e12 < flops < 19.8e12
    # the five gated attention mixers: 74% of it
    attn = (2 * 29_458_432 + 3 * 37_879_808
            + 3 * 2 * 4_063_488 + 2 * 2 * 33_558_528 / 8192 * 6144)
    assert 0.73 < attn * 6 * 8192 / flops < 0.75
    assert cfgmod.expert_capacity(sizes, 8192) == 64 * 512
    assert cfgmod.expert_even_share(sizes, 8192) == 256
    costs = cfgmod.scope_costs(sizes, 8192, 1)
    assert set(costs) == {"mx.attn.window", "mx.attn.causal",
                          "mx.moe.experts"}
    assert costs["mx.attn.window"][0] == 3 * 7 * 2 * 4_063_488 * 64 * 128
    assert costs["mx.attn.causal"][0] == 2 * 7 * 2 * 35_651_584 * 48 * 128
    # by the rows routed (8,192 at even routing), not the buffer's 32,768
    assert costs["mx.moe.experts"][0] == 4 * 11 * 2 * 8192 * 2048 * 512
    assert costs["mx.attn.window"][1] \
        == 8192 * 3 * (2 * 64 + 2 * 8) * 128 * 2 * 3
    assert costs["mx.attn.causal"][1] \
        == 8192 * 2 * (2 * 48 + 2 * 8) * 128 * 2 * 3
    # the window's count is the least the mathematics needs: half of
    # what two whole tiles a query tile compute
    computed = sum(min(i + 1, 2) for i in range(16)) * 512 * 512
    assert computed == 8_126_464
    assert 4_063_488 / computed == pytest.approx(0.5, abs=1e-3)
    twice = cfgmod.scope_costs(sizes, 8192, 2)
    assert twice["mx.attn.window"][0] == 2 * costs["mx.attn.window"][0]


def _flops_alone(fn, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt) for s, dt in shapes]
    return jax.jit(fn).lower(*args).compile().cost_analysis()["flops"]


def test_scope_costs_stay_under_what_the_ops_execute_alone():
    """``scope_costs`` beside ``cost_analysis()`` of each attention's
    gradient compiled alone (here, for the CPU, the composition: no
    chip is needed to count) at the published heads, 2,048 tokens: the
    window's count, the least the mathematics needs, is under what the
    banded blocks execute (a block of 512 queries against up to 1,023
    keys); the full layer's is what its blocks execute; the experts'
    count, by the rows routed, is a quarter of what the buffer's blocks
    execute."""
    from mxnet_tpu.ops import decoder_ops as D
    sizes, cfgmod, _ = manifest.config(CONFIG)
    seq, bf = 2048, jnp.bfloat16

    def ran(heads, window):
        shapes = (((1, seq, heads, 128), bf), ((1, seq, 8, 128), bf),
                  ((1, seq, 8, 128), bf))
        return _flops_alone(jax.grad(
            lambda *a: jnp.sum(D._causal_gqa(*a, D.QUERY_BLOCK, window)
                               .astype(jnp.float32)), argnums=(0, 1, 2)),
            *shapes)

    one = dict(sizes, num_hidden_layers=1, mlp_layer_types=["dense"])
    window = cfgmod.scope_costs(dict(
        one, layer_types=[SLIDING], num_attention_heads_per_layer=[64]),
        seq, 1)
    full = cfgmod.scope_costs(dict(
        one, layer_types=[FULL], num_attention_heads_per_layer=[48]), seq, 1)
    assert window["mx.attn.causal"][0] == full["mx.attn.window"][0] == 0
    assert window["mx.moe.experts"][0] == 0
    # XLA runs 5 of the 7 products under this plain sum of the context
    assert 0.4 < window["mx.attn.window"][0] / ran(64, 512) * 5 / 7 < 0.75
    assert 0.9 < full["mx.attn.causal"][0] / ran(48, None) * 5 / 7 < 1.05
    routed = cfgmod.scope_costs(sizes, 8192, 1)["mx.moe.experts"][0]
    buffer = 4 * 11 * 2 * cfgmod.expert_capacity(sizes, 8192) * 2048 * 512
    assert routed / buffer == 0.25


def test_configuration_keeps_every_published_key():
    sizes = manifest.load_json("configs", CONFIG + ".json")
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog here")
    with open(CATALOG) as f:
        (row,) = [r for r in map(json.loads, f)
                  if r["source_url"] == sizes["source"]]
    assert row["name"] == "Laguna-XS.2"
    for key, value in row["config"].items():
        if key not in sizes["reduced"]:
            assert sizes[key] == value, key
    assert sizes["reduced"] == ["num_hidden_layers", "num_experts",
                                "vocab_size"]
    assert set(sizes["reduced"]) == set(sizes["reduced_why"]) \
        == set(sizes["published"]) - {"layer_kinds"}
    for key in sizes["reduced"]:
        assert sizes["published"][key] == row["config"][key]
    dep = sizes["deployment"]
    assert dep["router_experts"] == row["config"]["num_experts"] == 256
    assert dep["chips_sharing_a_layer"] * sizes["num_experts"] == 256
    assert sizes["vocab_size"] * dep["chips_sharing_a_layer"] \
        == row["config"]["vocab_size"]
    # the floors: the leading dense layer and a whole period of four
    # after it, eight experts, an eighth of the vocabulary
    kinds, heads, mlps = (sizes[k] for k in (
        "layer_types", "num_attention_heads_per_layer", "mlp_layer_types"))
    assert len(kinds) == len(heads) == len(mlps) == 40
    assert kinds == [FULL, SLIDING, SLIDING, SLIDING] * 10
    assert heads == [48, 64, 64, 64] * 10
    assert mlps == ["dense"] + ["sparse"] * 39
    assert sizes["num_hidden_layers"] == 5
    assert sorted(kinds[1:5]) == sorted(kinds[:4])      # a whole period
    assert sizes["num_experts"] >= 8
    assert sizes["vocab_size"] * 8 >= row["config"]["vocab_size"]
    assert {"equations", "assumed", "left_out", "check", "toy"} <= set(sizes)
    assert {"gate", "router", "shared_expert", "qk_norm", "yarn"} \
        <= set(sizes["assumed"])
    # no width among the cuts
    for key in ("hidden_size", "head_dim", "moe_intermediate_size",
                "intermediate_size", "shared_expert_intermediate_size",
                "num_experts_per_tok", "sliding_window",
                "num_attention_heads", "num_key_value_heads",
                "moe_routed_scaling_factor", "rope_parameters",
                "partial_rotary_factor"):
        assert sizes[key] == row["config"][key] and key not in sizes["reduced"]


def test_parameters_as_the_file_states_them():
    sizes, cfgmod, _ = manifest.config(CONFIG)
    u, w, wd = (sizes["hidden_size"], sizes["moe_intermediate_size"],
                sizes["intermediate_size"])
    kv, d = sizes["num_key_value_heads"], sizes["head_dim"]

    def attn(h):
        return 2 * u * h * d + 2 * u * kv * d + u * h

    moe = 32 * 3 * u * w + 3 * u * sizes["shared_expert_intermediate_size"] \
        + 256 * u
    assert (attn(48), attn(64), 3 * u * wd, 3 * u * w, moe) == (
        29_458_432, 37_879_808, 50_331_648, 3_145_728, 104_333_312)
    layers = [attn(48) + 2 * u + 3 * u * wd] + 3 * [attn(64) + 2 * u + moe] \
        + [attn(48) + 2 * u + moe]
    assert layers == [79_794_176] + 3 * [142_217_216] + [133_795_840]
    total = sum(layers) + 2 * sizes["vocab_size"] * u + u
    assert total == 691_623_936
    assert "691,623,936" in sizes["deployment"]["parameters_here"]
    # and the blocks the builder makes hold that many
    toy = dict(sizes, **sizes["toy"])
    net, loss, _ = cfgmod.sharded_parts(toy, 0.0, 32)
    names = cfgmod.named_weights(net, loss)
    count = sum(v.size for k, v in names.items()
                if not k.endswith("expert_rows"))
    u, w, wd, d = (toy["hidden_size"], toy["moe_intermediate_size"],
                   toy["intermediate_size"], toy["head_dim"])
    kv = toy["num_key_value_heads"]
    moe = toy["num_experts"] * 3 * u * w \
        + 3 * u * toy["shared_expert_intermediate_size"] + 16 * u
    want = 2 * toy["vocab_size"] * u + u
    for kind, h, mlp in cfgmod.layers_built(toy):
        want += 2 * u * h * d + 2 * u * kv * d + u * h + 2 * u \
            + (3 * u * wd if mlp == "dense" else moe)
    assert count == want


# ---------------------------------------------------------------------------
def test_a_compiled_step_names_its_scopes():
    """The toy step compiled here carries all seven scopes in its
    instructions' metadata, the gate's in the backward too, and the
    program's own table (``telemetry.hlo_scopes``) names the gate."""
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
            if "transpose(jvp(mx.attn.rotary))" in line
            and "mx.attn.gate" in line]
    assert "mx.attn.gate" in set(telemetry.hlo_scopes(text)[1].values())
    assert scopes.scope_of("jit(f)/mx.attn.rotary/checkpoint/mx.attn.gate/"
                           "mul", names) == "mx.attn.gate"
    assert scopes.scope_of("jit(f)/mx.attn.rotary/checkpoint/mx.attn.window/"
                           "dot_general", names) == "mx.attn.window"
    assert scopes.scope_of("jit(f)/transpose(jvp(mx.attn.rotary))/"
                           "rematted_computation/dot_general", names) \
        == "mx.attn.rotary"


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
    run = types.SimpleNamespace(
        traced_steps=6, device_kind="TPU v5 lite", scope_costs={},
        scope_seconds={"mx.attn.gate": 0.06, "mx.attn.rotary": 0.36,
                       "mx.attn.window": 0.66})
    reader = manifest.layer_metric(name)
    assert reader.SCOPE == NEW_READERS[name] and reader.UNIT == "ms/step"
    assert reader.read(run) == pytest.approx(
        {"mx.attn.gate": 10.0, "mx.attn.rotary": 60.0}[reader.SCOPE])


def test_benchmark_json_lists_the_cell_and_its_metrics():
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
    # and in no list of a metric the cell does not read
    for name, m in by_name.items():
        if name not in listed:
            assert CELL not in m.get("workloads", []), name
    assert CELL in [m for m in bench["end_to_end"]
                    if m["name"] == "train_samples_per_s"][0]["workloads"]
    four = [w for w in bench["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(bench["workloads"]) // 4)
