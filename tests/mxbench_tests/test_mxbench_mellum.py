"""The mellum2_12b_a2_5b configuration's benchmark files: the cell's own
check in float32 at toy widths (that it catches every layer made full,
half the window, plain rotary on the full layer, a missing attention
factor and a wrong optimizer, and that its control, the reference with
bf16 masters, comes out wrong at the cell's own limits), the model's and
the scopes' counts beside what each op executes when compiled alone, the
configuration file against the catalog row and its parameter sum, the
scopes in a compiled step, and the two new readers. The toy's
``--rehearse`` run is ``test_mxbench_rehearse.py``'s, which takes every
cell it finds."""
import copy
import json
import os
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mxbench import manifest, run as mxrun, scopes

CELL = "mellum2_12b_a2_5b_longctx_s16384"
CONFIG = "mellum2_12b_a2_5b"
TRAFFIC = "longctx_clm_s16384"
# the rate's name: the cell's step takes what its sequences route, so its
# runs spread more widely than a 1% bound takes, and it is judged under a
# name and a bound of its own (mxbench/README.md, PERF.md section 2)
RATE = "train_routed_samples_per_s"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW_READERS = {"window_attn_ms.train": "mx.attn.window",
               "window_attn_roofline_pct.train": "mx.attn.window"}
SLIDING, FULL = "sliding_attention", "full_attention"


def _ctx(loss_rtol=1e-5, seed=5):
    ctx, gen, _ = mxrun.context(CELL, seed=seed, seconds=0.0, trace=False,
                                rehearse=True)
    # float32, and smaller than the toy (what a fault needs to show):
    # twice the window and four times the length YaRN extends from
    ctx.sizes = dict(ctx.sizes, compute_dtype="float32", hidden_size=64,
                     sliding_window=16)
    ctx.sizes["rope_parameters"] = copy.deepcopy(ctx.sizes["rope_parameters"])
    ctx.sizes["rope_parameters"][FULL]["original_max_position_embeddings"] = 8
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
    import re
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
    kinds = ctx.sizes["layer_types"][:ctx.sizes["num_hidden_layers"]]
    assert kinds == [SLIDING, SLIDING, SLIDING, FULL]


def _reference_losses(checked, model=None, optimizer=None):
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
    "every_layer_full": dict(model=lambda cfg: cfg.update(
        layer_types=[FULL] * 4)),
    "half_the_window": dict(model=lambda cfg: cfg.update(
        sliding_window=cfg["sliding_window"] // 2)),
    "plain_rotary_on_the_full_layer": dict(
        model=lambda cfg: cfg["rope_parameters"].update(
            {FULL: cfg["rope_parameters"][SLIDING]})),
    "attention_factor_1": dict(
        model=lambda cfg: cfg["rope_parameters"][FULL].update(
            attention_factor=1.0)),
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
        assert gen.agree(got, _reference_losses(checked), chk)[0]
    assert not gen.agree(got, _reference_losses(checked, **FAULTS[fault]),
                         chk)[0]


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
    assert (traffic["seq"], traffic["batch_per_chip"]) == (16384, 1)
    opt = traffic["optimizer"]
    assert (opt["name"], opt["lr"], opt["beta1"], opt["beta2"],
            opt["epsilon"]) == ("adamw", 1e-5, 0.9, 0.95, 1e-8)
    assert opt["wd"] == pytest.approx(0.1 * opt["lr"])
    assert traffic["feed"] == {"type": "token_rows", "pool_sequences": 256}
    assert (traffic["inflight_steps"], traffic["warmup_steps"],
            traffic["trace_seconds"], traffic["dropout"]) == (2, 3, 6, 0.0)
    mem = traffic["memory_analysis_b1"]
    assert 12e9 < mem["arguments_bytes"] + mem["temporaries_bytes"] < 15e9
    cell = manifest.workload(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == (CONFIG, TRAFFIC, 1)
    assert len(cell["why"]) <= 200 and len(cell["layer_metrics"]) == 16
    assert {n + "_routed" for n in NEW_READERS} <= set(cell["layer_metrics"])
    # twice the length YaRN extends from, sixteen windows
    sizes = manifest.load_json("configs", CONFIG + ".json")
    rope = sizes["rope_parameters"][FULL]
    assert traffic["seq"] == 2 * rope["original_max_position_embeddings"] \
        == 16 * sizes["sliding_window"]


# ---------------------------------------------------------------------------
def test_model_flops_and_the_scopes_counts():
    sizes, cfgmod, _ = manifest.config(CONFIG)
    # ISSUE 34's arithmetic: 16,253,440 pairs a head of a window layer at
    # 16,384 (992 a query), 134,225,920 causal (8,192.5), and what whole
    # 512-wide tiles make of them
    assert cfgmod.window_pairs(16384, 1024) == 16_253_440
    assert cfgmod.causal_pairs(16384) == 134_225_920
    assert cfgmod.tile_pairs(16384, 512) == 138_412_032
    assert cfgmod.window_pairs(100, 1024) == cfgmod.causal_pairs(100)
    assert cfgmod.layer_kinds(sizes) == {SLIDING: 3, FULL: 1}
    # multiply-adds a token: projections 21,233,664; router 147,456 +
    # 8 x 16/64 x 6,193,152; head 56,623,104
    assert cfgmod._proj_macs(sizes) == 21_233_664
    assert cfgmod._moe_macs(sizes) == 147_456 + 2 * 6_193_152
    per_tok = (4 * (21_233_664 + 12_533_760)
               + 3 * 2 * 16_253_440 / 16384 * 4096
               + 2 * 134_225_920 / 16384 * 4096 + 2304 * 24_576)
    assert cfgmod.train_flops_per_sample(sizes, 16384) \
        == per_tok * 6 * 16384
    assert 27.8e12 < cfgmod.train_flops_per_sample(sizes, 16384) < 27.9e12
    assert cfgmod.expert_capacity(sizes, 16384) == 144 * 512
    assert cfgmod.expert_even_share(sizes, 16384) == 2048
    costs = cfgmod.scope_costs(sizes, 16384, 1)
    assert set(costs) == {"mx.attn.window", "mx.attn.causal",
                          "mx.moe.experts"}
    assert costs["mx.attn.window"][0] == 3 * 7 * 2 * 16_253_440 * 32 * 128
    assert costs["mx.attn.causal"][0] == 7 * 2 * 138_412_032 * 32 * 128
    assert costs["mx.moe.experts"][0] == 4 * 11 * 2 * 73_728 * 2304 * 896
    # the window's count is the least the mathematics needs: two thirds
    # of what three whole tiles a query tile compute
    computed = sum(min(i + 1, 3) for i in range(32)) * 512 * 512
    assert computed == 24_379_392
    assert 16_253_440 / computed == pytest.approx(0.6667, abs=1e-3)
    twice = cfgmod.scope_costs(sizes, 16384, 2)
    assert twice["mx.attn.window"][0] == 2 * costs["mx.attn.window"][0]


def _flops_alone(fn, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt) for s, dt in shapes]
    return jax.jit(fn).lower(*args).compile().cost_analysis()["flops"]


def test_scope_costs_stay_under_what_the_ops_execute_alone():
    """``scope_costs`` beside ``cost_analysis()`` of each attention's
    gradient compiled alone (here, for the CPU, the composition: no
    chip is needed to count) at the published heads, 4,096 tokens: the
    window's count, the least the mathematics needs, is under what the
    banded blocks execute (a block of 512 queries against up to 1,535
    keys); the full layer's is what its blocks execute."""
    from mxnet_tpu.ops import decoder_ops as D
    sizes, cfgmod, _ = manifest.config(CONFIG)
    seq, bf = 4096, jnp.bfloat16
    shapes = (((1, seq, 32, 128), bf), ((1, seq, 4, 128), bf),
              ((1, seq, 4, 128), bf))

    def ran(window):
        return _flops_alone(jax.grad(
            lambda *a: jnp.sum(D._causal_gqa(*a, D.QUERY_BLOCK, window)
                               .astype(jnp.float32)), argnums=(0, 1, 2)),
            *shapes)

    one = dict(sizes, num_hidden_layers=1)
    window = cfgmod.scope_costs(dict(one, layer_types=[SLIDING]), seq, 1)
    full = cfgmod.scope_costs(dict(one, layer_types=[FULL]), seq, 1)
    assert window["mx.attn.causal"][0] == full["mx.attn.window"][0] == 0
    # XLA runs 5 of the 7 products under this plain sum of the context
    assert 0.55 < window["mx.attn.window"][0] / ran(1024) * 5 / 7 < 0.8
    assert 0.9 < full["mx.attn.causal"][0] / ran(None) * 5 / 7 < 1.05


def test_configuration_keeps_every_published_key():
    sizes = manifest.load_json("configs", CONFIG + ".json")
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog here")
    with open(CATALOG) as f:
        (row,) = [r for r in map(json.loads, f)
                  if r["source_url"] == sizes["source"]]
    assert row["name"] == "Mellum2-12B-A2.5B-Instruct"
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
    assert dep["router_experts"] == row["config"]["num_experts"] == 64
    assert dep["chips_sharing_a_layer"] * sizes["num_experts"] == 64
    assert sizes["vocab_size"] * dep["chips_sharing_a_layer"] \
        == row["config"]["vocab_size"]
    # the floors: a whole period and four layers, eight experts, an
    # eighth of the vocabulary
    kinds = sizes["layer_types"]
    assert len(kinds) == 28 and kinds == kinds[:4] * 7
    assert sizes["num_hidden_layers"] == 4 and sizes["num_experts"] >= 8
    assert sizes["vocab_size"] * 8 >= row["config"]["vocab_size"]
    assert {"equations", "assumed", "left_out", "check", "toy"} <= set(sizes)
    assert "mtp_head" in sizes["left_out"]
    # no width among the cuts
    for key in ("hidden_size", "head_dim", "moe_intermediate_size",
                "intermediate_size", "num_experts_per_tok", "sliding_window",
                "num_attention_heads", "num_key_value_heads"):
        assert sizes[key] == row["config"][key] and key not in sizes["reduced"]


def test_parameters_as_the_file_states_them():
    sizes, cfgmod, _ = manifest.config(CONFIG)
    u, w = sizes["hidden_size"], sizes["moe_intermediate_size"]
    h, kv, d = (sizes["num_attention_heads"], sizes["num_key_value_heads"],
                sizes["head_dim"])
    attn = 2 * u * h * d + 2 * u * kv * d
    layer = attn + 2 * d + 2 * u + 64 * u + 16 * 3 * u * w
    assert (attn, 3 * u * w, layer) == (21_233_664, 6_193_152, 120_476_416)
    total = 4 * layer + 2 * sizes["vocab_size"] * u + u
    assert total == 595_154_176
    assert "595,154,176" in sizes["deployment"]["parameters_here"]
    # and the blocks the builder makes hold that many
    toy = dict(sizes, **sizes["toy"])
    net, loss, _ = cfgmod.sharded_parts(toy, 0.0, 32)
    names = cfgmod.named_weights(net, loss)
    count = sum(v.size for k, v in names.items()
                if not k.endswith("expert_rows"))
    u, w, d = toy["hidden_size"], toy["moe_intermediate_size"], toy["head_dim"]
    h, kv = toy["num_attention_heads"], toy["num_key_value_heads"]
    layer = 2 * u * h * d + 2 * u * kv * d + 2 * d + 2 * u + 16 * u \
        + toy["num_experts"] * 3 * u * w
    assert count == 4 * layer + 2 * toy["vocab_size"] * u + u


# ---------------------------------------------------------------------------
def test_a_compiled_step_names_its_scopes():
    """The toy step compiled here carries all five scopes in its
    instructions' metadata, the window's in the backward too."""
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
            and "mx.attn.window" in line]
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


@pytest.mark.parametrize("name", sorted(NEW_READERS))
def test_scope_readers_read_a_run(name):
    sizes, cfgmod, _ = manifest.config(CONFIG)
    costs = cfgmod.scope_costs(sizes, 16384, 1)
    run = types.SimpleNamespace(
        traced_steps=6, device_kind="TPU v5 lite", scope_costs=costs,
        scope_seconds={"mx.attn.window": 0.36, "mx.attn.causal": 0.66,
                       "mx.moe.experts": 2.5})
    reader = manifest.layer_metric(name)
    assert reader.SCOPE == NEW_READERS[name]
    if name.endswith("_ms.train"):
        assert reader.UNIT == "ms/step"
        assert reader.read(run) == pytest.approx(60.0)
    else:
        flops, nbytes = costs["mx.attn.window"]
        assert reader.UNIT == "%"
        assert reader.read(run) == pytest.approx(
            100 * max(flops / 197e12, nbytes / 819e9) / 0.06)
        assert 0 < reader.read(run) < 100


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
        # the cell reads the scope under the name that moves its own rate
        m = by_name[name + "_routed"]
        assert CELL in m["workloads"] and m["layer"] == "kernels"
        assert m["moves"] == RATE
        assert m["unit"] == manifest.layer_metric(name).UNIT
    listed = manifest.workload(CELL)["layer_metrics"]
    for name in listed:
        assert CELL in by_name[name].get("workloads", [CELL]), name
    # and in no list of a metric the cell does not read
    for name, m in by_name.items():
        if name not in listed:
            assert CELL not in m.get("workloads", []), name
    assert CELL in [m for m in bench["end_to_end"]
                    if m["name"] == RATE][0]["workloads"]
    assert manifest.workload(CELL)["metrics"] == [RATE, "setup_s"]
