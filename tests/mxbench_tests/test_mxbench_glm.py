"""The glm_4_7_flash_30b_a3b configuration's benchmark files: the cell's
own check in float32 at toy widths (that it catches a router without
its scaling or renormalisation, a missing shared expert, a missing or
another multi-token-prediction term and a wrong optimizer, and that its
control, the reference with bf16 masters, comes out wrong at the cell's
own limits), the model's and the scopes' counts beside what each op
executes when compiled alone, the configuration file against the
catalog row and its parameter sum, the scopes in a compiled step, and
the three new readers. What the shared rotary key does to the result is
``tests/test_glm_moe_lite.py``'s (at toy widths the seeded init gives
attention nothing to look at; there the queries and keys are scaled).
The toy's ``--rehearse`` run is ``test_mxbench_rehearse.py``'s, which
takes every cell it finds."""
import copy
import json
import os
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mxbench import manifest, run as mxrun, scopes

CELL = "glm_4_7_flash_30b_a3b_midtrain_s8192"
CONFIG = "glm_4_7_flash_30b_a3b"
TRAFFIC = "midtrain_mtp_clm_s8192"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW_READERS = {"mla_proj_ms.train": "mx.attn.mla",
               "dense_mlp_ms.train": "mx.mlp",
               "mtp_combine_ms.train": "mx.mtp"}


@pytest.fixture(scope="module", autouse=True)
def _gates_as_found():
    """``context()`` switches telemetry on and commwatch off through
    the environment, for the process: not for the tests that run after
    this file's in the same worker."""
    gates = {k: os.environ.get(k)
             for k in ("MXNET_TELEMETRY", "MXNET_COMMWATCH")}
    yield
    for k, v in gates.items():
        if v is None:
            os.environ.pop(k, None)
        else:
            os.environ[k] = v


def _ctx(loss_rtol=1e-5, seed=5):
    ctx, gen, _ = mxrun.context(CELL, seed=seed, seconds=0.0, trace=False,
                                rehearse=True)
    # float32, and smaller than the toy (what a fault needs to show)
    ctx.sizes = dict(ctx.sizes, compute_dtype="float32", hidden_size=64)
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
    assert loop.net.mlp_kinds == ("dense", "sparse", "sparse")
    # the two terms of the last checked step, published where the
    # expert rows are
    from mxnet_tpu import telemetry
    rows = ctx.cfgmod.expert_rows(loop.step_obj.aux)
    assert sorted(rows) == ["layers1", "layers2", "mtp_block"]
    lm, mtp = (telemetry.gauge(n).value for n in ("mx_lm_loss",
                                                  "mx_mtp_loss"))
    assert got[1] == pytest.approx(lm + 0.1 * mtp, rel=1e-6)


def _reference_losses(checked, model=None, optimizer=None):
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
    if optimizer is not None:
        ctx.traffic = dict(ctx.traffic, optimizer=dict(
            ctx.traffic["optimizer"], **optimizer))
    ctx.say = lambda msg: None
    batch = ctx.traffic["batch_per_chip"] * len(ctx.devices)
    _, _, (want,) = gen.reference_first(ctx, batch, ctx.traffic["seq"])
    return want


FAULTS = {
    "no_routed_scaling": dict(model={"routed_scaling_factor": 1.0}),
    "weights_not_renormalised": dict(model={"norm_topk_prob": False}),
    "no_shared_expert": dict(model={"n_shared_experts": 0}),
    "no_mtp_term": dict(model={"mtp_loss_weight": 0.0}),
    "mtp_weight_0_3": dict(model={"mtp_loss_weight": 0.3}),
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
    assert (toy["seq"], toy["batch_per_chip"], toy["optimizer"]["lr"],
            toy["feed"]["pool_sequences"]) == (128, 2, 1e-3, 8)
    mem = traffic["memory_analysis_b1"]
    assert 12e9 < mem["arguments_bytes"] + mem["temporaries_bytes"] < 15e9
    cell = manifest.workload(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == (CONFIG, TRAFFIC, 1)
    assert cell["metrics"] == ["train_samples_per_s", "setup_s"]
    assert len(cell["why"]) <= 200 and len(cell["layer_metrics"]) == 22
    assert set(NEW_READERS) <= set(cell["layer_metrics"])
    # the Mellum 2 cell's list (there under the names that move
    # ``train_routed_samples_per_s``) without the window's two, the three
    # new ones, and the five of the program's own table
    mellum = manifest.workload("mellum2_12b_a2_5b_longctx_s16384")
    assert cell["layer_metrics"] == [
        m.replace(".train_routed", ".train")
        for m in mellum["layer_metrics"] if not m.startswith("window_")] \
        + list(NEW_READERS) + ["optimizer_ms.train", "param_cast_ms.train",
                               "lm_head_ms.train", "embed_ms.train",
                               "unscoped_ms.train"]
    # the longest length the causal backward takes at 256-wide heads
    from mxnet_tpu.ops import pallas_causal_gqa as P
    assert P._bwd_vmem_bytes(traffic["seq"], 256, 512) <= P._VMEM_BUDGET \
        < P._bwd_vmem_bytes(2 * traffic["seq"], 256, 512)


# ---------------------------------------------------------------------------
def test_model_flops_and_the_scopes_counts():
    sizes, cfgmod, _ = manifest.config(CONFIG)
    assert cfgmod.blocks_built(sizes) == {"dense": 1, "sparse": 5}
    assert cfgmod.causal_pairs(8192) == 33_558_528
    assert cfgmod.tile_pairs(8192, 512) == 35_651_584
    # multiply-adds a token: the five matrices of a latent-attention
    # layer; router 131,072 + (1 shared + 4 x 8/64) x 9,437,184
    assert cfgmod._proj_macs(sizes) == 21_759_232 - 768 - 512
    assert cfgmod._moe_macs(sizes) == 131_072 + 9_437_184 + 4_718_592
    per_tok = (6 * (21_757_952 + 33_558_528 / 8192 * 20 * 512)
               + 3 * 2048 * 10240 + 5 * 14_286_848 + 2 * 2048 * 2048
               + 2 * 2048 * 19_360)
    assert cfgmod.train_flops_per_sample(sizes, 8192) == per_tok * 6 * 8192
    # ISSUE 40's arithmetic: 604M multiply-adds a token, 29.7 TFLOP a
    # sequence; attention over the causal pairs 42%, MLA whole 63%
    assert 603e6 < per_tok < 605e6
    assert 29.6e12 < cfgmod.train_flops_per_sample(sizes, 8192) < 29.8e12
    pairs = 6 * 33_558_528 / 8192 * 20 * 512
    assert 0.41 < pairs / per_tok < 0.43
    assert 0.62 < (pairs + 6 * 21_757_952) / per_tok < 0.64
    assert 0.12 < 2 * 2048 * 19_360 / per_tok < 0.14
    assert cfgmod.expert_capacity(sizes, 8192) == 24 * 512
    assert cfgmod.expert_even_share(sizes, 8192) == 512
    costs = cfgmod.scope_costs(sizes, 8192, 1)
    assert set(costs) == {"mx.attn.causal", "mx.moe.experts"}
    # the 7 products, 256 + 256 lanes a pair a head
    assert costs["mx.attn.causal"][0] == 6 * 7 * 2 * 35_651_584 * 20 * 256
    assert costs["mx.attn.causal"][1] == 6 * 8192 * 20 * 4 * 256 * 2 * 3
    assert costs["mx.moe.experts"][0] == 5 * 11 * 2 * 12_288 * 2048 * 1536
    twice = cfgmod.scope_costs(sizes, 8192, 2)
    assert twice["mx.attn.causal"][0] == 2 * costs["mx.attn.causal"][0]


def _flops_alone(fn, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt) for s, dt in shapes]
    return jax.jit(fn).lower(*args).compile().cost_analysis()["flops"]


def test_scope_costs_stay_under_what_the_ops_execute_alone():
    """``scope_costs`` beside ``cost_analysis()`` of the attention's
    gradient compiled alone (here, for the CPU, the composition: no
    chip is needed to count) at the published 20 heads of 256 lanes,
    2,048 tokens: the count is what the composition's blocks execute,
    and never more."""
    from mxnet_tpu.ops import decoder_ops as D
    sizes, cfgmod, _ = manifest.config(CONFIG)
    seq, bf = 2048, jnp.bfloat16
    shapes = (((1, seq, 20, 256), bf),) * 3
    ran = _flops_alone(jax.grad(
        lambda *a: jnp.sum(D._causal_gqa(*a, D.QUERY_BLOCK)
                           .astype(jnp.float32)), argnums=(0, 1, 2)), *shapes)
    one = dict(sizes, num_hidden_layers=1, first_k_dense_replace=1,
               num_nextn_predict_layers=0)
    counted = cfgmod.scope_costs(one, seq, 1)["mx.attn.causal"][0]
    # XLA runs 5 of the 7 products under this plain sum of the context
    assert 0.9 < counted / ran * 5 / 7 < 1.05


def test_configuration_keeps_every_published_key():
    sizes = manifest.load_json("configs", CONFIG + ".json")
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog here")
    with open(CATALOG) as f:
        (row,) = [r for r in map(json.loads, f)
                  if r["source_url"] == sizes["source"]]
    assert row["name"] == "GLM-4.7-Flash"
    for key, value in row["config"].items():
        if key not in sizes["reduced"]:
            assert sizes[key] == value, key
    assert sizes["reduced"] == ["num_hidden_layers", "n_routed_experts",
                                "vocab_size"]
    assert set(sizes["reduced"]) == set(sizes["reduced_why"]) \
        == set(sizes["published"]) - {"layer_kinds"}
    for key in sizes["reduced"]:
        assert sizes["published"][key] == row["config"][key]
    dep = sizes["deployment"]
    assert dep["router_experts"] == row["config"]["n_routed_experts"] == 64
    assert dep["chips_sharing_a_layer"] * sizes["n_routed_experts"] == 64
    assert sizes["vocab_size"] * dep["chips_sharing_a_layer"] \
        == row["config"]["vocab_size"]
    # the floors: the leading dense layer once and four layers after
    # it, eight experts, an eighth of the vocabulary; the module built
    assert sizes["num_hidden_layers"] - sizes["first_k_dense_replace"] == 4
    assert sizes["n_routed_experts"] >= 8
    assert sizes["vocab_size"] * 8 >= row["config"]["vocab_size"]
    assert sizes["num_nextn_predict_layers"] == 1
    assert {"equations", "assumed", "left_out", "check", "toy"} <= set(sizes)
    assert "mtp_loss_weight" in sizes["assumed"] \
        and sizes["mtp_loss_weight"] == 0.1
    assert "mtp_loss_weight" not in row["config"]
    # no width among the cuts
    for key in ("hidden_size", "intermediate_size", "moe_intermediate_size",
                "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
                "qk_rope_head_dim", "v_head_dim", "num_experts_per_tok",
                "num_attention_heads", "num_key_value_heads",
                "n_shared_experts"):
        assert sizes[key] == row["config"][key] and key not in sizes["reduced"]


def test_parameters_as_the_file_states_them():
    sizes, cfgmod, _ = manifest.config(CONFIG)

    def count(s, held, layers):
        u, h = s["hidden_size"], s["num_attention_heads"]
        qr, kvr = s["q_lora_rank"], s["kv_lora_rank"]
        n, r, v = (s["qk_nope_head_dim"], s["qk_rope_head_dim"],
                   s["v_head_dim"])
        attn = (u * qr + qr + qr * h * (n + r) + u * (kvr + r) + kvr
                + kvr * h * (n + v) + h * v * u)
        dense = attn + 2 * u + 3 * u * s["intermediate_size"]
        expert = 3 * u * s["moe_intermediate_size"]
        routed = s["deployment"]["router_experts"]
        sparse = attn + 2 * u + routed * u + routed + (1 + held) * expert
        module = 2 * u + 2 * u * u + sparse + u
        return (attn, dense, expert, sparse, module,
                dense + (layers - 1) * sparse + module
                + 2 * s["vocab_size"] * u + u)

    assert count(sizes, 8, 5) == (21_759_232, 84_677_888, 9_437_184,
                                  106_829_120, 115_223_872, 706_518_848)
    stated = sizes["deployment"]["parameters_here"]
    for n in (21_759_232, 84_677_888, 9_437_184, 106_829_120, 115_223_872,
              706_518_848):
        assert "{:,}".format(n) in stated, n
    assert 706_518_848 * 16 == pytest.approx(11.30e9, rel=1e-3)
    # and the blocks the builder makes hold that many
    toy = dict(sizes, **sizes["toy"])
    net, loss, _ = cfgmod.sharded_parts(toy, 0.0, 32)
    names = cfgmod.named_weights(net, loss)
    built = sum(v.size for k, v in names.items()
                if not k.endswith(("expert_rows", "loss_terms")))
    assert built == count(toy, toy["n_routed_experts"],
                          toy["num_hidden_layers"])[-1]


# ---------------------------------------------------------------------------
def test_a_compiled_step_names_its_scopes():
    """The toy step compiled here carries all six scopes in its
    instructions' metadata, the attention's inside the mixer's in the
    backward too, and the program's own rule (the innermost ``mx.*``
    element) files every one of them."""
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
            if "transpose(jvp(mx.attn.mla))" in line
            and "mx.attn.causal" in line]
    assert scopes.scope_of("jit(f)/mx.attn.mla/checkpoint/mx.attn.causal/"
                           "dot_general", names) == "mx.attn.causal"
    assert scopes.scope_of("jit(f)/transpose(jvp(mx.attn.mla))/"
                           "rematted_computation/dot_general", names) \
        == "mx.attn.mla"
    _, table, _ = telemetry.hlo_scopes(text)
    assert set(names) | {"mx.head.ce", "mx.embed", "mx.optimizer"} \
        <= set(table.values())


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
    none = types.SimpleNamespace(traced_steps=0, device_kind="TPU v5 lite",
                                 scope_seconds={NEW_READERS[name]: 0.3})
    assert reader.read(none) is None


@pytest.mark.parametrize("name", sorted(NEW_READERS))
def test_scope_readers_read_a_run(name):
    run = types.SimpleNamespace(
        traced_steps=6, device_kind="TPU v5 lite", scope_costs={},
        scope_seconds={"mx.attn.mla": 0.36, "mx.mlp": 0.06, "mx.mtp": 0.012,
                       "mx.attn.causal": 0.9})
    reader = manifest.layer_metric(name)
    assert reader.SCOPE == NEW_READERS[name] and reader.UNIT == "ms/step"
    assert reader.read(run) == pytest.approx(
        {"mx.attn.mla": 60.0, "mx.mlp": 10.0, "mx.mtp": 2.0}[reader.SCOPE])


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
