"""The granite_4_0_h_micro configuration's benchmark files: the packed
feed (rows cut from a stream of documents, ids that restart with each
row, labels shifted by one), the cell's own check in float32 at toy
widths (that it catches a multiplier left out, the conv reduced to its
last tap and a wrong optimizer; what its two controls read at the
cell's own limits), the model's and the scopes' counts, the
configuration file against the catalog row and its parameter sum, the
scopes in a compiled step, and the two new readers. Everything in
``BENCHMARK.json`` is found by name. The toy's ``--rehearse`` run is
``test_mxbench_rehearse.py``'s, which takes every cell it finds; the
document boundaries position for position are
``tests/test_granite_hybrid.py``'s."""
import json
import os
import re
import types

import numpy as np
import pytest

from mxbench import manifest, run as mxrun, scopes

CELL = "granite_4_0_h_micro_pretrain_packed"
CONFIG = "granite_4_0_h_micro"
TRAFFIC = "pretrain_packed_clm_s8192"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW_READERS = ("mamba2_mixer_ms.train", "seq_documents.train")
MAMBA, ATTN = "mamba", "attention"


# ---------------------------------------------------------------------------
# the feed
# ---------------------------------------------------------------------------
def _feed(seed=3_000_000_019, batch=1, check=False):
    traffic, gen = manifest.traffic(TRAFFIC)
    ctx = types.SimpleNamespace(seed=seed, traffic=traffic, sizes={
        "vocab_size": 12544})
    return gen, gen.PackedRowsFeed(ctx, batch, traffic["seq"], check=check)


def test_rows_are_cut_from_a_stream_of_documents():
    gen, feed = _feed()
    assert feed.rows.shape == feed.segments.shape == (256, 8193)
    seg = feed.segments
    step = np.diff(seg, axis=1)
    assert (seg[:, 0] == 0).all() and set(np.unique(step)) == {0, 1}
    # about ten starts a row; a document is 16..8,192 tokens unless a
    # row's end cut it (only a row's first and last may be shorter)
    assert 10 < feed.documents_a_row() < 11.5
    for row in seg[:32]:
        n = np.bincount(row)
        assert n[1:-1].min() >= 16 and n.max() <= 8192
    # a document cut by a row's end goes on as the next row's document 0:
    # the stream's lengths, cut at the rows' ends, give the same ids
    rng = np.random.default_rng(3_000_000_019)
    rng.integers(0, 12544, (256, 8193), dtype=np.int32)
    spec = manifest.traffic(TRAFFIC)[0]["documents"]
    lengths = gen.document_lengths(rng, spec, 256 * 8193)
    assert lengths.min() >= 16 and lengths.max() <= 8192
    assert 480 < np.median(lengths) < 545 and 800 < lengths.mean() < 880
    ends = np.cumsum(lengths)
    cut = ends[ends < 2 * 8193]
    want = np.searchsorted(cut, np.arange(8193, 2 * 8193), side="right")
    assert (seg[1] == want - want[0]).all() and seg[1, -1] > 3
    # a batch: ids and labels one token apart, the ids' own documents
    ids, segs, labels = feed.host_batch()
    assert ids.shape == segs.shape == labels.shape == (1, 8192)
    assert (ids[:, 1:] == labels[:, :-1]).all()
    row = feed.order[0]
    assert (segs[0] == seg[row, :-1]).all() and ids.max() < 12544
    pairs = feed.pairs_a_row()
    assert 6e6 < pairs < 8.5e6 < 8192 * 8193 // 2
    # the same seed, the same rows; another seed, others; the check's
    # batch is the pool's first rows
    again = _feed()[1]
    assert (again.rows == feed.rows).all() and (again.segments == seg).all()
    assert not (_feed(seed=7)[1].segments == seg).all()
    assert _feed(check=True)[1].segments.shape == (1, 8193)


def test_the_traffic_file_is_the_issues():
    traffic, gen = manifest.traffic(TRAFFIC)
    assert traffic["kind"] == "train_lm_packed"
    assert (traffic["seq"], traffic["batch_per_chip"], traffic["loop"]) \
        == (8192, 1, "sharded_step")
    assert traffic["documents"] == {"distribution": "lognormal",
                                    "median": 512, "sigma": 1.0, "min": 16,
                                    "max": 8192}
    opt = traffic["optimizer"]
    assert (opt["name"], opt["lr"], opt["beta1"], opt["beta2"],
            opt["epsilon"]) == ("adamw", 3e-4, 0.9, 0.95, 1e-8)
    assert opt["wd"] == pytest.approx(0.1 * opt["lr"])
    assert traffic["feed"] == {"type": "packed_rows", "pool_sequences": 256}
    assert (traffic["inflight_steps"], traffic["warmup_steps"],
            traffic["trace_seconds"], traffic["dropout"]) == (2, 3, 6, 0.0)
    # the length rule: 8,192 because the compiled step stays under 15 GB
    mem = traffic["memory_analysis_s8192"]
    assert 14e9 < mem["arguments_bytes"] + mem["temporaries_bytes"] < 15e9
    # the generator is train_lm_stream's, handed the packed feed
    assert gen._lm.TokenRowsFeed is gen.PackedRowsFeed
    assert gen.UNITS["train_samples_per_s"] == "samples/s"
    other = manifest.load_module("traffic", "train_lm_stream.py")
    assert other.TokenRowsFeed is not gen.PackedRowsFeed
    cell = manifest.workload(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == (CONFIG, TRAFFIC, 1)
    assert cell["metrics"] == ["train_samples_per_s", "setup_s"]
    assert len(cell["why"]) <= 200
    assert set(NEW_READERS) <= set(cell["layer_metrics"])
    assert {"ssd_scan_ms.train", "ssd_scan_roofline_pct.train",
            "causal_attn_ms.train", "causal_attn_roofline_pct.train",
            "dense_mlp_ms.train", "lm_head_ms.train", "embed_ms.train",
            "optimizer_ms.train", "param_cast_ms.train", "unscoped_ms.train",
            "compile_s", "cache_hit_pct"} <= set(cell["layer_metrics"])
    assert not [m for m in cell["layer_metrics"] if m.startswith("moe_")]


# ---------------------------------------------------------------------------
# the cell's own check
# ---------------------------------------------------------------------------
def _ctx(seed=5):
    ctx, gen, _ = mxrun.context(CELL, seed=seed, seconds=0.0, trace=False,
                                rehearse=True)
    ctx.sizes = dict(ctx.sizes, compute_dtype="float32")
    ctx.traffic = dict(ctx.traffic, seq=64, batch_per_chip=2)
    ctx.sizes["check"] = dict(ctx.sizes["check"], loss_rtol=1e-5,
                              drop_rtol=2e-3)
    ctx.say = lambda msg: None
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
    """The loss before any update and after one AdamW update, on a
    packed batch; what was checked is what goes on into the window."""
    ctx, _, loop, ok, got = checked
    assert ok and len(got) == 2 and got[1] < got[0]
    assert int(loop.step_obj._t) == ctx.sizes["check"]["steps"] == 2
    assert loop.weights is None
    assert ctx.cfgmod.layer_kinds(ctx.sizes) == [MAMBA, ATTN, MAMBA]
    assert list(loop.step_obj.params).count("embed_weight") == 1
    assert list(loop.step_obj.aux) == ["seq_documents"]
    assert ctx.cfgmod.expert_rows(loop.step_obj.aux) == {}
    from mxnet_tpu import telemetry
    assert telemetry.gauge("mx_seq_documents", block="model").get() > 1.0


def _reference_losses(model=None, patch=None, optimizer=None):
    """The reference's losses on the check's own weights and batch,
    given another model or optimizer than the program's."""
    ctx, gen = _ctx()
    if model is not None:
        ctx.sizes = dict(ctx.sizes, **model)
    if patch is not None:
        # (the context's reference module is its own copy)
        name, make = patch
        setattr(ctx.refmod, name, make(ctx.refmod))
    if optimizer is not None:
        ctx.traffic = dict(ctx.traffic, optimizer=dict(
            ctx.traffic["optimizer"], **optimizer))
    batch = ctx.traffic["batch_per_chip"] * len(ctx.devices)
    _, _, (want,) = gen.reference_first(ctx, batch, ctx.traffic["seq"])
    return want


def _last_tap(ref):
    real = ref.conv
    return lambda x, w, b, seg: real(x, w[:, -1:], b, seg)


FAULTS = {
    "the_residual_multiplier_left_out": dict(
        model=dict(residual_multiplier=1.0)),
    "the_conv_reduced_to_its_last_tap": dict(patch=("conv", _last_tap)),
    "another_epsilon": dict(optimizer={"epsilon": 1e-3}),
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


def test_what_the_two_controls_read_at_the_file_s_limits():
    """Both controls through the check's own comparison at the limits
    the configuration's file gives (toy widths, the cell's optimizer).
    The bf16 masters come out wrong, by the change over an update. The
    reference with no document reset passes ``loss_rtol``: on uniform
    random tokens the first loss does not see where a mixer looks (the
    file's ``check.why`` gives the chip's readings), which is why the
    boundaries are held position for position in
    tests/test_granite_hybrid.py."""
    ctx, gen, _ = mxrun.context(CELL, seed=3_000_000_019, seconds=0.0,
                                trace=False, rehearse=True)
    ctx.say = lambda msg: None
    whole = manifest.load_json("configs", CONFIG + ".json")
    # (a rehearsal has limits of its own, in the file's ``toy``: the
    # toy's change over an update is 0.003 of its loss; here the cell's)
    assert ctx.sizes["check"] == whole["toy"]["check"] != whole["check"]
    chk = ctx.sizes["check"] = whole["check"]
    assert ctx.sizes["compute_dtype"] == "bfloat16"
    assert 0 < chk["loss_rtol"] < chk["drop_rtol"] <= 0.01
    assert "no document reset" in chk["why"] and "bf16 masters" in chk["why"]
    batch = ctx.traffic["batch_per_chip"] * len(ctx.devices)
    ok, first, drop = gen.control(ctx, batch, ctx.traffic["seq"])
    assert not ok and first <= chk["loss_rtol"] and drop > chk["drop_rtol"]
    ok, first, drop = gen.control_no_reset(ctx, batch, ctx.traffic["seq"])
    assert first <= chk["loss_rtol"]


# ---------------------------------------------------------------------------
# counts
# ---------------------------------------------------------------------------
def test_model_flops_and_the_scopes_counts():
    sizes, cfgmod, _ = manifest.config(CONFIG)
    assert cfgmod.head_dim(sizes) == 64
    assert cfgmod.layer_kinds(sizes) == [MAMBA] * 5 + [ATTN] + [MAMBA] * 4
    assert cfgmod.causal_pairs(8192) == 33_558_528
    assert cfgmod.document_pairs([3, 5]) == 6 + 15
    pairs = 7_000_000
    macs = cfgmod.macs_per_token(sizes, 8192, pairs)
    ssd = 128 * 128 + 64 * 128 * 64 + 2 * 64 * 64 * 128
    assert macs["mamba"] == 9 * (2048 * 8512 + 4096 * 2048 + 4352 * 4 + ssd)
    assert macs["attn_proj"] == 10_485_760
    assert macs["attn_pairs"] == pytest.approx(2 * pairs / 8192 * 2048)
    assert macs["mlp"] == 10 * 50_331_648
    assert macs["head"] == 2048 * 12544
    flops = cfgmod.train_flops_per_sample(sizes, 8192, pairs)
    assert flops == pytest.approx(sum(macs.values()) * 6 * 8192, rel=1e-12)
    # 38.8 TFLOP a sequence; the mixers a third, the MLPs most
    assert 38.7e12 < flops < 38.9e12 < cfgmod.train_flops_per_sample(
        sizes, 8192)
    total = sum(macs.values())
    assert 0.30 < macs["mamba"] / total < 0.33
    assert 0.62 < macs["mlp"] / total < 0.65
    costs = cfgmod.scope_costs(sizes, 8192, 1, pairs)
    assert set(costs) == {"mx.mamba2.ssd", "mx.attn.causal"} \
        < set(cfgmod.SCOPES)
    # the attention by the pairs inside documents, at 64 lanes
    assert costs["mx.attn.causal"][0] == 2 * 7 * pairs * 32 * 64
    assert costs["mx.attn.causal"][1] == 8192 * (2 * 32 + 2 * 8) * 64 * 2 * 3
    assert costs["mx.attn.causal"][0] < cfgmod.scope_costs(
        sizes, 8192, 1)["mx.attn.causal"][0] / 4
    # the scan by what runs: the Nemotron file's rule at one group
    carry = 64 * 64 * 64 * 64 * 128
    again = 8192 * (128 * 128 + 64 * 64 * 128) + carry
    assert costs["mx.mamba2.ssd"][0] == 9 * 2 * (3 * (8192 * ssd + carry)
                                                 + again)
    assert costs["mx.mamba2.ssd"][1] \
        == 9 * 8192 * (2 * 4096 + 2 * 128 + 64) * 2 * 4
    twice = cfgmod.scope_costs(sizes, 8192, 2, pairs)
    assert twice["mx.attn.causal"] == tuple(
        2 * n for n in costs["mx.attn.causal"])


def test_configuration_keeps_every_published_key():
    sizes = manifest.load_json("configs", CONFIG + ".json")
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog here")
    with open(CATALOG) as f:
        (row,) = [r for r in map(json.loads, f)
                  if r["source_url"] == sizes["source"]]
    assert row["name"] == "granite-4.0-h-micro"
    for key, value in row["config"].items():
        if key not in sizes["reduced"]:
            assert sizes[key] == value, key
    assert sizes["reduced"] == ["num_hidden_layers", "vocab_size"]
    assert set(sizes["reduced"]) == set(sizes["reduced_why"]) \
        == set(sizes["published"]) - {"layer_kinds"}
    for key in sizes["reduced"]:
        assert sizes["published"][key] == row["config"][key]
    # the floors: a whole period of ten, an eighth of the vocabulary
    kinds = sizes["layer_types"]
    period = [MAMBA] * 5 + [ATTN] + [MAMBA] * 4
    assert kinds == period * 4 and kinds[:sizes["num_hidden_layers"]] \
        == period
    assert sizes["vocab_size"] * 8 == row["config"]["vocab_size"]
    dep = sizes["deployment"]
    assert dep["pipeline_stages"] * sizes["num_hidden_layers"] == 40
    assert dep["vocabulary_shares"] * sizes["vocab_size"] == 100352
    # the chunk: the published one kept, the program's beside it
    assert (sizes["mamba_chunk_size"], sizes["scan_chunk"]) == (256, 128)
    assert {"equations", "assumed", "left_out", "check", "toy"} <= set(sizes)
    assert {"documents", "loss_mask", "optimizer", "init", "scan_chunk",
            "mlp_width", "attention_blocks", "multipliers"} \
        <= set(sizes["assumed"])
    # no width among the cuts
    for key in ("hidden_size", "shared_intermediate_size", "mamba_n_heads",
                "mamba_d_head", "mamba_d_state", "mamba_d_conv",
                "mamba_n_groups", "mamba_expand", "num_attention_heads",
                "num_key_value_heads", "residual_multiplier",
                "embedding_multiplier", "attention_multiplier",
                "logits_scaling", "rms_norm_eps"):
        assert sizes[key] == row["config"][key] and key not in sizes["reduced"]


def test_parameters_as_the_file_states_them():
    sizes, cfgmod, _ = manifest.config(CONFIG)
    u, w = sizes["hidden_size"], sizes["shared_intermediate_size"]
    inner, conv = 64 * 64, 64 * 64 + 2 * 128
    mamba = u * (inner + conv + 64) + conv * 4 + conv + 3 * 64 + inner \
        + inner * u
    attn = 2 * u * u + 2 * u * 8 * 64
    mlp = 3 * u * w
    assert (mamba, attn, mlp) == (25_847_232, 10_485_760, 50_331_648)
    layers = 9 * (mamba + mlp + 2 * u) + attn + mlp + 2 * u
    assert (mamba + mlp + 2 * u, attn + mlp + 2 * u, layers) \
        == (76_182_976, 60_821_504, 746_468_288)
    total = layers + sizes["vocab_size"] * u + u
    assert total == 772_160_448
    assert "772,160,448" in sizes["deployment"]["parameters_here"]
    # and the blocks the builder makes hold that many, the head none of
    # its own (toy widths; the count of documents is no weight)
    toy = dict(sizes, **sizes["toy"])
    net, loss, n_in = cfgmod.sharded_parts(toy, 0.0, 32)
    assert n_in == 3
    names = cfgmod.named_weights(net, loss)
    count = sum(v.size for k, v in names.items()
                if not k.endswith("seq_documents"))
    u, w = toy["hidden_size"], toy["shared_intermediate_size"]
    h, p, n = toy["mamba_n_heads"], toy["mamba_d_head"], toy["mamba_d_state"]
    inner, conv = h * p, h * p + 2 * n
    d = cfgmod.head_dim(toy)
    want = toy["vocab_size"] * u + u
    for kind in cfgmod.layer_kinds(toy):
        want += 2 * u + 3 * u * w + (
            u * (inner + conv + h) + conv * 5 + 3 * h + inner + inner * u
            if kind == MAMBA else
            2 * u * u + 2 * u * toy["num_key_value_heads"] * d)
    assert count == want


# ---------------------------------------------------------------------------
def test_a_compiled_step_names_its_scopes():
    """The toy step compiled here carries all four scopes in its
    instructions' metadata, the scan's inside the mixer's."""
    import jax
    from mxnet_tpu.parallel import MeshConfig, P, ShardedTrainStep, make_mesh
    ctx, _ = _ctx()
    names = ctx.cfgmod.SCOPES
    net, loss, n_in = ctx.cfgmod.sharded_parts(ctx.sizes, 0.0, 32)
    mesh = make_mesh(MeshConfig(dp=1), devices=jax.devices()[:1])
    step = ShardedTrainStep(net, loss, mesh, optimizer="adamw",
                            n_data_inputs=n_in, data_specs=[P()] * n_in)
    ids = np.zeros((2, 32), np.int32)
    text = step._fused.lower(step.params, step.aux, step.states, step._t_dev,
                             step._rng_dev, ids, ids,
                             ids).compile().as_text()
    found = scopes.scope_map(text, names)
    assert set(found.values()) == set(names)
    assert scopes.scope_of("jit(f)/mx.mamba2/checkpoint/mx.mamba2.ssd/mul",
                           names) == "mx.mamba2.ssd"
    assert scopes.scope_of("jit(f)/transpose(jvp(mx.mamba2))/"
                           "rematted_computation/dot_general", names) \
        == "mx.mamba2"


@pytest.mark.parametrize("name", NEW_READERS)
def test_new_readers_report_nothing_without_their_source(name):
    """On a program without the scope or the gauge (a parent commit),
    and in an untraced run."""
    from mxnet_tpu import telemetry
    telemetry.reset()
    reader = manifest.layer_metric(name)
    bare = types.SimpleNamespace(traced_steps=3, device_kind="TPU v5 lite")
    assert reader.read(bare) is None
    other = types.SimpleNamespace(traced_steps=3, device_kind="TPU v5 lite",
                                  scope_seconds={"mx.attn.causal": 0.3},
                                  scope_costs={})
    assert reader.read(other) is None
    untraced = types.SimpleNamespace(traced_steps=0, device_kind="TPU v5 lite",
                                     scope_seconds={}, scope_costs={})
    assert reader.read(untraced) is None


def test_the_new_readers_read_a_run():
    """The mixers whole hold their scan; the documents' count is the
    gauge the model's publisher sets."""
    from mxnet_tpu import telemetry
    run = types.SimpleNamespace(
        traced_steps=6, device_kind="TPU v5 lite", scope_costs={},
        scope_seconds={"mx.mamba2.ssd": 0.18, "mx.mamba2": 0.66,
                       "mx.mlp": 1.2})
    mixer = manifest.layer_metric("mamba2_mixer_ms.train")
    assert (mixer.SCOPE, mixer.UNIT) == ("mx.mamba2", "ms/step")
    assert mixer.read(run) == pytest.approx(140.0)
    assert manifest.layer_metric("ssd_scan_ms.train").read(run) \
        == pytest.approx(30.0)
    docs = manifest.layer_metric("seq_documents.train")
    telemetry.reset()
    try:
        assert docs.read(run) is None
        telemetry.gauge("mx_seq_documents", block="model").set(10.5)
        assert docs.UNIT == "count" and docs.read(run) == 10.5
    finally:
        telemetry.reset()


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
    for name, layer, source in (
            ("mamba2_mixer_ms.train", "kernels", "device_trace"),
            ("seq_documents.train", "input pipeline", "program_counter")):
        m = by_name[name]
        assert CELL in m["workloads"] and m["layer"] == layer
        assert (m["moves"], m["source"]) == ("train_samples_per_s", source)
        assert m["unit"] == manifest.layer_metric(name).UNIT
    listed = manifest.workload(CELL)["layer_metrics"]
    for name in listed:
        assert CELL in by_name[name].get("workloads", [CELL]), name
        assert by_name[name]["moves"] in ("train_samples_per_s", "setup_s")
    for name, m in by_name.items():
        if name not in listed:
            assert CELL not in m.get("workloads", []), name
    (rate,) = [m for m in bench["end_to_end"]
               if m["name"] == "train_samples_per_s"]
    assert CELL in rate["workloads"]
    four = [w for w in bench["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(bench["workloads"]) // 4)
