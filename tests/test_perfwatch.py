"""Performance-trajectory store + regression detection tests
(ISSUE 19, mxnet_tpu/perfwatch.py + tools/bench_json.py +
tools/perfwatch.py; docs/OBSERVABILITY.md "Performance trajectory").
All tier-1 (`obs` marker, not `slow`)."""
import glob
import json
import os

import pytest

from mxnet_tpu import dist, perfwatch, telemetry

pytestmark = pytest.mark.obs

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# the driver's five round-1..5 headline rows (ResNet-50 img/s/chip on a
# v5e; the BENCH_r0N.json record files themselves were deleted in PR 21
# — round-5 figures, not re-measured). The statistics tests below are
# calibrated on this trajectory; the wrappers are rebuilt per session.
_HISTORY = [
    {"value": 2337.52, "vs_baseline": 0.7792},
    {"value": 2752.49, "vs_baseline": 0.9175},
    {"value": 2846.83, "vs_baseline": 0.9489},
    {"value": 2780.09, "vs_baseline": 0.9267,
     "path": "gluon_hybridize_trainer",
     "sharded_train_step_img_s": 2819.84},
    {"value": 2789.14, "vs_baseline": 0.9297,
     "path": "gluon_hybridize_trainer", "method": "xplane_device_time",
     "sharded_train_step_img_s": 2819.96},
]
_WARN_TAIL = ("/root/repo/bench.py:159: DeprecationWarning: Conversion of "
              "an array with ndim > 0 to a scalar is deprecated\n"
              "  float(jax.device_get(loss.sum()._jax()))\n")


@pytest.fixture(scope="module")
def bench_files(tmp_path_factory):
    """BENCH_r01..r05.json driver wrappers ({"n","cmd","rc","tail",
    "parsed"}) written into a temp dir; returns their sorted paths."""
    d = tmp_path_factory.mktemp("bench_history")
    paths = []
    for n, extra in enumerate(_HISTORY, 1):
        parsed = {"metric": "resnet50_v1_train_throughput",
                  "unit": "images/sec/chip"}
        parsed.update(extra)
        path = str(d / ("BENCH_r%02d.json" % n))
        with open(path, "w") as f:
            json.dump({"n": n, "cmd": "python bench.py", "rc": 0,
                       "tail": _WARN_TAIL + json.dumps(parsed) + "\n",
                       "parsed": parsed}, f)
        paths.append(path)
    return paths


def _bench_glob(bench_files):
    return os.path.join(os.path.dirname(bench_files[0]), "BENCH_r*.json")


@pytest.fixture(autouse=True)
def _clean_perfwatch(monkeypatch):
    monkeypatch.delenv("MXNET_PERF_DB", raising=False)
    monkeypatch.delenv("MXNET_PERFWATCH", raising=False)
    monkeypatch.delenv("MXNET_PERFWATCH_TOL", raising=False)
    monkeypatch.delenv("MXNET_PERFWATCH_TOL_OVERRIDES", raising=False)
    perfwatch.refresh()
    telemetry.reset()
    yield
    perfwatch.refresh()
    telemetry.reset()


def _env(kind="tpu_v4", rev="abc123"):
    return {"device_kind": kind, "git_rev": rev, "flags": {}}


def _rec(value, metric="t_train_throughput",
         unit="images/sec/chip", **extra):
    rec = {"metric": metric, "value": value, "unit": unit}
    rec.update(extra)
    return rec


# ---------------------------------------------------------------------------
# store
# ---------------------------------------------------------------------------
def test_store_roundtrip_atomic_and_idempotent(tmp_path):
    db = perfwatch.PerfDB(str(tmp_path / "db"))
    fp = db.ingest(_rec(100.0, vs_baseline=0.5), source="t",
                   round=1, env=_env())
    assert fp
    # idempotent: byte-identical record is a no-op
    assert db.ingest(_rec(100.0, vs_baseline=0.5), source="t",
                     round=1, env=_env()) is None
    assert db.ingest(_rec(101.0, vs_baseline=0.51), source="t",
                     round=2, env=_env())
    # round-trip through a FRESH handle (reads the published file)
    db2 = perfwatch.PerfDB(db.root)
    assert db2.device_kinds() == ["tpu_v4"]
    assert db2.metrics("tpu_v4") == ["t_train_throughput"]
    rows = db2.records("tpu_v4", "t_train_throughput")
    assert [r["value"] for r in rows] == [100.0, 101.0]
    assert rows[0]["env"]["device_kind"] == "tpu_v4"
    assert rows[0]["record"]["vs_baseline"] == 0.5
    # atomic publish: no tmp files left behind, one parseable JSONL
    leftovers = [p for p in glob.glob(os.path.join(db.root, "*", "*"))
                 if ".tmp." in p]
    assert leftovers == []
    path = os.path.join(db.root, "tpu_v4", "t_train_throughput.jsonl")
    with open(path) as f:
        assert len([json.loads(l) for l in f if l.strip()]) == 2
    # derived sub-series ride along
    series = db2.series("tpu_v4", "t_train_throughput")
    assert series["t_train_throughput.vs_baseline"][0][0] == 0.5


def test_fingerprint_partitioning_two_device_kinds(tmp_path):
    """Two device kinds are disjoint trajectories: a v5e run can
    never be judged against v4 history."""
    db = perfwatch.PerfDB(str(tmp_path))
    for i, v in enumerate([100.0, 101.0, 99.0, 100.5]):
        db.ingest(_rec(v), round=i, env=_env("tpu_v4"))
    # same metric, way-lower value, different chip: not a regression
    db.ingest(_rec(60.0), round=9, env=_env("tpu_v5e"))
    assert sorted(db.device_kinds()) == ["tpu_v4", "tpu_v5e"]
    rows = perfwatch.scan(db)
    by_kind = {r["device_kind"]: r for r in rows
               if r["metric"] == "t_train_throughput"}
    assert by_kind["tpu_v4"]["n"] == 4
    assert by_kind["tpu_v5e"]["n"] == 1      # never mixed in
    assert by_kind["tpu_v5e"]["verdict"] == "flat"
    assert by_kind["tpu_v4"]["verdict"] == "flat"


def test_ingest_file_wrapper_and_glob_idempotent(tmp_path, bench_files):
    """BENCH_r*.json driver wrappers ingest via their parsed record,
    stamped with the round from the wrapper's n."""
    db = perfwatch.PerfDB(str(tmp_path / "db"))
    out = db.ingest_glob(_bench_glob(bench_files))
    assert len(out) == len(bench_files) >= 5
    assert all(len(fps) == 1 for fps in out.values())
    again = db.ingest_glob(_bench_glob(bench_files))
    assert all(fps == [] for fps in again.values())    # idempotent
    kind = db.device_kinds()[0]
    rows = db.records(kind, "resnet50_v1_train_throughput")
    assert [r["round"] for r in rows] == list(
        range(1, len(bench_files) + 1))


# ---------------------------------------------------------------------------
# verdicts
# ---------------------------------------------------------------------------
def test_flat_noise_trajectory_stays_green():
    vals = [100.0, 100.5, 99.8, 100.2, 100.1, 99.9]
    v = perfwatch.judge_series(vals, +1, metric="t")
    assert v["verdict"] == "flat"
    # large-amplitude noise: an 8% swing in an 8%-noisy series is
    # within the MAD band — noise, not signal
    spiky = [100, 108, 93, 107, 94, 106, 95, 92.0]
    v = perfwatch.judge_series(spiky, +1, metric="t")
    assert v["verdict"] == "flat"


def test_regression_and_improvement_verdicts():
    base = [100.0, 100.5, 99.8, 100.2, 100.1]
    down = perfwatch.judge_series(base + [90.0], +1, metric="t")
    assert down["verdict"] == "regressed"
    assert down["delta_rel"] < -0.05
    up = perfwatch.judge_series(base + [110.0], +1, metric="t")
    assert up["verdict"] == "improved"
    # lower-is-better flips the polarity
    lat = perfwatch.judge_series(base + [110.0], -1, metric="t_ms")
    assert lat["verdict"] == "regressed"
    # sub-tolerance dip stays flat even when many MADs out
    small = perfwatch.judge_series(
        [100.0, 100.01, 99.99, 100.0, 98.0], +1, metric="t")
    assert small["verdict"] == "flat"
    # unknown direction never gates
    unk = perfwatch.judge_series(base + [50.0], 0, metric="mystery")
    assert unk["verdict"] == "flat"


def test_per_metric_tolerance_overrides(monkeypatch):
    vals = [100.0, 100.5, 99.8, 100.2, 100.1, 93.0]   # -7% dip
    assert perfwatch.judge_series(vals, +1,
                                  metric="t")["verdict"] == "regressed"
    monkeypatch.setenv("MXNET_PERFWATCH_TOL_OVERRIDES", "t=0.10")
    assert perfwatch.judge_series(vals, +1,
                                  metric="t")["verdict"] == "flat"
    # prefix also covers derived sub-series; longest match wins
    assert perfwatch.judge_series(
        vals, +1, metric="t.vs_baseline")["verdict"] == "flat"
    monkeypatch.setenv("MXNET_PERFWATCH_TOL_OVERRIDES",
                       "t=0.10,t.vs_baseline=0.01")
    assert perfwatch.judge_series(
        vals, +1, metric="t.vs_baseline")["verdict"] == "regressed"


def test_change_point_localization():
    # level shift smack in the middle of a clean series
    vals = [10.0] * 4 + [8.5] * 4
    cp = perfwatch.change_point(vals, -1)       # ms: lower is better
    assert cp is not None
    assert cp["index"] == 4
    assert cp["kind"] == "improvement"
    # same series for a higher-is-better metric is a regression
    assert perfwatch.change_point(vals, +1)["kind"] == "regression"
    # flat noise: no change point to report
    assert perfwatch.change_point(
        [10.0, 10.1, 9.9, 10.05, 9.95, 10.0], +1) is None
    # the checked-in BENCH history localizes its r01->r02 level shift
    series = [2337.52, 2752.49, 2846.83, 2780.09, 2789.14]
    cp = perfwatch.change_point(series, +1)
    assert cp["index"] == 1 and cp["kind"] == "improvement"


def test_metric_direction_rules():
    d = perfwatch.metric_direction
    assert d("t", "images/sec/chip") == 1
    assert d("serve_throughput", "req/s") == 1
    assert d("kernel_micro_worst_paired_median_ratio",
             "candidate/twin") == -1
    assert d("comm_micro_disabled_overhead", "disabled/stripped") == -1
    assert d("x.p99_ms", "") == -1
    assert d("x.mfu", "") == 1
    assert d("x.steady_recompiles", "") == -1
    assert d("x.grad_noise_scale", "") == 0


# ---------------------------------------------------------------------------
# CLI: report renders the checked-in history, --gate flips on a
# synthetic 10% regression naming the metric
# ---------------------------------------------------------------------------
def test_perfwatch_gate_green_on_checked_in_history(capsys, bench_files):
    """Tier-1 smoke: the round-1..5 history must gate green."""
    import tools.perfwatch as pw
    assert pw.main(["report", "--gate", _bench_glob(bench_files)]) == 0
    out = capsys.readouterr().out
    assert "resnet50_v1_train_throughput" in out
    assert "PERFWATCH_GATE_OK" in out
    # the r01->r02 optimization shows up as a localized level shift
    assert "improvement@r02" in out


def test_perfwatch_gate_trips_on_injected_regression(tmp_path, capsys,
                                                     bench_files):
    import tools.perfwatch as pw
    for p in bench_files:
        with open(p) as f:
            w = json.load(f)
        with open(tmp_path / os.path.basename(p), "w") as f:
            json.dump(w, f)
    with open(bench_files[-1]) as f:
        w = json.load(f)
    parsed = dict(w["parsed"])
    parsed["value"] = round(parsed["value"] * 0.9, 2)     # -10%
    parsed.pop("sharded_train_step_img_s", None)
    with open(tmp_path / "BENCH_r99.json", "w") as f:
        json.dump({"n": len(bench_files) + 1, "cmd": w["cmd"],
                   "rc": 0, "tail": "", "parsed": parsed}, f)
    rc = pw.main(["report", "--gate",
                  str(tmp_path / "BENCH_r*.json")])
    out = capsys.readouterr().out
    assert rc == 1
    assert "PERFWATCH REGRESSION: resnet50_v1_train_throughput" in out
    # confirmed regressions surface on the telemetry side too
    snap = telemetry.snapshot()
    assert any(k.startswith("mx_perf_regressions_total")
               and "resnet50_v1_train_throughput" in k
               for k in snap["counters"])
    assert "perf=" in telemetry.heartbeat_line()


def test_perfwatch_ingest_and_report_persistent_store(tmp_path, capsys,
                                                      bench_files):
    import tools.perfwatch as pw
    db_dir = str(tmp_path / "db")
    rc = pw.main(["ingest", _bench_glob(bench_files),
                  "--db", db_dir])
    assert rc == 0
    assert pw.main(["report", "--gate", "--db", db_dir]) == 0
    out = capsys.readouterr().out
    assert "PERFWATCH_GATE_OK" in out


# ---------------------------------------------------------------------------
# the emit seam
# ---------------------------------------------------------------------------
def test_maybe_record_seam_gating(tmp_path, monkeypatch):
    rec = _rec(100.0, env=_env())
    # no store configured: inert
    assert perfwatch.maybe_record(rec) is None
    # store + default-on gate: records
    monkeypatch.setenv("MXNET_PERF_DB", str(tmp_path))
    perfwatch.refresh()
    assert perfwatch.maybe_record(rec, source="t")
    # MXNET_PERFWATCH=0 wins over the store path
    monkeypatch.setenv("MXNET_PERFWATCH", "0")
    perfwatch.refresh()
    assert perfwatch.maybe_record(_rec(101.0, env=_env())) is None
    # ...and the gate is CACHED until refresh (the <5% hot-seam rule)
    monkeypatch.setenv("MXNET_PERFWATCH", "1")
    assert perfwatch.maybe_record(_rec(102.0, env=_env())) is None
    perfwatch.refresh()
    assert perfwatch.maybe_record(_rec(102.0, env=_env()))


def test_emit_records_and_prints_one_line(tmp_path, monkeypatch,
                                          capsys):
    import tools.bench_json as bench_json
    monkeypatch.setenv("MXNET_PERF_DB", str(tmp_path))
    perfwatch.refresh()
    out_rec = bench_json.emit(_rec(123.0), source="t")
    line = capsys.readouterr().out.strip()
    assert json.loads(line) == out_rec
    assert out_rec["env"]["device_kind"]      # fingerprint stamped
    db = perfwatch.PerfDB(str(tmp_path))
    kind = db.device_kinds()[0]
    assert db.records(kind, "t_train_throughput")[0]["value"] == 123.0


def test_environment_fingerprint_contents():
    fp = perfwatch.environment_fingerprint()
    assert fp["device_kind"]                 # cpu on the test mesh
    assert fp["git_rev"]                     # a real checkout
    assert isinstance(fp["flags"], dict)
    # the store's own knobs never fork the trajectory partition
    assert not any(k.startswith("MXNET_PERF") for k in fp["flags"])


# ---------------------------------------------------------------------------
# bench-JSON schema
# ---------------------------------------------------------------------------
def test_bench_json_schema_accepts_and_rejects():
    import tools.bench_json as bench_json
    assert bench_json.validate(_rec(1.0)) == []
    assert bench_json.validate({"metric": "x"})          # missing
    assert bench_json.validate(_rec(float("nan")))       # non-finite
    assert bench_json.validate(_rec(True))               # bool value
    assert bench_json.validate(_rec(1.0, metric="Bad-Name"))
    assert bench_json.validate(_rec(1.0, unit=""))
    assert bench_json.validate(_rec(1.0, env={"nope": 1}))
    assert bench_json.validate([1, 2])
    with pytest.raises(ValueError, match="schema violation"):
        bench_json.check({"metric": "x"})
    with pytest.raises(ValueError):
        bench_json.emit({"metric": "x"})


def test_checked_in_history_validates_and_parses_clean(bench_files):
    """Every history record is schema-valid, and the driver's
    last-JSON-line rule recovers exactly the parsed record from the
    raw stdout tail — DeprecationWarning lines in the tail never
    confuse the parse."""
    import tools.bench_json as bench_json
    assert len(bench_files) >= 5
    for p in bench_files:
        with open(p) as f:
            w = json.load(f)
        assert bench_json.validate(w["parsed"]) == [], p
        tail_rec = bench_json.last_json_line(w.get("tail", ""))
        if tail_rec is not None:
            assert tail_rec["metric"] == w["parsed"]["metric"]
            assert tail_rec["value"] == w["parsed"]["value"]


def test_tool_json_emitters_validate():
    """Every migrated --json emitter routes through bench_json.emit
    (validation at emit time); spot-check the cheap ones end-to-end
    and the expensive ones structurally (their emit sites)."""
    import tools.bench_json as bench_json
    # structural: every tool that prints a bench record now calls
    # bench_json.emit — no hand-rolled print(json.dumps({"metric"...
    tools_dir = os.path.join(REPO, "tools")
    emitters = ["kernel_micro.py", "serve_bench.py", "bert_bench.py",
                "zero_micro.py", "quant_micro.py", "serve_micro.py",
                "comm_micro.py", "trace_micro.py",
                "staticcheck_micro.py", "perfwatch.py"]
    for name in emitters:
        with open(os.path.join(tools_dir, name)) as f:
            src = f.read()
        assert "bench_json" in src, name
    with open(os.path.join(REPO, "bench.py")) as f:
        src = f.read()
    assert "from bench_json import emit" in src
    assert 'print(json.dumps({"metric"' not in src
    # the headline rows the new emitters produce are schema-valid
    for rec in (
        {"metric": "zero_micro_state_ratio", "value": 0.13,
         "unit": "zero/replicated_bytes_ratio"},
        {"metric": "quant_micro_bus_ratio", "value": 0.27,
         "unit": "int8/f32_bus_bytes_ratio"},
        {"metric": "serve_micro_worst_overhead", "value": 1.04,
         "unit": "paired_median_ratio"},
        {"metric": "comm_micro_disabled_overhead", "value": 1.01,
         "unit": "disabled/stripped"},
        {"metric": "trace_micro_disabled_overhead", "value": 1.02,
         "unit": "disabled/stripped"},
        {"metric": "staticcheck_micro_worst_idle_overhead",
         "value": 1.03, "unit": "paired_median_ratio"},
        {"metric": "perfwatch_micro_disabled_overhead",
         "value": 1.01, "unit": "disabled/stripped"},
    ):
        assert bench_json.validate(rec) == [], rec
        # and every one is a lower-is-better ratio (gateable)
        assert perfwatch.metric_direction(rec["metric"],
                                          rec["unit"]) == -1


# ---------------------------------------------------------------------------
# autotune training corpus (ROADMAP 4)
# ---------------------------------------------------------------------------
KERNEL_MICRO_REC = {
    "metric": "kernel_micro_worst_paired_median_ratio",
    "value": 1.1, "unit": "candidate/twin",
    "on_tpu": False, "small": True, "speed_gate_enforced": False,
    "kernels": {
        "layer_norm": {"candidate_ms": 0.098, "twin_ms": 0.11,
                       "paired_median_ratio": 0.9,
                       "steady_recompiles": 0},
        "bias_gelu": {"candidate_ms": 0.059, "twin_ms": 0.045,
                      "paired_median_ratio": 1.1,
                      "steady_recompiles": 0}},
    "autotune": "measure",
    "autotune_table": {
        "tpu_v4|pallas_layer_norm_2|C=128,M=256,esize=4":
            {"block_rows": 128},
        "tpu_v4|pallas_bias_gelu|C=32,M=64,esize=4":
            {"block_rows": 32}},
}


def test_autotune_corpus_export_shape(tmp_path):
    db = perfwatch.PerfDB(str(tmp_path / "db"))
    db.ingest(KERNEL_MICRO_REC, source="kernel_micro", round=1,
              env=_env())
    exported = perfwatch.export_autotune_corpus(db)
    assert list(exported) == ["tpu_v4"]
    path, n = exported["tpu_v4"]
    assert n == 2
    with open(path) as f:
        corpus = json.load(f)
    entry = corpus["tpu_v4|pallas_layer_norm_2|C=128,M=256,esize=4"]
    assert entry["params"] == {"block_rows": 128}
    assert entry["features"] == {"C": 128, "M": 256, "esize": 4}
    # measured time joined from the matching kernel-vs-twin row
    assert entry["measured_ms"] == 0.098
    assert entry["mode"] == "measure"
    assert corpus["tpu_v4|pallas_bias_gelu|C=32,M=64,esize=4"][
        "measured_ms"] == 0.059


def test_autotune_loads_corpus_unmodified(tmp_path, monkeypatch):
    """The corpus file is a valid MXNET_AUTOTUNE_CACHE: autotune's
    loader and validation rules accept it as-is."""
    from mxnet_tpu import autotune
    db = perfwatch.PerfDB(str(tmp_path / "db"))
    db.ingest(KERNEL_MICRO_REC, source="kernel_micro", round=1,
              env=_env())
    path, _ = perfwatch.export_autotune_corpus(db)["tpu_v4"]
    # rewrite entry keys onto THIS process's device kind so lookup's
    # entry_key matches (the corpus was recorded on tpu_v4)
    with open(path) as f:
        corpus = json.load(f)
    kind = autotune._device_kind()
    rewritten = {k.replace("tpu_v4", kind): v
                 for k, v in corpus.items()}
    cache = tmp_path / "cache.json"
    with open(cache, "w") as f:
        json.dump(rewritten, f)
    monkeypatch.setenv("MXNET_AUTOTUNE", "cost")
    monkeypatch.setenv("MXNET_AUTOTUNE_CACHE", str(cache))
    autotune.clear()
    try:
        params = autotune.lookup(
            "pallas_bias_gelu", {"C": 32, "M": 64, "esize": 4},
            default={"block_rows": 8})
        assert params == {"block_rows": 32}
        # a validate hook that rejects falls back to the default —
        # the corpus obeys the cache-validation rules unchanged
        params = autotune.lookup(
            "pallas_bias_gelu", {"C": 32, "M": 64, "esize": 4},
            default={"block_rows": 8}, validate=lambda p: False)
        assert params == {"block_rows": 8}
    finally:
        autotune.clear()


# ---------------------------------------------------------------------------
# fleet sharing
# ---------------------------------------------------------------------------
def test_fleet_publish_and_merge_idempotent(tmp_path):
    db = perfwatch.PerfDB(str(tmp_path / "a"))
    for i, v in enumerate([100.0, 101.0]):
        db.ingest(_rec(v), round=i, env=_env())
    kv = dist.KV(dist.LocalKV())
    assert perfwatch.publish_fleet(db, kv) == 1
    other = perfwatch.PerfDB(str(tmp_path / "b"))
    assert perfwatch.merge_fleet(other, kv) == 1
    assert perfwatch.merge_fleet(other, kv) == 0     # idempotent
    rows = other.records("tpu_v4", "t_train_throughput")
    assert len(rows) == 1 and rows[0]["value"] == 101.0
    assert rows[0]["env"]["device_kind"] == "tpu_v4"


# ---------------------------------------------------------------------------
# heartbeat / telemetry surface
# ---------------------------------------------------------------------------
def test_heartbeat_perf_section_read_only(tmp_path, monkeypatch):
    # quiescent: no perf= section, and rendering registers nothing
    before = len(telemetry.snapshot()["counters"])
    line = telemetry.heartbeat_line()
    assert "perf=" not in line
    assert len(telemetry.snapshot()["counters"]) == before
    # ingest through the seam: the section appears
    monkeypatch.setenv("MXNET_PERF_DB", str(tmp_path))
    perfwatch.refresh()
    perfwatch.maybe_record(_rec(100.0, env=_env()), source="t")
    assert "perf=ingested:1,regressions:0" in telemetry.heartbeat_line()
