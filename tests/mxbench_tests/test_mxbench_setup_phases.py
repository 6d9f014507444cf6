"""The nine ``setup_*_s`` readers (``layer_metrics/_setup_phases.py``):
nothing on a program without ``telemetry.startup_phases``; over a
hand-made timeline the eight phases and ``setup_unattributed_s`` are
``setup_s``, split by the rule the program states."""
import json
import os
import time

import pytest

from mxbench import manifest, meters
from mxbench.record import Run

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

READERS = {
    "setup_import_s": "host loop", "setup_init_s": "host loop",
    "setup_graph_s": "graph", "setup_place_s": "training step",
    "setup_lower_s": "compile", "setup_compile_miss_s": "compile",
    "setup_cache_load_s": "compile", "setup_first_launch_s": "device",
    "setup_unattributed_s": "device"}


def _run(setup_s):
    return Run(cell={}, sizes={}, traffic={}, device_kind="TPU v5 lite",
               chips=1, correct=True, attempted=1, failed=0,
               end_to_end={"setup_s": (setup_s, "s")}, window_s=1.0,
               samples=1, flops_per_sample=1.0, peak_bytes=0,
               setup_compiles=0, setup_compile_s=0.0, setup_cache_hits=0)


@pytest.fixture
def program(monkeypatch):
    """The program's registries, emptied, with telemetry on."""
    from mxnet_tpu import compilewatch, telemetry
    monkeypatch.setenv("MXNET_TELEMETRY", "1")
    telemetry.refresh()
    telemetry.reset()
    compilewatch.reset()
    yield telemetry, compilewatch
    telemetry.refresh()
    telemetry.reset()
    compilewatch.reset()


@pytest.mark.parametrize("name", sorted(READERS))
def test_reader_is_found_and_reads_nothing_from_a_program_without_phases(
        name, monkeypatch, program):
    telemetry, _ = program
    # no cell lists the readers yet (a cell's list is its own file's, a
    # `benchmark` issue's to edit), so BENCHMARK.json has no entry for
    # them: a `workloads` list may not be empty. The layer each entry
    # is to name is one the benchmark already has.
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    assert READERS[name] in {m["layer"] for m in bench["per_layer"]}
    assert "setup_s" in {m["name"] for m in bench["end_to_end"]}
    assert name in manifest.names_in("layer_metrics", ".py")
    reader = manifest.layer_metric(name)
    assert reader.UNIT == "s"
    monkeypatch.delattr(telemetry, "startup_phases")
    assert reader.read(_run(60.0)) is None


def test_phases_and_the_rest_are_setup_s(monkeypatch, program, capsys):
    telemetry, compilewatch = program
    # the process's start on the program's clock; every edge of the
    # timeline lies whole seconds from the cut, /proc's 10 ms away
    start = time.perf_counter() - meters.process_age_s()
    setup_s = 100.0

    def at(*spans):
        return [("setup::" + n, start + a, start + b, None)
                for n, a, b in spans]

    monkeypatch.setattr(telemetry._SETUPLOG, "spans", at(
        ("import", 1.0, 9.0), ("native", 4.0, 5.0),     # nested
        ("init", 10.0, 30.0), ("first_launch", 16.0, 17.0),
        ("graph", 31.0, 33.0), ("place", 40.0, 44.0),
        ("first_launch", 60.0, 60.5),
        ("init", 105.0, 110.0)))            # after the set-up instant

    def record(fn, t, stages, cache):
        compilewatch.publish({
            "site": "test", "fn": fn, "instance": fn, "kind": "compile",
            "stages": stages, "flops": None, "bytes": {}, "signature": [],
            "changed": [], "time": start + t, "persistent_cache": cache})

    record("eager_op", 12.0, {"trace": 1.0, "lower": 1.0, "compile": 2.0},
           "miss")                          # inside init
    record("sharded_step:fused_step", 50.0,
           {"trace": 3.0, "lower": 2.0, "compile": 5.0}, "hit")
    record("late", 120.0, {"trace": 1.0, "lower": 1.0, "compile": 1.0},
           "miss")                          # inside the window: not set-up
    run = _run(setup_s)
    got = {name: manifest.layer_metric(name).read(run) for name in READERS}
    assert got == pytest.approx({
        "setup_import_s": 8.0, "setup_init_s": 20.0 - 4.0 - 1.0,
        "setup_graph_s": 2.0, "setup_place_s": 4.0,
        "setup_lower_s": 2.0 + 5.0, "setup_compile_miss_s": 2.0,
        "setup_cache_load_s": 5.0, "setup_first_launch_s": 1.5,
        "setup_unattributed_s": 100.0 - (8 + 20 + 2 + 4 + 10 + 0.5)},
        abs=1e-6)
    assert sum(got.values()) == pytest.approx(setup_s, abs=1e-9)
    # one timeline a run, and the line that names the misses
    assert run.setup_phases["covered"] == pytest.approx(44.5, abs=1e-6)
    out = capsys.readouterr().out
    assert out.count("set-up by the program's phases") == 1
    assert "eager_op" in out and "'late'" not in out
