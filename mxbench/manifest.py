"""Finds the benchmark's data files by name. A cell, a traffic mix, a
configuration and a per-layer metric are each files of their own; a new
one is added by adding files (and the entries in BENCHMARK.json), never
by editing a file that is there.

    workloads/<cell>.json          config, traffic, chips, metrics,
                                   layer_metrics, why
    configs/<config>.json + .py    sizes; block builder + model FLOPs
    reference/<config>.py          plain float32 jax.numpy reference
    traffic/<mix>.json             parameters; "kind" names the generator
    traffic/<kind>.py              the one general generator of a kind
    layer_metrics/<metric>.py      read(run) -> number or None
    peaks.json                     device peaks by device_kind
"""
from __future__ import annotations

import importlib.util
import json
import os

ROOT = os.path.dirname(os.path.abspath(__file__))


def _path(*parts: str) -> str:
    return os.path.join(ROOT, *parts)


def load_json(*parts: str) -> dict:
    path = _path(*parts)
    if not os.path.exists(path):
        raise FileNotFoundError("mxbench: no file %s" % path)
    with open(path) as f:
        return json.load(f)


def load_module(*parts: str):
    """Import a file under mxbench/ by path (metric names hold dots,
    so their files are not importable by name)."""
    path = _path(*parts)
    if not os.path.exists(path):
        raise FileNotFoundError("mxbench: no file %s" % path)
    name = "mxbench._file." + "_".join(parts).replace(".", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def names_in(directory: str, suffix: str) -> list:
    return sorted(f[:-len(suffix)] for f in os.listdir(_path(directory))
                  if f.endswith(suffix) and not f.startswith("_"))


def workload_names() -> list:
    return names_in("workloads", ".json")


def workload(name: str) -> dict:
    cell = load_json("workloads", name + ".json")
    cell["name"] = name
    return cell


def config(name: str):
    """(sizes dict, builder module, reference module)."""
    return (load_json("configs", name + ".json"),
            load_module("configs", name + ".py"),
            load_module("reference", name + ".py"))


def traffic(name: str):
    """(parameters dict, generator module of the file's ``kind``)."""
    params = load_json("traffic", name + ".json")
    return params, load_module("traffic", params["kind"] + ".py")


def layer_metric(name: str):
    return load_module("layer_metrics", name + ".py")


def peaks(device_kind: str) -> dict:
    table = load_json("peaks.json")
    if device_kind not in table or device_kind.startswith("_"):
        raise KeyError("mxbench/peaks.json has no peaks for device_kind "
                       "%r: add the device with its source" % device_kind)
    return table[device_kind]
