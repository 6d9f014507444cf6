#!/usr/bin/env python
"""The standardized bench-JSON schema: one shared emit + validate
helper for every benchmark and micro-gate in the repo (ISSUE 19).

Every tool that measures something ends its run by printing exactly
one JSON object on one stdout line, shaped::

    {"metric": <snake_case str>,     # headline series name
     "value":  <finite number>,      # the headline measurement
     "unit":   <non-empty str>,      # "images/sec/chip", "ms", ...
     ...}                            # any extra JSON-serializable
                                     # context (sub-metrics, tables)

Before this module each emitter hand-rolled that dict; now they all
route through :func:`emit`, which (a) validates the record against
the schema — a malformed record fails the emitting tool loudly
instead of poisoning the trajectory silently, (b) stamps the
environment fingerprint (device_kind, git rev, MXNET_* flags) that
the perfwatch store partitions on, and (c) feeds the record through
the ``perfwatch.maybe_record`` ingestion seam — inert unless
MXNET_PERF_DB names a trajectory store (see
docs/OBSERVABILITY.md "Performance trajectory").

The driver that wraps bench stdout into ``BENCH_r*.json`` parses the
LAST line that parses as JSON — :func:`last_json_line` is that exact
rule, importable so tests and the perfwatch ingester agree with it.
"""
from __future__ import annotations

import json
import math
import re
import sys
from typing import Any, Dict, List, Optional

__all__ = ["REQUIRED", "validate", "check", "emit", "last_json_line"]

REQUIRED = ("metric", "value", "unit")

_METRIC_RE = re.compile(r"^[a-z][a-z0-9_]*$")


def validate(record: Any) -> List[str]:
    """All the ways ``record`` violates the bench-JSON schema
    (empty list = valid)."""
    if not isinstance(record, dict):
        return ["record is %s, not a dict" % type(record).__name__]
    problems = []
    metric = record.get("metric")
    if not isinstance(metric, str) or not _METRIC_RE.match(metric):
        problems.append("metric %r is not a snake_case identifier"
                        % (metric,))
    value = record.get("value")
    if isinstance(value, bool) or not isinstance(value, (int, float)):
        problems.append("value %r is not a number" % (value,))
    elif not math.isfinite(value):
        problems.append("value %r is not finite" % (value,))
    unit = record.get("unit")
    if not isinstance(unit, str) or not unit:
        problems.append("unit %r is not a non-empty string" % (unit,))
    for k in record:
        if not isinstance(k, str):
            problems.append("non-string key %r" % (k,))
    env = record.get("env")
    if env is not None:
        if not isinstance(env, dict) or \
                not isinstance(env.get("device_kind"), str):
            problems.append("env %r lacks a device_kind string"
                            % (env,))
    try:
        json.dumps(record)
    except (TypeError, ValueError) as e:
        problems.append("not JSON-serializable: %s" % e)
    return problems


def check(record: Any) -> Dict[str, Any]:
    """Raise ValueError (naming every problem) unless ``record`` is
    schema-valid; returns it for chaining."""
    problems = validate(record)
    if problems:
        raise ValueError("bench-JSON schema violation: "
                         + "; ".join(problems))
    return record


def emit(record: Dict[str, Any], *, source: str = "",
         stream=None) -> Dict[str, Any]:
    """Validate, fingerprint, record, and print one bench-JSON line.

    The record is printed on its own stdout line (the driver/parse
    contract) AFTER being stamped with the perfwatch environment
    fingerprint and offered to the trajectory store. A failure in
    either raises: a record without its fingerprint is not one to
    compare. Returns the (enriched) record."""
    from mxnet_tpu import perfwatch
    check(record)
    if "env" not in record:
        record["env"] = perfwatch.environment_fingerprint()
    perfwatch.maybe_record(record, source=source)
    print(json.dumps(record), file=stream or sys.stdout)
    return record


def last_json_line(text: str) -> Optional[Dict[str, Any]]:
    """The last stdout line that parses as a JSON object — the exact
    rule the BENCH_r*.json driver wrapper uses for its ``parsed``
    field (DeprecationWarnings or stray prints between records do not
    confuse it, but a tool must keep its record on ONE line)."""
    out = None
    for line in text.splitlines():
        line = line.strip()
        if line.startswith("{") and line.endswith("}"):
            try:
                obj = json.loads(line)
            except ValueError:
                continue
            if isinstance(obj, dict):
                out = obj
    return out


if __name__ == "__main__":
    # validator mode: pipe tool stdout (or a record) through it
    rec = last_json_line(sys.stdin.read())
    if rec is None:
        print("bench_json: no JSON object line found")
        sys.exit(1)
    probs = validate(rec)
    for p in probs:
        print("bench_json: %s" % p)
    print("bench_json: %s (metric=%s)"
          % ("INVALID" if probs else "OK", rec.get("metric")))
    sys.exit(1 if probs else 0)
