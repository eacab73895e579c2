"""Finds a cell's files by the names in ``BENCHMARK.json`` and refuses bad names.

A cell is ``{name, config, traffic, chips}``. Its configuration is the file the
``configs`` entry names; its traffic mix is ``<dir>/traffic/<traffic>.json``
and each of its metrics ``<dir>/metrics/<metric>.py``, looked for in every
directory of ``paths`` in order. Nothing here knows a cell, a model or a metric
by name.
"""

from __future__ import annotations

import importlib.util
import json
import os
import re

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")


class BenchmarkError(ValueError):
    """``BENCHMARK.json`` or a file it names is not what the contract allows."""


def _name(value, what):
    if not isinstance(value, str) or not NAME.match(value):
        raise BenchmarkError(f"{what} {value!r}: a name is 1-64 of "
                             f"A-Z a-z 0-9 _ . - and starts with none of . -")
    return value


def _metric(entry, end_to_end):
    _name(entry.get("name"), "metric")
    if not isinstance(entry.get("unit"), str) or not UNIT.match(entry["unit"]):
        raise BenchmarkError(f"metric {entry['name']}: unit {entry.get('unit')!r}")
    if entry.get("better") not in ("lower", "higher"):
        raise BenchmarkError(f"metric {entry['name']}: better {entry.get('better')!r}")
    allowed = ("device_trace", "host_clock") if end_to_end else SOURCES
    if entry.get("source") not in allowed:
        raise BenchmarkError(f"metric {entry['name']}: source {entry.get('source')!r}")
    return entry


def load_benchmark(path=None) -> dict:
    """The parsed benchmark file, every name and unit checked."""
    with open(path or os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for c in bench["configs"]:
        _name(c["name"], "config")
    for w in bench["workloads"]:
        _name(w["name"], "workload")
        _name(w["config"], "config")
        _name(w["traffic"], "traffic")
        if w["chips"] not in (1, 4):
            raise BenchmarkError(f"workload {w['name']}: chips {w['chips']!r}")
    for m in bench["end_to_end"]:
        _metric(m, True)
    for m in bench["per_layer"]:
        _metric(m, False)
    return bench


def _find(paths, *parts):
    for d in paths:
        p = os.path.join(ROOT, d, *parts)
        if os.path.isfile(p):
            return p
    raise BenchmarkError(f"no {os.path.join(*parts)} under any of {paths}")


def _lists(metric, cell_name):
    return "workloads" not in metric or cell_name in metric["workloads"]


def load_cell(name: str, path=None) -> dict:
    """Everything one run needs: the cell's entry, its configuration and traffic
    as read from their files, and its metrics' entries (per-layer ones with the
    path of their reader)."""
    bench = load_benchmark(path)
    _name(name, "workload")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise BenchmarkError(f"unknown workload {name!r}; have {sorted(cells)}")
    cell = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    if cell["config"] not in configs:
        raise BenchmarkError(f"workload {name}: unknown config {cell['config']!r}")
    with open(os.path.join(ROOT, configs[cell["config"]]["file"])) as f:
        config = json.load(f)
    with open(_find(bench["paths"], "traffic", cell["traffic"] + ".json")) as f:
        traffic = json.load(f)
    # a metric with no "workloads" key belongs to every cell that reports the
    # end-to-end metric it moves (an end-to-end one: to every cell)
    end_to_end = [m for m in bench["end_to_end"] if _lists(m, name)]
    reported = {m["name"] for m in end_to_end}
    per_layer = [dict(m, reader=_find(bench["paths"], "metrics", m["name"] + ".py"))
                 for m in bench["per_layer"]
                 if _lists(m, name) and m["moves"] in reported]
    return {"cell": cell, "config": config, "traffic": traffic,
            "end_to_end": end_to_end, "per_layer": per_layer,
            "run_seconds": bench["run_seconds"]}


def load_reader(path: str):
    """The ``read(run)`` function of one per-layer metric's file."""
    spec = importlib.util.spec_from_file_location(
        "benchmark_metric_" + re.sub(r"\W", "_", os.path.basename(path)), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def load_peaks(device_kind: str) -> dict:
    """This chip's peaks; a chip the table lacks is an error, not a default."""
    with open(os.path.join(ROOT, "benchmark", "peaks.json")) as f:
        peaks = json.load(f)
    if device_kind not in peaks:
        raise BenchmarkError(f"device kind {device_kind!r} is not in "
                             f"benchmark/peaks.json ({sorted(peaks)})")
    return peaks[device_kind]
