"""Find a cell's parts by name: the data-driven half of the harness.

``BENCHMARK.json`` at the checkout's root lists cells, configurations and
metrics. Each part lives in a file of its own, found by its name:

* a configuration: the ``file`` its entry names (``configs/<name>.json``);
* a traffic mix: ``traffic/<name>.json``, parameters that the one
  generator (``run.drive``) reads;
* a metric, end-to-end or per-layer: ``metrics/<name>.py``, a module with
  ``read(ctx) -> float | None``. A metric split by cells, ``<base>.<part>``
  (the same quantity under another bound, or moving another end-to-end
  metric), is read by ``metrics/<base>.py`` unless it has a file of its
  own.

A later PR adds a cell, a mix or a metric by adding files and entries;
nothing here names one.
"""

from __future__ import annotations

import importlib.util
import json
import os

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def load(root: str = ROOT) -> dict:
    """The parsed ``BENCHMARK.json`` of the checkout at ``root``."""
    with open(os.path.join(root, "BENCHMARK.json")) as fd:
        spec = json.load(fd)
    spec["_root"] = root
    return spec


def _named(entries: list[dict], name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"no {what} named {name!r} in BENCHMARK.json")


def cell(spec: dict, name: str) -> dict:
    return _named(spec["workloads"], name, "workload")


def config(spec: dict, name: str) -> dict:
    """The configuration file of ``name``, with ``name`` and ``dir`` (the
    directory its side files are read from) added."""
    entry = _named(spec["configs"], name, "config")
    path = os.path.join(spec["_root"], entry["file"])
    with open(path) as fd:
        data = json.load(fd)
    data["name"] = name
    data["dir"] = os.path.dirname(path)
    return data


def traffic(spec: dict, name: str) -> dict:
    path = os.path.join(spec["_root"], "benchmark", "traffic", name + ".json")
    with open(path) as fd:
        data = json.load(fd)
    data["name"] = name
    return data


def metrics(spec: dict, cell_name: str, traced: bool) -> list[dict]:
    """The cell's end-to-end metrics (untraced) or per-layer ones
    (traced): every entry whose ``workloads`` lists the cell, or that
    has no ``workloads`` key."""
    entries = spec["per_layer"] if traced else spec["end_to_end"]
    return [m for m in entries
            if cell_name in m.get("workloads", [cell_name])]


def reader(spec: dict, metric_name: str):
    """``read(ctx)`` of ``metrics/<metric_name>.py``, or of
    ``metrics/<base>.py`` for a metric ``<base>.<part>`` without a file of
    its own."""
    folder = os.path.join(spec["_root"], "benchmark", "metrics")
    path = os.path.join(folder, metric_name + ".py")
    if not os.path.isfile(path):
        path = os.path.join(folder, metric_name.split(".")[0] + ".py")
    if not os.path.isfile(path):
        raise KeyError(f"no reader file for metric {metric_name!r}")
    mod_name = "benchmark_metric_" + metric_name.replace(".", "_").replace(
        "-", "_")
    loaded = importlib.util.spec_from_file_location(mod_name, path)
    module = importlib.util.module_from_spec(loaded)
    loaded.loader.exec_module(module)
    return module.read


def peaks(device_kind: str) -> dict:
    """The published peaks of ``device_kind`` from ``peaks.json``. A kind
    the table does not hold is an error: there is no default device."""
    with open(os.path.join(BENCH, "peaks.json")) as fd:
        table = json.load(fd)["devices"]
    if device_kind not in table:
        raise KeyError(f"device kind {device_kind!r} is not in "
                       f"benchmark/peaks.json; add its published peaks")
    return table[device_kind]
