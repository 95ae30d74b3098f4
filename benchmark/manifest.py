"""BENCHMARK.json and the files it names.

Everything that belongs to one configuration, traffic mix, cell or
per-layer metric sits in a file of its own and is found by its name:

    benchmark/configs/<config>.json      sizes (+ <config>_reference.py)
    benchmark/traffic/<traffic>.json     parameters of one fixed trace
    benchmark/cells/<workload>.json      the cell's limits for `correct`
    benchmark/metrics/<metric>.json      a per-layer metric's reader + args;
                                         all else of it is BENCHMARK.json's
    benchmark/readers/<reader>.py        one function `read(ctx, **args)`
    benchmark/families/<family>.py       how a configuration is built

A later PR adds files and entries in BENCHMARK.json; it edits nothing.
"""
from __future__ import annotations

import importlib
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def _load(kind, name):
    path = os.path.join(HERE, kind, f"{name}.json")
    with open(path) as f:
        return json.load(f)


def benchmark_json():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def config(name):
    return dict(_load("configs", name), name=name)


def traffic(name):
    return _load("traffic", name)


def cell_file(name):
    return _load("cells", name)


def metric_file(name):
    return _load("metrics", name)


def reader(name):
    return importlib.import_module(f"benchmark.readers.{name}").read


def family(name):
    return importlib.import_module(f"benchmark.families.{name}")


def reference(config_name):
    return importlib.import_module(
        f"benchmark.configs.{config_name}_reference")


def workload(name, manifest=None):
    manifest = manifest or benchmark_json()
    for w in manifest["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                   f"(known: {[w['name'] for w in manifest['workloads']]})")


def cell(workload_name, rehearsal=False, manifest=None):
    """(workload entry, configuration, traffic mix, limits) of a cell.
    With `rehearsal`, the cell file's tiny sizes are laid over the
    configuration and the mix, and its limits (read on the CPU at the
    tiny size, where the chip's would not tell the control from the
    program) over the cell's own."""
    wl = workload(workload_name, manifest)
    cfg, mix = config(wl["config"]), traffic(wl["traffic"])
    own = cell_file(wl["name"])
    limits = own["limits"]
    if rehearsal:
        over = own["rehearsal"]
        cfg, mix = {**cfg, **over["config"]}, {**mix, **over["traffic"]}
        limits = {**limits, **over.get("limits", {})}
    return wl, cfg, mix, limits


def metrics_of(workload_name, group, manifest=None):
    """The metrics of `group` ('end_to_end' / 'per_layer') that the
    cell reports. An end-to-end metric without `workloads` is every
    cell's; a per-layer metric without it is reported wherever the
    end-to-end metric it moves is, the cells of later PRs too."""
    manifest = manifest or benchmark_json()
    mine = {m["name"] for m in manifest["end_to_end"]
            if workload_name in m.get("workloads", [workload_name])}
    if group == "end_to_end":
        return [m for m in manifest[group] if m["name"] in mine]
    return [m for m in manifest[group]
            if (workload_name in m["workloads"] if "workloads" in m
                else m["moves"] in mine)]
