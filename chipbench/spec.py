"""Reads BENCHMARK.json and finds the files a cell is made of.

No jax here.  A cell names a configuration and a traffic mix; both are
data files.  Whatever belongs to one configuration, one mix, one kernel
or one per-layer metric is a file of its own, found by its name under
one of the benchmark's ``paths``, so a later PR adds files and one entry
to BENCHMARK.json and edits nothing that is there.
"""
import importlib.util
import json
import os

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class SpecError(Exception):
    pass


def load_benchmark(path=None):
    """The benchmark file as a dict, with ``root`` (the directory every
    relative path in it starts from) added."""
    path = path or os.path.join(ROOT, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    bench["root"] = ROOT
    return bench


def _find(bench, *parts):
    """The first ``<path>/<parts...>`` that exists, over ``paths``."""
    tried = []
    for p in bench["paths"]:
        cand = os.path.join(bench["root"], p, *parts)
        tried.append(cand)
        if os.path.isfile(cand):
            return cand
    raise SpecError(f"no such file under the benchmark's paths: {tried}")


def _by_name(rows, name, what):
    for row in rows:
        if row["name"] == name:
            return row
    raise SpecError(f"BENCHMARK.json names no {what} {name!r}; it has "
                    f"{[r['name'] for r in rows]}")


def cell(bench, workload):
    """Everything one cell is made of: its entry, its configuration
    (the entry and the file's content), its traffic parameters, and the
    metrics it reports."""
    wl = _by_name(bench["workloads"], workload, "workload")
    cfg_row = _by_name(bench["configs"], wl["config"], "config")
    with open(os.path.join(bench["root"], cfg_row["file"])) as f:
        config = json.load(f)
    with open(_find(bench, "traffic", wl["traffic"] + ".json")) as f:
        traffic = json.load(f)

    def mine(metric):
        return workload in metric.get("workloads", [workload])

    return {
        "workload": wl, "config_row": cfg_row, "config": config,
        "traffic": traffic,
        "end_to_end": [m for m in bench["end_to_end"] if mine(m)],
        "per_layer": [m for m in bench["per_layer"] if mine(m)],
    }


def load_module(bench, folder, name):
    """``<path>/<folder>/<name>.py`` as a module.  Names may hold dots
    (``mfu.train``), so the file is loaded by its path."""
    path = _find(bench, folder, name + ".py")
    spec = importlib.util.spec_from_file_location(
        f"chipbench_{folder}_{name.replace('.', '_').replace('-', '_')}",
        path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def limits(bench, workload):
    """The limit of every number compared in this cell: name -> limit."""
    with open(_find(bench, "limits", workload + ".json")) as f:
        return json.load(f)["limits"]


def peaks(bench, device_kind):
    """The chip's published peaks.  A device without a row is an error,
    never a default."""
    with open(_find(bench, "peaks.json")) as f:
        table = json.load(f)["devices"]
    if device_kind is None:
        return next(iter(table.values()))
    if device_kind not in table:
        raise SpecError(f"peaks.json has no row for device_kind "
                        f"{device_kind!r}; it has {sorted(table)}")
    return table[device_kind]
