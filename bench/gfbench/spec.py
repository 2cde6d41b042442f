"""Find a cell's configuration, traffic mix and metric readers by name.

Everything that belongs to one configuration, mix or metric is a file of
its own, found from the names in ``BENCHMARK.json``:

* a cell is an entry of ``workloads``;
* its configuration is the ``file`` of the entry of ``configs`` it names;
* its traffic mix is ``bench/traffic/<traffic>.json``, planned by the
  generator of the mix's ``kind``, ``bench/generators/<kind>.py``;
* the limits that decide its ``correct`` are ``bench/limits/<cell>.json``;
* each metric is read by ``bench/metrics/<metric>.py``, whose
  ``read(run)`` returns a number, or None where it finds nothing to read.

A new cell, mix, arrival process, configuration or metric is new files
and entries.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


def load(root: Path = ROOT) -> dict:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def _named(entries: list, name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"no {what} named {name!r}")


def _lists(entry: dict, cell: str) -> bool:
    return "workloads" not in entry or cell in entry["workloads"]


def resolve(bench: dict, name: str, root: Path = ROOT) -> dict:
    """A cell with its configuration, mix and metric entries."""
    cell = _named(bench["workloads"], name, "workload")
    centry = _named(bench["configs"], cell["config"], "config")
    with open(root / centry["file"]) as f:
        conf = json.load(f)
    with open(root / "bench" / "traffic" / f"{cell['traffic']}.json") as f:
        mix = json.load(f)
    with open(root / "bench" / "limits" / f"{name}.json") as f:
        limits = json.load(f)["limits"]
    e2e = [m for m in bench["end_to_end"] if _lists(m, name)]
    moved = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"]
             if (name in m["workloads"] if "workloads" in m
                 else m["moves"] in moved)]
    return {"name": name, "chips": cell["chips"], "config": conf,
            "mix": mix, "limits": limits, "end_to_end": e2e,
            "per_layer": layer}


def _function(folder: str, name: str, fn: str, root: Path):
    path = root / "bench" / folder / f"{name}.py"
    mod_name = f"bench_{folder}_" + "".join(
        c if c.isalnum() else "_" for c in name)
    spec = importlib.util.spec_from_file_location(mod_name, path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return getattr(mod, fn)


def reader(name: str, root: Path = ROOT):
    """The ``read`` function of ``bench/metrics/<name>.py``."""
    return _function("metrics", name, "read", root)


def generator(kind: str, root: Path = ROOT):
    """The ``plan`` function of ``bench/generators/<kind>.py``."""
    return _function("generators", kind, "plan", root)


def peaks(root: Path = ROOT) -> dict:
    with open(root / "bench" / "peaks.json") as f:
        return json.load(f)
