"""Find a cell's configuration, traffic mix and metric readers by name.

Everything that belongs to one configuration, mix or metric is a file of
its own, found from the names in ``BENCHMARK.json``:

* a cell is an entry of ``workloads``;
* its configuration is the ``file`` of the entry of ``configs`` it names;
* the configuration's ``architecture`` is ``bench/archs/<name>.py``: the
  DiT block's weights, reference velocity, guided rows and operation
  counts (see ``bench/archs/adaln-cross-swiglu.py`` for what it gives);
* its traffic mix is ``bench/traffic/<traffic>.json``, planned by the
  generator of the mix's ``kind``, ``bench/generators/<kind>.py``;
* the limits that decide its ``correct`` are ``bench/limits/<cell>.json``;
* each metric is read by ``bench/metrics/<metric>.py``, whose
  ``read(run)`` returns a number, or None where it finds nothing to read.

A new cell, mix, arrival process, configuration, architecture or metric
is new files and entries.  Code files are looked for under the root the
cell was resolved from, then under this benchmark's own, so that a test
fixture adds only the files it needs.
"""
from __future__ import annotations

import functools
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
    """A cell with its configuration, architecture, mix and metric
    entries."""
    cell = _named(bench["workloads"], name, "workload")
    centry = _named(bench["configs"], cell["config"], "config")
    with open(root / centry["file"]) as f:
        conf = json.load(f)
    if "architecture" not in conf:
        raise KeyError(f"{centry['file']} names no architecture")
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
            "arch": arch(conf["architecture"], root), "mix": mix,
            "limits": limits, "end_to_end": e2e, "per_layer": layer}


def _module(folder: str, name: str, root: Path):
    path = root / "bench" / folder / f"{name}.py"
    if not path.exists():
        path = ROOT / "bench" / folder / f"{name}.py"
    return _load(path, f"bench_{folder}_" + "".join(
        c if c.isalnum() else "_" for c in name))


@functools.cache
def _load(path: Path, mod_name: str):
    """A code file, executed once a process."""
    spec = importlib.util.spec_from_file_location(mod_name, path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reader(name: str, root: Path = ROOT):
    """The ``read`` function of ``bench/metrics/<name>.py``."""
    return _module("metrics", name, root).read


def generator(kind: str, root: Path = ROOT):
    """The ``plan`` function of ``bench/generators/<kind>.py``."""
    return _module("generators", kind, root).plan


def arch(name: str, root: Path = ROOT):
    """The module ``bench/archs/<name>.py``."""
    return _module("archs", name, root)


def arch_of(run: dict):
    """The architecture a run record names, from the root it ran from."""
    return arch(run["arch"], Path(run["root"]))


def peaks(root: Path = ROOT) -> dict:
    with open(root / "bench" / "peaks.json") as f:
        return json.load(f)
