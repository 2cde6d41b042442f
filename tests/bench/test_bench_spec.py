"""Every cell resolves by name to its configuration, architecture, mix,
traffic generator and metric readers, a cell and a generator that exist
only in a test fixture resolve the same way, the program's config is
held to every size a configuration file states, and BENCHMARK.json keeps
to the benchmark's contract."""
import copy
import json
import re

import pytest

from conftest import REPO
from gfbench import check, serve, spec, traffic

BENCH = spec.load()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_bench_cell_resolves(cell):
    c = spec.resolve(BENCH, cell)
    assert c["chips"] in (1, 4)
    assert {"model", "text_encoder", "program"} <= set(c["config"])
    assert c["arch"] is spec.arch(c["config"]["architecture"])
    assert set(c["limits"]) == set(check.NUMBERS)
    assert callable(spec.generator(c["mix"]["kind"]))
    names = {m["name"] for m in c["end_to_end"]}
    assert "setup_s" in names and len(names) >= 2
    assert c["per_layer"]
    for m in c["end_to_end"] + c["per_layer"]:
        assert callable(spec.reader(m["name"]))


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_bench_cell_traffic_is_the_same_work_for_every_seed(cell, peak):
    c = spec.resolve(BENCH, cell)
    model, lt = c["config"]["model"], c["config"]["text_encoder"]["prompt_len"]
    runs = [traffic.generate(c["mix"], model, c["arch"], peak,
                             BENCH["run_seconds"], s, lt)
            for s in (5, 2 ** 31 + 7)]
    shape = [sorted((p.cls, p.height, p.width, p.frames, p.steps, p.guidance,
                     p.due) for p in r) for r in runs]
    assert shape[0] == shape[1] and len(runs[0]) >= 2
    assert len({p.id for p in runs[0]} | {p.id for p in runs[1]}) == \
        2 * len(runs[0])


# N at the v5e's peak: 2 x 51 s over 50 steps at the step's least time
@pytest.mark.parametrize("cell,n", [("image-batch-1c", 27),
                                    ("video-batch-1c", 17)])
def test_bench_batch_size_is_pinned(cell, n, peak):
    c = spec.resolve(BENCH, cell)
    conf = c["config"]
    got = traffic.generate(c["mix"], conf["model"], c["arch"], peak,
                           BENCH["run_seconds"], 2 ** 31 + 5,
                           conf["text_encoder"]["prompt_len"])
    assert len(got) == n


def test_bench_fixture_generator_resolves_by_name(fixture_root, peak):
    mix = {"kind": "staggered", "classes": {"S": {"height": 64, "width": 64,
                                                   "frames": 1}},
           "mix": {"S": 1.0}, "steps": 2, "count": 4, "gap_s": 0.5}
    with pytest.raises(FileNotFoundError):
        spec.generator("staggered")
    got = traffic.generate(mix, {"patch_size": 2}, None, peak, 10.0, 3, 77,
                           root=fixture_root)
    assert [p.due for p in got] == [0.0, 0.5, 1.0, 1.5]
    assert sorted(p.id for p in got) == [f"s3-g{i:03d}" for i in range(4)]


def test_bench_fixture_cell_resolves(fixture_root):
    fixture = spec.load(fixture_root)
    assert "tiny-batch" not in {w["name"] for w in BENCH["workloads"]}
    c = spec.resolve(fixture, "tiny-batch", root=fixture_root)
    assert c["config"]["name"] == "tiny"
    assert c["mix"]["steps"] == 3
    assert [m["name"] for m in c["end_to_end"]] == [
        "setup_s", "denoise_tokens_per_s"]


@pytest.mark.parametrize("conf", sorted(
    p.name for p in (REPO / "bench" / "configs").glob("*.json")))
def test_bench_config_matches_program(conf):
    with open(REPO / "bench" / "configs" / conf) as f:
        c = json.load(f)
    cfg = serve.program_config(c)
    assert cfg.use_pallas
    assert cfg.dit.in_channels == c["model"]["in_channels"]
    assert cfg.dit.cond_dim == c["text_encoder"]["d_model"]
    assert c["source"].startswith("https://")
    entry = {x["file"]: x for x in BENCH["configs"]}[f"bench/configs/{conf}"]
    assert entry["reduced"] == c["reduced"] and entry["source"] == c["source"]


@pytest.mark.parametrize("change,refusal", [
    (("d_ff", 4096), "is not the file's"),
    (("num_double_layers", 4), "has no"),
    (("latent_frames", 2), "is not the file's")])
def test_bench_program_config_refuses_a_size_it_does_not_hold(
        change, refusal):
    with open(REPO / "bench" / "configs" / "pixart-sigma-xl2-1024.json") as f:
        c = json.load(f)
    serve.program_config(c)
    bad = copy.deepcopy(c)
    bad["model"][change[0]] = change[1]
    with pytest.raises(ValueError, match=refusal):
        serve.program_config(bad)


def test_bench_table_keeps_to_contract():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in BENCH[k]]
    assert all(NAME.match(n) for n in names)
    for k in ("configs", "workloads"):
        assert len({x["name"] for x in BENCH[k]}) == len(BENCH[k])
    assert len({m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]}) \
        == len(BENCH["end_to_end"]) + len(BENCH["per_layer"])
    cells = {w["name"] for w in BENCH["workloads"]}
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e
        for w in m["workloads"]:
            assert w in cells
            assert "workloads" not in e2e[m["moves"]] or \
                w in e2e[m["moves"]]["workloads"]
    for w in BENCH["workloads"]:
        assert len(w["why"]) <= 200
        assert (REPO / "bench" / "traffic" / f"{w['traffic']}.json").exists()
    for c in BENCH["configs"]:
        assert (REPO / c["file"]).exists()
        assert c["file"].startswith("bench/")
    assert len(json.dumps(BENCH)) < 64 * 1024
