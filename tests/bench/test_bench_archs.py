"""A DiT architecture is a file of its own.  A block that exists only in
the test fixture (``fixture/bench/archs/joint-guidance-embed.py``: joint
text-latent attention, the guidance scale as an input, one row per guided
step, no adaLN kernel) goes through the cell's resolution, the reference,
the numbers that decide ``correct``, the output sample, the step spans,
the metric readers and the traffic generator with no edit to the harness,
and each of them uses its counts and rows."""
import json
import shutil
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import FIXTURE
from gfbench import check, flops, serve, spec, traffic
from gfbench import reference as R

SEED = 3000000013
CELL = "tiny-joint-batch"
JOINT, ADALN = "joint-guidance-embed", "adaln-cross-swiglu"
PEAK = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


@pytest.fixture(scope="module")
def cell():
    return spec.resolve(spec.load(FIXTURE), CELL, root=FIXTURE)


@pytest.fixture(scope="module")
def ref(cell):
    return check.Reference(cell["config"], SEED, cell["arch"])


def test_bench_fixture_arch_resolves(cell):
    arch = cell["arch"]
    assert cell["config"]["architecture"] == JOINT
    assert arch is spec.arch(JOINT, FIXTURE)
    assert arch is spec.arch_of({"arch": JOINT, "root": str(FIXTURE)})
    with pytest.raises(FileNotFoundError):
        spec.arch(JOINT)
    assert arch.rows(4.5) == arch.rows(None) == ("cond",)
    # the cells that name the benchmarked block find it from the fixture
    tiny = spec.resolve(spec.load(FIXTURE), "tiny-batch", root=FIXTURE)
    assert tiny["arch"] is spec.arch(ADALN)
    assert tiny["arch"].rows(4.5) == ("cond", "uncond")


def test_bench_config_without_architecture_is_refused(tmp_path):
    root = tmp_path / "fx"
    shutil.copytree(FIXTURE / "bench", root / "bench")
    shutil.copy(FIXTURE / "BENCHMARK.json", root / "BENCHMARK.json")
    path = root / "bench" / "configs" / "tiny-joint.json"
    conf = json.loads(path.read_text())
    del conf["architecture"]
    path.write_text(json.dumps(conf))
    with pytest.raises(KeyError, match="names no architecture"):
        spec.resolve(spec.load(root), CELL, root=root)


def _items(ref, guidance=4.5, apply=None):
    """A sound step, encode and initial latent of one request, made from
    the reference itself; ``apply`` is the guidance the step's velocity
    was taken at (the request's own by default)."""
    rid, steps, n_tok = "s1-b000", 3, 16
    pd = 2 ** 2 * ref.conf["model"]["in_channels"]
    sigma0 = float(R.flow_sigmas(steps, ref.conf["flow_shift"])[0])
    x_in = R.initial_latent(rid, n_tok, pd, sigma0)
    it = {"what": "step", "req": rid, "step": 0, "steps": steps,
          "guidance": guidance, "x_in": x_in}
    with jax.default_matmul_precision("highest"):
        v, s_now, s_next = ref.step(dict(it, guidance=apply or guidance),
                                    jnp.float32)
    it["x_out"] = (x_in + np.float32(s_next - s_now) * v[0]).astype(
        np.float32)
    return [it,
            {"what": "encode", "req": rid, "guidance": guidance,
             "embeds": ref.embeds(rid, False, jnp.float32),
             "embeds_uncond": None},
            {"what": "latent0", "req": rid, "steps": steps, "latent": x_in}]


def test_bench_fixture_arch_gaps_and_faults(ref):
    items = _items(ref)
    rows, _, _ = ref.step(items[0], jnp.float32)
    assert rows.shape[0] == 1
    assert not any(uncond for _, uncond, _ in ref._embeds)
    found = check.gaps(ref, items)
    assert set(found) == set(check.NUMBERS)
    assert found["step_gap"] < 1e-4 and found["state_gap"] < 1e-6
    assert found["encode_gap"] == found["latent0_gap"] == 0.0
    assert check.verdict(found, {k: 1e-3 for k in check.NUMBERS})[0]
    # the guidance scale is an input: a step taken at another scale is
    # another velocity
    other = check.gaps(ref, _items(ref, apply=1.5))
    assert other["step_gap"] > 0.01
    control = check.gaps(ref, items, control=True)
    assert control["state_gap"] > found["state_gap"]
    faults = check.fault_readings(ref, items)
    assert set(faults) == {"unchanged_state.step_gap",
                           "unchanged_state.state_gap"}


def _graph(guidance, with_uncond):
    """A request's graph as ``check.collect`` reads it: an encode task
    (text, initial latent) and one denoise step."""
    def art(**data):
        return SimpleNamespace(layout=SimpleNamespace(ranks=[0]),
                               data={0: data})
    txt = {"embeds": np.ones((4, 8), np.float32)}
    if with_uncond:
        txt["embeds_uncond"] = np.zeros((4, 8), np.float32)
    lat = np.zeros((16, 64), np.float32)
    arts = {"txt": art(**txt), "lat0": art(latent=lat),
            "lat1": art(latent=lat + 1)}
    tasks = {"enc": SimpleNamespace(kind="encode", outputs=["txt", "lat0"]),
             "d0": SimpleNamespace(kind="denoise", step_index=0,
                                   inputs=["txt", "lat0"], outputs=["lat1"])}
    req = SimpleNamespace(id="r0", steps=3, guidance=guidance)
    return SimpleNamespace(artifacts=arts, tasks=tasks, request=req)


def test_bench_collect_asks_uncond_embeds_only_of_an_uncond_row():
    step = {"req": "r0", "task": "d0", "finish": 1.0}
    joint, adaln = spec.arch(JOINT, FIXTURE), spec.arch(ADALN)
    cp = SimpleNamespace(graphs={"r0": _graph(4.5, with_uncond=False)})
    items = check.collect(cp, [step], 1, 3, joint)
    assert [it["what"] for it in items] == ["step", "encode", "latent0"]
    assert items[1]["embeds_uncond"] is None
    with pytest.raises(KeyError, match="embeds_uncond"):
        check.collect(cp, [step], 1, 3, adaln)
    cp = SimpleNamespace(graphs={"r0": _graph(4.5, with_uncond=True)})
    assert check.collect(cp, [step], 1, 3, adaln)[1]["embeds_uncond"] \
        is not None


@pytest.mark.parametrize("name,guidance,cfg,rows", [
    (JOINT, 4.5, 1, 1), (JOINT, None, 1, 1), (ADALN, 4.5, 1, 2),
    (ADALN, 4.5, 2, 1), (ADALN, None, 1, 1)])
def test_bench_step_span_rows_are_the_archs(name, guidance, cfg, rows):
    task = SimpleNamespace(kind="denoise", meta={"tokens": 256})
    layout = SimpleNamespace(cfg=cfg, degree=cfg)
    graph = SimpleNamespace(request=SimpleNamespace(guidance=guidance))
    arch = spec.arch(name, FIXTURE)
    span, meta = serve._exec_span(arch, task, layout, 0, None, graph)
    assert span == "bench.exec.denoise"
    assert meta == {"tokens": 256, "rows": rows, "degree": cfg}


def _run(cell, name):
    """A window of two guided steps of 256 tokens, traced: flash and
    adaLN ops inside each step."""
    m, lt = cell["config"]["model"], cell["config"]["text_encoder"][
        "prompt_len"]
    tr = {"host": [["bench.traced_window", 0, 1000, {}],
                   ["bench.exec.denoise", 0, 400, {"tokens": 256,
                                                   "rows": 1}],
                   ["bench.exec.denoise", 500, 400, {"tokens": 256,
                                                     "rows": 1}]],
          "device": [["flash_attention.1", "jit__post", 50, 200],
                     ["adaln_modulate.1", "jit__post", 260, 40],
                     ["flash_attention.1", "jit__post", 550, 300]]}
    step = {"kind": "denoise", "failed": False, "tokens": 256, "rows": 1,
            "start": 0.0, "finish": 1.0, "duration": 1.0}
    return {"trace": {"events": tr, "span": (0, 1000)}, "model": m,
            "text_len": lt, "peak": PEAK, "arch": name,
            "root": str(FIXTURE),
            "window": {"w0": 0.0, "w1": 2.0, "seconds": 2.0},
            "steps": [step, dict(step, start=1.0, finish=2.0)]}


def test_bench_readers_use_the_archs_counts(cell):
    arch, run = cell["arch"], _run(cell, JOINT)
    m, lt = run["model"], run["text_len"]
    mfu = spec.reader("step_mfu")(run)
    assert mfu == pytest.approx(100 * 2 * arch.step_flops(m, 256, 1, lt)
                                / (2.0 * PEAK["flops_per_s"]))
    flash = spec.reader("flash_roofline")(run)
    least = 2 * flops.least_time(*arch.step_flash(m, 256, 1, lt), PEAK)[0]
    assert flash == pytest.approx(100 * least / 500e-9)
    # the block calls no adaLN kernel: nothing to read, whatever the
    # trace holds
    assert spec.reader("adaln_roofline")(run) is None
    other = _run(cell, ADALN)
    assert spec.reader("step_mfu")(other) != pytest.approx(mfu)
    assert spec.reader("flash_roofline")(other) != pytest.approx(flash)
    assert spec.reader("adaln_roofline")(other) > 0


def test_bench_generator_sizes_batch_with_the_archs_counts(cell):
    arch, conf = cell["arch"], cell["config"]
    m, lt = conf["model"], conf["text_encoder"]["prompt_len"]
    c = cell["mix"]["classes"]["M"]
    steps = cell["mix"]["steps"]
    t_step = arch.step_flops(m, traffic.tokens(m, c), 1, lt) \
        / PEAK["flops_per_s"]
    mix = dict(cell["mix"], margin=4.5 * steps * t_step / 10.0)
    got = traffic.generate(mix, m, arch, PEAK, 10.0, SEED, lt)
    assert len(got) == 5
    assert all(p.guidance == cell["mix"]["guidance"] for p in got)
    # the same mix under the two-row block: each step counts two rows
    adaln = traffic.generate(mix, m, spec.arch(ADALN), PEAK, 10.0, SEED, lt)
    assert len(adaln) < len(got)
