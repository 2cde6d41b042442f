#!/usr/bin/env python3
"""Smoke test of the served path on a TPU, at the full width of dit-image.

Serves a handful of requests through the normal entry point —
``ServingEngine`` -> ``ControlPlane`` -> ``ThreadBackend`` ->
``DiTPipeline`` — with ``DIT_IMAGE`` at its published width
(configs/dit_models.py) and the Pallas kernels compiled, then checks what
comes out.  Weights are random from a fixed seed, with the zero-init
adaLN gates and output head livened so that no comparison is vacuous.

One chip (the default), two ranks sharing the chip and one weight copy:

* a 512 px and a 1024 px unguided request at SP degree 1; a guided
  1024 px request at degree 2 (batched classifier-free guidance, B=2
  through every kernel, K/V gathered over GFC at every step); a 1024 px
  request at degree 2 with ``cache_interval=2`` (refresh steps gather,
  hit steps run the splice kernel on stale remote K/V);
* no failed or timed-out request, no collective timeout, no worker
  error, finite pixels;
* the two degree-2 requests' final latents against the same requests
  served at degree 1 by a one-rank engine on the same weights: the
  guided one within ``SP_BOUND``, the cached one within
  ``CACHE_BUDGET``;
* one denoise step of the served path (``forward_sp_tokens``, kernels
  on) against the plain-jnp float32 reference ``dit.forward`` at
  ``highest`` matmul precision, and the flash, splice and adaLN (B=2)
  kernels against their jnp oracles, at served shapes on the same chip
  (rel-L2 <= ``REFERENCE_BOUND``).

Four chips (``--chips 4``): only SP across chips and its comparison —
four ranks, one per chip, serve a cached 1024 px request (refresh and
hit steps) and a guided 512 px request at SP degree 4; a one-rank engine
on the same weights serves them at degree 1; the pixels must agree
within ``CACHE_BUDGET`` and ``SP_BOUND`` respectively, and every chip's
``peak_bytes_in_use`` must reach the weight bytes, which shows each rank
computed on its own chip.

Run from the root of a checkout, on a machine with a TPU:

    python chip_smoke.py
    python chip_smoke.py --chips 4

Earlier lines report phase times (compile and warm-up apart from
serving), the device, peak device memory and every rel-L2.  The last
line is ``{"ok": true, "device": {...}}``.  Any failed check, a backend
other than TPU, ``REPRO_USE_PALLAS`` in the environment, or a missing
``src/repro`` beside this file exits non-zero without that line.
"""
from __future__ import annotations

import argparse
import concurrent.futures
import contextlib
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax import monitoring  # noqa: E402

from repro.compile_cache import use_compile_cache  # noqa: E402
from repro.configs.dit_models import DIT_IMAGE  # noqa: E402
from repro.core.executor import rank_device  # noqa: E402
from repro.core.scheduler import Dispatch, Policy  # noqa: E402
from repro.core.trajectory import ExecutionLayout, Request  # noqa: E402
from repro.kernels import ops, ref  # noqa: E402
from repro.models import dit, text_encoder, vae  # noqa: E402
from repro.serving.cache_demo import liven, rel_l2  # noqa: E402
from repro.serving.engine import ServingEngine  # noqa: E402

STEPS = 4
CACHE_INTERVAL = 2
LIVEN_SEED = 123
# served (kernels, default matmul precision, which runs float32 matmuls
# in bf16 passes) against the float32 reference at `highest` precision
REFERENCE_BOUND = 2e-2
# degree d against degree 1 with every K/V gathered fresh: the same
# arithmetic per token, so only float reassociation separates them; a
# wrong shard offset or gather moves the result by O(1)
SP_BOUND = 1e-3
# degree d with `cache_interval=2` against degree 1: hit steps attend to
# stale remote K/V, an approximation and not a rounding error.  The
# reduced two-layer demo holds it to 5e-2 (tests/test_cache_backends.py);
# at 28 layers of full width on livened random weights one four-chip run
# measured 8.3e-2 at degree 4 (two stale hits in four steps), so the
# budget here is 0.15: it bounds the approximation, while SP_BOUND
# carries correctness.
CACHE_BUDGET = 0.15


class SmokeFailure(Exception):
    pass


def check(ok: bool, what: str):
    print(f"check {'ok' if ok else 'FAILED'}: {what}", flush=True)
    if not ok:
        raise SmokeFailure(what)


@contextlib.contextmanager
def phase(name: str, compiles: list):
    n0, t0 = len(compiles), time.perf_counter()
    yield
    dt = time.perf_counter() - t0
    print(f"phase {name}: {dt:.2f} s wall, {len(compiles) - n0} backend "
          f"compiles ({sum(compiles[n0:]):.2f} s)", flush=True)


# ---------------------------------------------------------------------------
# engine, policy, warm-up
# ---------------------------------------------------------------------------

class FixedDegrees(Policy):
    """One task at a time, oldest request first: denoise steps at the
    request's listed SP degree on ranks 0..d-1, encode and decode on
    rank 0."""
    name = "fixed-degrees"

    def __init__(self, degrees: dict):
        self.degrees = degrees

    def schedule(self, view):
        if view.running or not view.ready:
            return []
        task, req, _ = min(view.ready, key=lambda x: (
            x[1].arrival, x[1].id, x[0].step_index))
        d = self.degrees[req.id] if task.kind == "denoise" else 1
        return [Dispatch(task.id, ExecutionLayout(tuple(range(d))))]


def build_engine(cfg, degrees: dict, num_ranks: int, pipeline=None):
    """An engine serving each request at its listed degree; with
    ``pipeline`` it shares that pipeline's (already livened) weights."""
    eng = ServingEngine(cfg, FixedDegrees(degrees), num_ranks,
                        seed=0, cache_interval=CACHE_INTERVAL,
                        pipeline=pipeline)
    if pipeline is None:
        liven(eng.pipeline, seed=LIVEN_SEED)
    return eng


def weight_bytes(pipeline) -> int:
    trees = (pipeline.dit_params, pipeline.txt_params, pipeline.vae_params)
    return sum(x.nbytes for x in jax.tree.leaves(trees))


def _warm_rank(pipeline, cfg, rank: int, jobs):
    """Run every denoise shape ``rank`` will see, plus encode and decode,
    under that rank's device: a cold compile on one rank must not keep
    its peers waiting at a GFC all-gather past the collective timeout."""
    dit_p, txt_p, vae_p = pipeline.weights(rank)
    pd = cfg.dit.patch_size ** 2 * cfg.dit.in_channels
    kv_shape = (cfg.num_kv_heads, cfg.head_dim)
    with jax.default_device(rank_device(rank)):
        for batch, n_total, degree, mode, res in jobs:
            n_loc, off = n_total // degree, rank * (n_total // degree)

            def kv_gather(k, v, layer):
                if mode == "solo":
                    return k, v
                full = jnp.zeros((batch, n_total) + kv_shape, k.dtype)
                if mode == "gather":
                    return full, full
                return ops.SplicedKV(full, full, k, v, off)

            out = dit.forward_sp_tokens(
                dit_p, jnp.zeros((batch, n_loc, pd)),
                jnp.zeros((batch,)),
                jnp.zeros((batch, 77, cfg.dit.cond_dim)), cfg,
                pos_offset=off, n_total=n_total, kv_gather=kv_gather)
            jax.block_until_ready(out)
            if rank == 0:
                f, h, w, c = dit.latent_shape(cfg, res, res)
                jax.block_until_ready(vae.decode(
                    vae_p, jnp.zeros((1, f, h, w, c)), cfg))
        if rank == 0:
            jax.block_until_ready(text_encoder.encode(
                txt_p, jnp.zeros((1, 77), jnp.int32), pipeline.txt_cfg,
                dtype=jnp.float32))


def warm(eng, requests, degrees: dict):
    """Warm every shape the served run will use, one thread per rank."""
    cfg = eng.cfg
    per_rank: dict[int, list] = {}
    for req in requests:
        d = degrees[req.id]
        n_total = dit.token_count(cfg, req.height, req.width)
        assert n_total % d == 0, (n_total, d)
        batch = 2 if req.guidance is not None else 1
        modes = ["solo"] if d == 1 else (
            ["gather"] if req.guidance is not None else ["gather", "hit"])
        for r in range(d):
            for m in modes:
                per_rank.setdefault(r, []).append(
                    (batch, n_total, d, m, req.height))
    with concurrent.futures.ThreadPoolExecutor(len(per_rank)) as pool:
        futs = [pool.submit(_warm_rank, eng.pipeline, cfg, r, jobs)
                for r, jobs in per_rank.items()]
        for f in futs:
            f.result()


def serve_checked(eng, requests, what: str):
    """Serve and hold the run to: nothing failed, nothing timed out, no
    collective timeout, no worker error, finite pixels of the right
    shape.  Returns the metrics."""
    m = eng.serve(requests, timeout=600.0)
    check(m["failed"] == 0 and m["completed"] == len(requests),
          f"{what}: {m['completed']}/{len(requests)} completed, "
          f"{m['failed']} failed")
    check(not m["timed_out_requests"],
          f"{what}: timed-out requests {m['timed_out_requests']}")
    check(not eng.backend.timeouts,
          f"{what}: collective timeouts {eng.backend.timeouts[:2]}")
    check(not eng.backend.errors,
          f"{what}: worker errors {eng.backend.errors[:1]}")
    for req in requests:
        px = eng.result_pixels(req)
        ok = px is not None and px.shape == (1, req.height, req.width, 3) \
            and bool(np.isfinite(px).all())
        check(ok, f"{what}: {req.id} pixels "
                  f"{None if px is None else px.shape} all finite")
    return m


def final_latent(eng, rid: str):
    """The denoised latent tokens a request's decode read."""
    g = eng.cp.graphs[rid]
    dec = next(t for t in g.tasks.values() if t.kind == "decode")
    art = g.artifacts[dec.inputs[0]]
    assert art.layout.degree == 1, art.layout
    return art.data[art.layout.ranks[0]]["latent"]


def denoise_modes(eng, rid: str) -> list:
    """(degree, cfg, cache mode) of each denoise dispatch of ``rid``."""
    return [(len(e["ranks"]), e.get("cfg", 1), e.get("cache"))
            for e in eng.cp.events
            if e["ev"] == "dispatch" and e["kind"] == "denoise"
            and e["req"] == rid]


def peak_bytes(devices) -> list:
    return [(d.memory_stats() or {}).get("peak_bytes_in_use")
            for d in devices]


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

def reference_step(eng, res: int) -> float:
    """rel-L2 of one served denoise step (kernels on, default matmul
    precision) against the plain float32 jnp reference at ``highest``
    precision, on rank 0's device."""
    cfg = eng.cfg
    params = eng.pipeline.weights(0)[0]
    f, h, w, c = dit.latent_shape(cfg, res, res)
    n = dit.token_count(cfg, res, res)
    with jax.default_device(rank_device(0)):
        k1, k2 = jax.random.split(jax.random.PRNGKey(7))
        lat = jax.random.normal(k1, (1, f, h, w, c), jnp.float32)
        txt = jax.random.normal(k2, (1, 77, cfg.dit.cond_dim), jnp.float32)
        t = jnp.array([750.0], jnp.float32)
        served = dit.forward_sp_tokens(
            params, dit.patchify(lat, cfg.dit.patch_size), t, txt, cfg,
            pos_offset=0, n_total=n, kv_gather=lambda k, v, layer: (k, v))
        with jax.default_matmul_precision("highest"):
            want = dit.forward(params, lat, t, txt,
                               cfg.with_(use_pallas=False),
                               dtype=jnp.float32)
        want = dit.patchify(want, cfg.dit.patch_size)
        served, want = jax.device_get((served, want))
    check(bool(np.isfinite(served).all()) and served.shape == want.shape,
          f"served step {served.shape} finite")
    return rel_l2(served, want)


def kernel_checks(eng, res: int) -> dict:
    """rel-L2 of each compiled kernel against its jnp oracle at
    ``highest`` precision, at the served shapes of a degree-2 shard of a
    ``res`` px image, on rank 0's device."""
    cfg = eng.cfg
    n = dit.token_count(cfg, res, res)
    half, h, hd, d = n // 2, cfg.num_heads, cfg.head_dim, cfg.d_model
    with jax.default_device(rank_device(0)):
        keys = iter(jax.random.split(jax.random.PRNGKey(11), 10))

        def normal(*shape, scale=1.0):
            return scale * jax.random.normal(next(keys), shape)

        q, kf, vf = (normal(1, half, h, hd) for _ in range(3))
        k, v = normal(1, n, h, hd), normal(1, n, h, hd)
        x, resid = normal(2, n, d), normal(2, n, d)
        sh, sc, g = (normal(2, d, scale=0.5) for _ in range(3))
        got = {
            "flash q-shard": ops.attention(q, k, v, use_pallas=True),
            "splice": ops.splice_attention(q, k, v, kf, vf, offset=half,
                                           use_pallas=True),
            "adaLN B=2": ops.fused_adaln(x, sh, sc, g, resid,
                                         use_pallas=True),
        }
        with jax.default_matmul_precision("highest"):
            want = {
                "flash q-shard": ref.attention_ref(q, k, v),
                "splice": ref.splice_attention_ref(q, k, v, kf, vf,
                                                   offset=half),
                "adaLN B=2": ref.adaln_ref(x, sh, sc, g, resid),
            }
        got, want = jax.device_get((got, want))
    return {name: rel_l2(got[name], want[name]) for name in got}


def degree_1_twin(pipeline, cfg, requests, compiles):
    """Serve ``requests`` again at degree 1 on a one-rank engine sharing
    ``pipeline``'s weights; returns {id: (final latent, pixels)}."""
    with phase("degree-1 twin (1-rank engine, same weights)",
               compiles):
        eng = build_engine(cfg, {r.id: 1 for r in requests}, 1,
                           pipeline=pipeline)
        serve_checked(eng, requests, "1-rank serve")
        out = {r.id: (final_latent(eng, r.id), eng.result_pixels(r))
               for r in requests}
    eng.shutdown()
    return out


def one_chip(cfg, compiles, *, small=512, large=1024, steps=STEPS):
    reqs = [
        Request("unguided-s", "dit-image", small, small, steps=steps,
                arrival=0.000),
        Request("unguided-l", "dit-image", large, large, steps=steps,
                arrival=0.001),
        Request("guided-l", "dit-image", large, large, steps=steps,
                arrival=0.002, guidance=4.0),
        Request("cached-l", "dit-image", large, large, steps=steps,
                arrival=0.003),
    ]
    degrees = {"unguided-s": 1, "unguided-l": 1, "guided-l": 2,
               "cached-l": 2}
    with phase("build engine (2 ranks, weights, liven)", compiles):
        eng = build_engine(cfg, degrees, num_ranks=2)
    wb = weight_bytes(eng.pipeline)
    print(f"weights: {wb} bytes", flush=True)
    with phase("reference step (compile + run)", compiles):
        err_ref = reference_step(eng, large)
    print(f"rel-L2 served step vs float32 reference ({large} px): "
          f"{err_ref:.3e}", flush=True)
    with phase("kernel checks (compile + run)", compiles):
        err_kernels = kernel_checks(eng, large)
    for name, err in err_kernels.items():
        print(f"rel-L2 {name} kernel vs jnp oracle: {err:.3e}", flush=True)
    with phase("warm-up (every served shape)", compiles):
        warm(eng, reqs, degrees)
    with phase(f"serve ({len(reqs)} requests)", compiles):
        m = serve_checked(eng, reqs, "2-rank serve")
    print(f"serve metrics: {json.dumps(m, default=str)}", flush=True)
    modes = {r.id: denoise_modes(eng, r.id) for r in reqs}
    for rid, ms in modes.items():
        print(f"denoise dispatches {rid}: {ms}", flush=True)
    sp2 = [r for r in reqs if degrees[r.id] == 2]
    sp2_out = {r.id: (final_latent(eng, r.id), eng.result_pixels(r))
               for r in sp2}
    eng.shutdown()
    twin = degree_1_twin(eng.pipeline, cfg, sp2, compiles)
    errs = compare(sp2_out, twin, 2)
    peak = peak_bytes(jax.devices()[:1])[0]
    print(f"peak_bytes_in_use device 0: {peak}", flush=True)

    check(err_ref <= REFERENCE_BOUND, f"served step vs reference rel-L2 "
          f"{err_ref:.3e} <= {REFERENCE_BOUND}")
    for name, err in err_kernels.items():
        check(err <= REFERENCE_BOUND,
              f"{name} kernel rel-L2 {err:.3e} <= {REFERENCE_BOUND}")
    check(modes["guided-l"] == [(2, 1, None)] * steps,
          "guided request ran batched CFG (B=2) at degree 2, uncached")
    hit_modes = {mode for _, _, mode in modes["cached-l"]}
    check(all(d == 2 for d, _, _ in modes["cached-l"])
          and {"refresh", "hit"} <= hit_modes,
          f"cached request ran refresh and hit steps: {sorted(hit_modes)}")
    check(errs["guided-l"][0] <= SP_BOUND, f"guided-l latent rel-L2 "
          f"{errs['guided-l'][0]:.3e} <= {SP_BOUND} (exact SP)")
    check(errs["cached-l"][0] <= CACHE_BUDGET, f"cached-l latent rel-L2 "
          f"{errs['cached-l'][0]:.3e} <= {CACHE_BUDGET} (stale hits)")
    if jax.devices()[0].platform == "tpu":
        check(peak is not None and peak >= wb,
              f"device 0 peak {peak} >= weight bytes {wb}")


def compare(served: dict, twin: dict, degree: int) -> dict:
    """{id: (latent rel-L2, pixel rel-L2)} of degree-``degree`` outputs
    against the degree-1 twin's, printed."""
    errs = {}
    for rid, (lat, px) in served.items():
        errs[rid] = (rel_l2(lat, twin[rid][0]), rel_l2(px, twin[rid][1]))
        print(f"rel-L2 {rid}, degree {degree} vs degree 1: final latent "
              f"{errs[rid][0]:.3e}, pixels {errs[rid][1]:.3e}", flush=True)
    return errs


def four_chip(cfg, compiles, *, small=512, large=1024,
              steps=STEPS, degree=4):
    devices = jax.devices()
    check(len(devices) == degree,
          f"{len(devices)} devices for SP degree {degree}")
    reqs = [
        Request(f"sp{degree}-cached-l", "dit-image", large, large,
                steps=steps, arrival=0.000),
        Request(f"sp{degree}-guided-s", "dit-image", small, small,
                steps=steps, arrival=0.001, guidance=4.0),
    ]
    cached, guided = (r.id for r in reqs)
    with phase(f"build engine ({degree} ranks)", compiles):
        eng = build_engine(cfg, {r.id: degree for r in reqs}, degree)
    wb = weight_bytes(eng.pipeline)
    print(f"weights: {wb} bytes", flush=True)
    with phase("warm-up (every served shape, one thread per chip)",
               compiles):
        warm(eng, reqs, {r.id: degree for r in reqs})
    with phase(f"serve ({len(reqs)} requests at degree {degree})",
               compiles):
        m = serve_checked(eng, reqs, f"{degree}-rank serve")
    print(f"serve metrics: {json.dumps(m, default=str)}", flush=True)
    modes = {r.id: denoise_modes(eng, r.id) for r in reqs}
    for rid, ms in modes.items():
        print(f"denoise dispatches {rid}: {ms}", flush=True)
    served = {r.id: (final_latent(eng, r.id), eng.result_pixels(r))
              for r in reqs}
    eng.shutdown()
    errs = compare(served, degree_1_twin(eng.pipeline, cfg, reqs, compiles),
                   degree)
    peaks = peak_bytes(devices)
    for d, peak in zip(devices, peaks):
        print(f"peak_bytes_in_use device {d.id}: {peak}", flush=True)

    hit_modes = {mode for _, _, mode in modes[cached]}
    check(all(d == degree for d, _, _ in modes[cached])
          and {"refresh", "hit"} <= hit_modes,
          f"{cached} ran refresh and hit steps at degree {degree}")
    check(modes[guided] == [(degree, 1, None)] * steps,
          f"{guided} ran batched CFG at degree {degree}, uncached")
    check(errs[guided][1] <= SP_BOUND, f"{guided} pixel rel-L2 "
          f"{errs[guided][1]:.3e} <= {SP_BOUND} (exact SP)")
    check(errs[cached][1] <= CACHE_BUDGET, f"{cached} pixel rel-L2 "
          f"{errs[cached][1]:.3e} <= {CACHE_BUDGET} (stale hits)")
    for d, peak in zip(devices, peaks):
        if d.platform == "tpu":
            check(peak is not None and peak >= wb,
                  f"device {d.id} peak {peak} >= weight bytes {wb}")


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: the served mix on one chip; 4: SP degree 4 "
                         "across four chips against degree 1")
    args = ap.parse_args(argv)
    if "REPRO_USE_PALLAS" in os.environ:
        print("chip_smoke: REPRO_USE_PALLAS is set; it can swap the jnp "
              "reference onto the chip, unset it", file=sys.stderr)
        return 2
    print(f"compile cache: {use_compile_cache()}", flush=True)
    compiles: list = []
    monitoring.register_event_duration_secs_listener(
        lambda ev, dur, **kw: compiles.append(dur)
        if ev == "/jax/core/compile/backend_compile_duration" else None)

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {dev.platform!r})",
              file=sys.stderr)
        return 1
    cfg = DIT_IMAGE.with_(use_pallas=True)
    print(f"device: {dev.platform} {dev.device_kind} x{len(jax.devices())}"
          f", jax {jax.__version__}", flush=True)
    print(f"model: {cfg.name}, {cfg.num_layers} layers, d_model "
          f"{cfg.d_model}, {cfg.num_heads} heads x {cfg.head_dim}, d_ff "
          f"{cfg.d_ff}, float32", flush=True)
    print(f"kernel path on: {ops.use_pallas_enabled(cfg.use_pallas)}, "
          f"interpret mode: {ops.interpret_mode()}", flush=True)
    try:
        check(ops.use_pallas_enabled(cfg.use_pallas)
              and not ops.interpret_mode(),
              "Pallas kernels compiled (not interpreted) on the served path")
        t0 = time.perf_counter()
        if args.chips == 4:
            four_chip(cfg, compiles)
        else:
            one_chip(cfg, compiles)
    except SmokeFailure as e:
        print(f"chip_smoke FAILED: {e}", file=sys.stderr)
        return 1
    print(f"total {time.perf_counter() - t0:.2f} s, "
          f"{len(compiles)} backend compiles", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
