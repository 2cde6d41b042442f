"""The served step's compiled layer programs (models/dit.py): each
segment is built once per shape and then serves every layer and step;
degree 1, degree 2 over GFC threads and a §11 cache hit through the
splice segment agree with the plain jnp path; and the step's forward
region reports how many programs it built."""
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.dit_models import DIT_IMAGE
from repro.core.gfc import GroupFreeComm
from repro.core.telemetry import Telemetry
from repro.core.trajectory import Request
from repro.diffusion.pipeline import DiTPipeline
from repro.kernels import ops
from repro.models import dit
from repro.serving.cache_demo import liven
from repro.serving.engine import ServingEngine
from test_serving_engine import FixedSP

# a name of its own, so no other test in the process has built these
# programs already
CFG = DIT_IMAGE.reduced().with_(name="dit-image-layer-programs",
                                use_pallas=True)
JNP = CFG.with_(use_pallas=False)
SEGMENTS = 4            # head, pre, post, tail
N = 64                  # 128 px: 64 tokens
# the kernel tests' tolerance, kernel against oracle (test_kernels.py)
TOL = dict(atol=1e-5, rtol=1e-5)


@pytest.fixture(scope="module")
def params():
    pipe = DiTPipeline(CFG, seed=0)
    liven(pipe)               # non-zero gates and head: a real output
    return pipe.dit_params


def _inputs(seed, n=N, batch=1):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    patch = CFG.dit.patch_size ** 2 * CFG.dit.in_channels
    tok = jax.random.normal(ks[0], (batch, n, patch))
    t = jax.random.uniform(ks[1], (batch,), minval=0.0, maxval=1000.0)
    txt = jax.random.normal(ks[2], (batch, 77, CFG.dit.cond_dim))
    return tok, t, txt


def _local(k, v, layer):
    return k, v


def _forward(params, cfg, tok, t, txt, *, off=0, n_total=N,
             kv_gather=_local):
    return np.asarray(dit.forward_sp_tokens(
        params, tok, t, txt, cfg, pos_offset=off, n_total=n_total,
        kv_gather=kv_gather))


def test_one_shape_builds_each_segment_once(params):
    before = dit.builds()
    _forward(params, CFG, *_inputs(1))
    assert dit.builds() - before == SEGMENTS
    # more steps at the shape, every layer through the same programs
    for seed in (2, 3):
        _forward(params, CFG, *_inputs(seed))
    assert dit.builds() - before == SEGMENTS
    # a new token count builds each segment once more
    _forward(params, CFG, *_inputs(4, n=2 * N), n_total=2 * N)
    _forward(params, CFG, *_inputs(5, n=2 * N), n_total=2 * N)
    assert dit.builds() - before == 2 * SEGMENTS


def test_degree_1_matches_the_jnp_path(params):
    tok, t, txt = _inputs(6, batch=2)      # batched CFG rows
    got = _forward(params, CFG, tok, t, txt)
    want = _forward(params, JNP, tok, t, txt)
    assert np.abs(want).max() > 0.01
    np.testing.assert_allclose(got, want, **TOL)


def test_degree_2_over_gfc_threads_matches_the_jnp_path(params):
    tok, t, txt = _inputs(7)
    want = _forward(params, JNP, tok, t, txt)
    comm = GroupFreeComm(2)
    desc = comm.register_group((0, 1))
    half = N // 2
    out, errors = {}, []

    def rank(r):
        def gather(k, v, layer):
            return (jnp.asarray(comm.all_gather(desc, r, np.asarray(k),
                                                axis=1)),
                    jnp.asarray(comm.all_gather(desc, r, np.asarray(v),
                                                axis=1)))
        try:
            out[r] = _forward(params, CFG, tok[:, r * half:(r + 1) * half],
                              t, txt, off=r * half, kv_gather=gather)
        except Exception as e:   # noqa: BLE001 - reported below
            errors.append(e)

    threads = [threading.Thread(target=rank, args=(r,)) for r in (0, 1)]
    for th in threads:
        th.start()
    for th in threads:
        th.join(timeout=120)
    assert not errors and not any(th.is_alive() for th in threads), errors
    np.testing.assert_allclose(np.concatenate([out[0], out[1]], axis=1),
                               want, **TOL)


def test_cache_hit_through_the_splice_segment_matches_the_jnp_path(params):
    """Rank 1 of 2 on a §11 hit: the stale K/V of an earlier step, with
    this step's fresh shard spliced in at the rank's offset."""
    stale = {}

    def record(k, v, layer):
        stale[layer] = (np.asarray(k), np.asarray(v))
        return k, v
    _forward(params, JNP, *_inputs(8), kv_gather=record)   # the refresh

    off = N // 2
    tok, t, txt = _inputs(9)
    shard = tok[:, off:]

    def spliced(k, v, layer):
        return ops.SplicedKV(jnp.asarray(stale[layer][0]),
                             jnp.asarray(stale[layer][1]), k, v, off)

    def materialized(k, v, layer):
        K, V = (a.copy() for a in stale[layer])
        K[:, off:] = np.asarray(k)
        V[:, off:] = np.asarray(v)
        return jnp.asarray(K), jnp.asarray(V)

    before = dit.builds()
    got = _forward(params, CFG, shard, t, txt, off=off, kv_gather=spliced)
    assert dit.builds() > before           # the splice segment was built
    want = _forward(params, JNP, shard, t, txt, off=off,
                    kv_gather=materialized)
    np.testing.assert_allclose(got, want, **TOL)
    fresh = _forward(params, JNP, tok, t, txt)[:, off:]
    assert not np.allclose(got, fresh, **TOL)   # the stale K/V was used


def test_forward_region_reports_builds():
    tel = Telemetry()
    eng = ServingEngine(CFG.with_(name="dit-image-builds-stat"), FixedSP(1),
                        1, telemetry=tel)
    req = Request(id="b0", model="dit-image", height=64, width=64, frames=1,
                  steps=3, arrival=0.0)
    try:
        assert eng.serve([req], timeout=120.0)["completed"] == 1
    finally:
        eng.shutdown()
    builds = [stats["builds"] for name, _, _, stats in tel.overlay
              if name == "gfdit.step.forward"]
    # the first step builds every segment at its shape, the rest none
    assert builds == [SEGMENTS, 0, 0]
