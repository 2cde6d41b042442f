"""Table 1 analogue: dynamic-group setup costs on TPU/JAX.

Paper (NCCL, 8-GPU): new_group ~0.5 ms; FIRST collective 217-778 ms cold
init + ~0.5 GB/GPU; warm collective fast; GFC registration ~60 us.

JAX mapping measured here on 8 forced host CPU devices (subprocess pinned
to JAX_PLATFORMS=cpu).  Every row is a host-device number, labelled
``host_cpu`` in its derived column, and never a chip measurement:
  cold_compile   = build Mesh + jit + compile a subgroup collective for a
                   NEW group (the XLA analogue of NCCL cold init)
  cache_hit      = same-size different-members group through the
                   compile-once-per-group-shape executable cache
  gfc_register   = GFC logical-descriptor registration (metadata only)
  warm_collective= executing an already-bound collective
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path

RESULTS = Path(__file__).parent / "results"

_CHILD = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json, time
import jax, jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.executable_cache import ExecutableCache
from repro.core.gfc import GroupFreeComm

devs = jax.devices()
out = {}

def time_cold(ranks):
    t0 = time.perf_counter()
    mesh = Mesh(np.array([devs[r] for r in ranks]), ("g",))
    fn = jax.jit(jax.shard_map(
        lambda x: jax.lax.all_gather(x, "g", tiled=True),
        mesh=mesh, in_specs=P("g"), out_specs=P(), check_vma=False))
    x = jnp.arange(len(ranks) * 1024, dtype=jnp.float32)
    fn.lower(x).compile()
    return time.perf_counter() - t0

# cold path: new group of each size -> mesh + jit + compile
for size in (2, 4, 8):
    ranks = tuple(range(size))
    out[f"cold_compile_size{size}_ms"] = time_cold(ranks) * 1e3

# executable cache: first group pays compile; same-size different members
# is a metadata bind
cache = ExecutableCache()
comm = GroupFreeComm(8)
for size in (2, 4, 8):
    d1 = comm.register_group(tuple(range(size)))
    cache.bind("all_gather", d1, (1024,), jnp.float32)     # compiles
    t0 = time.perf_counter()
    reps = 50
    for i in range(reps):
        ranks = tuple((i + j) % 8 for j in range(size))
        d2 = comm.register_group(tuple(sorted(set(ranks)))[:size]
                                 if len(set(ranks)) >= size else d1.ranks)
        cache.bind("all_gather", d2, (1024,), jnp.float32) # cache hit
    out[f"cache_hit_size{size}_us"] = (time.perf_counter() - t0) / reps * 1e6

# GFC descriptor registration (the paper's ~60us number), with each
# call ALSO sampled through the telemetry plane (DESIGN.md §15) so the
# table can report the setup-latency distribution, not just the mean
from repro.core.telemetry import Telemetry
tel = Telemetry()
comm.telemetry = tel
t0 = time.perf_counter()
reps = 2000
for i in range(reps):
    comm.register_group((i % 8, (i + 3) % 8))
out["gfc_register_us"] = (time.perf_counter() - t0) / reps * 1e6
comm.telemetry = None
pct = tel.gfc_percentiles()
out["gfc_register_p50_us"] = pct["p50_us"]
out["gfc_register_p90_us"] = pct["p90_us"]
out["gfc_register_p99_us"] = pct["p99_us"]
out["gfc_register_hist"] = tel.gfc_histogram()

# warm collective through a bound executable
d = comm.register_group((0, 1, 2, 3))
run = cache.bind("all_gather", d, (1024,), jnp.float32)
x = jnp.arange(4 * 1024, dtype=jnp.float32)
run(x)                                                     # warmup
t0 = time.perf_counter()
for _ in range(20):
    jax.block_until_ready(run(x))
out["warm_collective_us"] = (time.perf_counter() - t0) / 20 * 1e6
out["compiles"] = cache.stats["compiles"]
print(json.dumps(out))
"""


def run() -> dict:
    env = dict(os.environ)
    env["PYTHONPATH"] = str(Path(__file__).resolve().parents[1] / "src")
    # forced host CPU devices: the child must never contend for a chip
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run([sys.executable, "-c", _CHILD], env=env,
                          capture_output=True, text=True, timeout=600)
    if proc.returncode != 0:
        raise RuntimeError(proc.stderr[-2000:])
    data = json.loads(proc.stdout.strip().splitlines()[-1])
    RESULTS.mkdir(exist_ok=True)
    (RESULTS / "group_setup.json").write_text(json.dumps(data, indent=1))
    return data


def rows(data: dict) -> list[tuple[str, float, str]]:
    out = []
    for size in (2, 4, 8):
        out.append((f"group_setup.cold_compile_size{size}",
                    data[f"cold_compile_size{size}_ms"] * 1e3,
                    "paper_first_coll_217-778ms"))
        out.append((f"group_setup.cache_hit_size{size}",
                    data[f"cache_hit_size{size}_us"],
                    "descriptor_bind_same_size"))
    out.append(("group_setup.gfc_register", data["gfc_register_us"],
                "paper_60us"))
    hist = data.get("gfc_register_hist", {})
    nonzero = ";".join(f"{k}={v}" for k, v in hist.items() if v)
    out.append(("group_setup.gfc_register_p50",
                data.get("gfc_register_p50_us", float("nan")),
                "telemetry_histogram"))
    out.append(("group_setup.gfc_register_p99",
                data.get("gfc_register_p99_us", float("nan")),
                nonzero or "telemetry_histogram"))
    out.append(("group_setup.warm_collective", data["warm_collective_us"],
                "steady_state"))
    return [(name, v, f"host_cpu;{derived}") for name, v, derived in out]


if __name__ == "__main__":
    d = run()
    for name, us, derived in rows(d):
        print(f"{name},{us:.1f},{derived}")
