"""TPU-native GFC realizations: membership-as-data grouped collectives and
the compile-once-per-group-shape executable cache (subprocess: multi-device
host mesh so the main test process keeps 1 device)."""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[1]

_CHILD = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import json
import jax, jax.numpy as jnp
import numpy as np
from repro.core.executable_cache import ExecutableCache
from repro.core.gfc import GroupFreeComm
from repro.core.grouped import build_grouped_ops

mesh = jax.make_mesh((4,), ("g",))
ops = build_grouped_ops(mesh)
out = {}

# grouped all-reduce with membership as DATA: groups {0,1} and {2,3}
x = jnp.arange(4, dtype=jnp.float32).reshape(4, 1) + 1.0   # [1,2,3,4]
gids = jnp.array([[0], [0], [1], [1]], jnp.int32)
red = ops["all_reduce"](x, gids)
out["red"] = np.asarray(red).ravel().tolist()              # [3,3,7,7]

# changing membership = new INPUT, zero recompile
gids2 = jnp.array([[0], [1], [1], [0]], jnp.int32)
red2 = ops["all_reduce"](x, gids2)
out["red2"] = np.asarray(red2).ravel().tolist()            # [5,5,5,5]? no:
# groups {0,3} sum=5, {1,2} sum=5 -> [5,5,5,5]

# executable cache: same-size different-members reuses the compiled module
cache = ExecutableCache()
comm = GroupFreeComm(4)
d1 = comm.register_group((0, 1))
d2 = comm.register_group((2, 3))
r1 = cache.bind("all_reduce", d1, (4,), jnp.float32)
r2 = cache.bind("all_reduce", d2, (4,), jnp.float32)
out["compiles"] = cache.stats["compiles"]
out["hits"] = cache.stats["hits"]
y = jnp.ones((8,), jnp.float32)
out["ar"] = float(np.asarray(r1(y))[0])                    # psum over 2 = 2
print(json.dumps(out))
"""


@pytest.mark.slow
def test_grouped_and_cache():
    env = dict(os.environ)
    env["PYTHONPATH"] = str(REPO / "src")
    env["JAX_PLATFORMS"] = "cpu"       # forced host devices, never a chip
    proc = subprocess.run([sys.executable, "-c", _CHILD], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["red"] == [3.0, 3.0, 7.0, 7.0]
    assert out["red2"] == [5.0, 5.0, 5.0, 5.0]
    assert out["compiles"] == 1 and out["hits"] >= 1
    assert out["ar"] == 2.0
