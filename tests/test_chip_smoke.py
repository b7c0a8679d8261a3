"""``chip_smoke.py``'s four-chip phase on a fake four-device CPU mesh, at a
small table: the ``shards=1`` table laid out over the mesh by
``shard_table`` (per-partition packing, 64-bucket partitions that each pad
a 128-bucket group) and served by ``make_distributed_stream`` must stay
bit-exact with the replicated oracle and pass the host check, on both
backends.  Runs in a subprocess so the fake devices stay out of this one."""
import os
import subprocess
import sys

import pytest

SCRIPT = """
import chip_smoke as cs
cs.TABLE = dict(cs.TABLE, backend="BACKEND")
cs.run_mesh(1 << 8, 16, 2 ** 31 + 7)
print("SMOKE_MESH_OK")
"""


@pytest.mark.parametrize("backend", ["jnp", "pallas"])
def test_smoke_mesh_phase_on_cpu(backend):
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.pathsep.join([root, os.path.join(root, "src")])
    r = subprocess.run([sys.executable, "-c",
                        SCRIPT.replace("BACKEND", backend)], env=env,
                       capture_output=True, text=True, timeout=600, cwd=root)
    assert "SMOKE_MESH_OK" in r.stdout, r.stdout + r.stderr
    assert r.stdout.count("bit-exact with the shards=1 replicated") == 2
