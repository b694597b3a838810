"""A whole run of the tensor-parallel cell's small twin on four virtual CPU
devices: the KV-head-sharded step, driven by the harness, comes out
correct against the reference (a child process, since the device count
is fixed when JAX starts)."""
from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent

CHILD = """
import json, sys, time
from pathlib import Path
import jax
import harness, smallcell, spec
root = Path(sys.argv[1])
name = smallcell.make(root, "qwen1.5-4b.tp4-decode", chips=4)
c = spec.load(name, root)
out = harness.run_cell(c, 2 ** 31 + 99, 4.0, False, jax.devices()[:4],
                       time.perf_counter())
print(json.dumps(out))
"""


def test_sharded_step_is_correct(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join(
                   [str(HERE), str(HERE.parent / "src"),
                    os.environ.get("PYTHONPATH", "")]))
    p = subprocess.run([sys.executable, "-c", CHILD, str(tmp_path)], env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["device"]["count"] == 4
    assert out["correct"], out["checks"]
