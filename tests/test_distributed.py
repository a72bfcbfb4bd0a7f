"""Multi-device correctness: forward/grad/MoE/SP-decode parity between
the sharded execution (8 fake CPU devices) and single-device reference.

Runs tests/_dist_worker.py in a subprocess because the fake-device count
must be fixed before jax initializes (the main pytest process keeps its
single real device)."""
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WORKER = os.path.join(ROOT, "tests", "_dist_worker.py")

CASES = ["forward_parity", "grad_parity_sp", "moe_a2a_parity",
         "moe_small_batch_psum", "sp_decode_parity", "compressed_psum"]


def _run(*cases):
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["JAX_PLATFORMS"] = "cpu"      # the child never contends for a chip
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    r = subprocess.run([sys.executable, WORKER, *cases],
                       capture_output=True, text=True, env=env, cwd=ROOT,
                       timeout=540)
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr}"
    for c in cases:
        assert f"OK {c}" in r.stdout, r.stdout


@pytest.mark.parametrize("case", CASES)
def test_distributed(case):
    _run(case)
