"""The command refuses a machine without a TPU: non-zero exit, no result."""
import os
import subprocess
import sys

import run


def test_command_refuses_a_cpu_device():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, str(run.BENCH / "run.py"), "--workload",
         "internlm2-1.8b.conversation", "--seed", str(2**31 + 3),
         "--seconds", "1", "--trace", "0"],
        cwd=run.ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode != 0
    assert "{" not in out.stdout
    assert "TPU" in out.stderr
