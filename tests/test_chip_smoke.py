"""chip_smoke.py without a chip: it must fail in phase 1 and print no
result, whatever it is asked to run (the contract the driver checks in
a sandbox like this one). The five phases themselves need the chip."""

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.parametrize("args", [[], ["--chips", "4"], ["--rows", "400000"]],
                         ids=["default", "chips4", "rows"])
def test_refuses_to_run_on_cpu(args):
    from lightgbm_tpu.hostenv import cpu_child_env
    out = subprocess.run([sys.executable, os.path.join(REPO, "chip_smoke.py")]
                         + args, env=cpu_child_env(), cwd=REPO,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode != 0
    assert "needs a TPU" in out.stderr
    assert '"ok"' not in out.stdout
    assert out.stdout.strip().splitlines()[-1] == "== phase 1: device"
