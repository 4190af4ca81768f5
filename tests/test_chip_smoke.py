"""chip_smoke.py's steps on the CPU mesh, and its refusal to run there.

The smoke's step functions are platform-free; running them here at
2^12 rows keeps the command that goes to the chip from failing there
on anything a CPU run could have caught.
"""

import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402

from dryad_tpu import DryadContext  # noqa: E402


@pytest.mark.parametrize("name,step", chip_smoke.STEPS,
                         ids=[n for n, _ in chip_smoke.STEPS])
def test_step_on_cpu_mesh(mesh8, name, step, capsys):
    ctx = DryadContext(num_partitions_=8)
    step(ctx, 1 << 12, chip_smoke.SEED)
    line = capsys.readouterr().out.strip().splitlines()[-1]
    assert f"rows={1 << 12}" in line and "ok=True" in line, line


def test_smoke_refuses_cpu():
    """No fallback: on a machine without a TPU the script exits
    non-zero, names the platform, and prints no result line."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chip_smoke.py")],
        capture_output=True, text=True, timeout=120, env=env, cwd=ROOT,
    )
    assert out.returncode != 0
    assert "platform='cpu'" in out.stderr, out.stderr
    assert out.stdout.strip() == "", out.stdout
