"""The chip smoke script's contract off the chip, and its float32 bounds."""
import importlib.util
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.engine import compute

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(REPO, "chip_smoke.py")


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def test_exits_nonzero_without_a_tpu():
    """Off the chip the script refuses to run and prints no result line."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, SCRIPT], cwd=REPO, env=env,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert '"platform"' not in proc.stdout
    assert "no TPU" in proc.stderr


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_round_out_f32_contains_bounds_and_is_exact(smoke, seed):
    """Outward rounding keeps every row a query matched (the float32 box
    contains the float64 one) and leaves bounds the kernel reads
    exactly."""
    rng = np.random.default_rng(seed)
    lo = rng.uniform(-1e6, 1e6, (64, 12))
    lo[1] = np.float32(0.1)          # already float32: unchanged
    hi = lo + rng.uniform(0, 1e3, lo.shape)
    lo[0, :3] = -np.inf
    hi[0, 3:6] = np.inf
    lo32, hi32 = smoke.round_out_f32(lo, hi)
    assert np.all(lo32 <= lo) and np.all(hi32 >= hi)
    assert compute.float32_exact(lo32, hi32)
    assert np.array_equal(lo32[1], lo[1])
    # the tightest such box: one float32 step inward would cut the range
    inner_lo = np.nextafter(lo32.astype(np.float32), np.float32(np.inf))
    finite = np.isfinite(lo32) & (lo32 != lo)
    assert np.all(inner_lo[finite] > lo[finite])
