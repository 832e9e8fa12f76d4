"""Pipeline parallelism: GPipe schedule == sequential execution.

The real multi-stage run needs >1 device, which conflicts with the
1-device test process — so the 2-stage check runs the demo script in a
subprocess (same pattern as the dry-run); the 1-stage degenerate case
runs in-process.
"""
import pathlib
import subprocess
import sys

import jax
import jax.numpy as jnp
from jax.sharding import AxisType
import numpy as np
import pytest

from repro.distributed.pipeline import bubble_fraction, gpipe_forward

ROOT = pathlib.Path(__file__).resolve().parents[1]


def test_single_stage_degenerates_to_sequential():
    mesh = jax.make_mesh((1,), ("stage",),
                         axis_types=(AxisType.Auto,))
    d = 8
    ws = jax.random.normal(jax.random.PRNGKey(0), (1, d, d)) / jnp.sqrt(d)

    def stage_fn(w, x):
        return jnp.tanh(x @ w)

    xs = jax.random.normal(jax.random.PRNGKey(1), (4, 2, d))
    ys = gpipe_forward(stage_fn, ws, xs, mesh=mesh)
    ref = jax.vmap(lambda x: stage_fn(ws[0], x))(xs)
    np.testing.assert_allclose(ys, ref, rtol=1e-6, atol=1e-6)


@pytest.mark.slow      # spawns a 4-host-device subprocess; minutes on CPU
def test_two_stage_pipeline_subprocess():
    out = subprocess.run(
        [sys.executable, str(ROOT / "examples" / "pipeline_demo.py")],
        capture_output=True, text=True, timeout=300,
        env={"PYTHONPATH": str(ROOT / "src"), "PATH": "/usr/bin:/bin",
             "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
             "HOME": "/tmp"},
    )
    assert out.returncode == 0, out.stderr[-2000:]
    assert "== sequential: OK" in out.stdout
    assert "pipelined transformer (4 layers / 2 stages) " \
           "== standard forward: OK" in out.stdout


def test_bubble_fraction():
    assert bubble_fraction(1, 8) == 0.0
    assert bubble_fraction(4, 4) == 3 / 7
    assert bubble_fraction(2, 30) < 0.04
