"""Pipeline-parallelism demo: GPipe schedule over a 'stage' mesh axis.

    PYTHONPATH=src python examples/pipeline_demo.py

Must run as its own process (needs >1 host device).  Splits a 4-layer
MLP across 2 pipeline stages, streams 8 microbatches through, and checks
the result against the sequential reference.
"""
import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")

import pathlib  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1] / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import AxisType  # noqa: E402
import numpy as np  # noqa: E402

from repro.distributed.pipeline import bubble_fraction, gpipe_forward  # noqa: E402


def main() -> None:
    n_stages, layers_per_stage, d = 2, 2, 16
    n_micro, mb = 8, 4

    key = jax.random.PRNGKey(0)
    ws = jax.random.normal(key, (n_stages, layers_per_stage, d, d)) \
        / jnp.sqrt(d)

    def stage_fn(w_stage, x):
        for i in range(layers_per_stage):
            x = jnp.tanh(x @ w_stage[i])
        return x

    xs = jax.random.normal(jax.random.fold_in(key, 1), (n_micro, mb, d))

    mesh = jax.make_mesh((n_stages,), ("stage",),
                         axis_types=(AxisType.Auto,))
    ys = gpipe_forward(stage_fn, ws, xs, mesh=mesh)

    # sequential reference
    ref = xs
    for s in range(n_stages):
        ref = jax.vmap(lambda x: stage_fn(ws[s], x))(ref)

    np.testing.assert_allclose(np.asarray(ys), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)
    print(f"pipeline({n_stages} stages, {n_micro} microbatches) == "
          f"sequential: OK")
    print(f"bubble fraction: {bubble_fraction(n_stages, n_micro):.2%} "
          f"(GPipe (S-1)/(M+S-1))")

    # --- pipelined TRANSFORMER (first-class model feature) -------------
    from repro.models.transformer import ModelConfig, forward, init_params
    from repro.models.pipelined import pipelined_forward

    cfg = ModelConfig(name="pp-lm", n_layers=4, d_model=32, n_heads=4,
                      kv_heads=2, d_ff=64, vocab=64, dtype=jnp.float32)
    params = init_params(jax.random.PRNGKey(7), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(8), (8, 16), 0, 64)
    want, _, _ = forward(params, cfg, tokens=toks, mode="train")
    got = pipelined_forward(params, cfg, toks, mesh=mesh,
                            n_stages=n_stages, microbatches=4)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)
    print(f"pipelined transformer ({cfg.n_layers} layers / {n_stages} "
          f"stages) == standard forward: OK")


if __name__ == "__main__":
    main()
