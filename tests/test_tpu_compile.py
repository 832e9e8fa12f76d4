"""Compile-only checks of the bounded DCL forward kernels for TPU v5e.

Interpret mode accepts constructs that the TPU compiler (Mosaic) refuses
— a row gather on a VMEM value, a ragged int8 band copy, a 4-D block
whose minor dimension is not lane-aligned — so every kernel of the
serving path is also compiled here for a *described* v5e chip, at the
published ResNet-50 DCL shapes (512x512 input, batch 4, bound B=2).
Nothing runs: the compiler raises what it would raise on the chip, and
the compiled program must contain the Pallas kernel
(``tpu_custom_call``), not a stand-in.

The topology is described inside a module fixture, never at import:
only one process may load the TPU compiler library at a time, and the
test runner's workers all import this file.
"""
import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import plan

# (H, W, C, stride) of the DCL inputs at 512x512: c3 (3 layers), the
# strided first c4 layer, c4 (5 layers), the strided first c5 layer,
# c5 (2 layers).  M == C for every one of them.
SHAPES = [(64, 64, 128, 1), (64, 64, 256, 2), (32, 32, 256, 1),
          (32, 32, 512, 2), (16, 16, 512, 1)]
BATCH = 4
BOUND = 2.0
DATAPATHS = ("fp32", "int8", "chain")


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    # A described chip's compile is written to the persistent cache but
    # cannot be read back without the chip: keep the cache out of it.
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        jax.config.update("jax_enable_compilation_cache", prev)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _program(datapath, h, w, c, s, dev):
    """The jitted per-layer program of one datapath — padding,
    quantization, weight blocking and the kernel, exactly as
    ``ops.deform_conv`` / ``ops.deform_conv_chain`` run it — and its
    argument shapes."""
    def arg(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=dev)

    ho, wo = (h - 1) // s + 1, (w - 1) // s + 1
    geo = dict(kernel_size=3, stride=s, dilation=1, offset_bound=BOUND)
    tiles = dict(tile_h=None, tile_w=None, tile_c=None, tile_m=None)
    x, off, wt = arg((BATCH, h, w, c)), arg((BATCH, ho, wo, 18)), \
        arg((9, c, c))
    if datapath == "fp32":
        spec = plan.DCSpec(dataflow="zero_copy", interpret=False, **geo,
                           **tiles)
        return functools.partial(plan.bounded_forward, spec), (x, off, wt)
    if datapath == "int8":
        def fn(x, off, wt, sx, sw):
            return plan.int8_forward(x, off, wt, x_scale=sx, w_scale=sw,
                                     interpret=False, **geo, **tiles)
        return fn, (x, off, wt, arg(()), arg((c,)))

    def fn(x, wt, w_off, b_off, b, sx, sw, swo, sy):
        return plan.chain_forward(
            x, wt, w_off, b_off, b, x_scale=sx, w_scale=sw,
            w_offset_scale=swo, y_scale=sy, emit="int8", interpret=False,
            **geo, **tiles)
    return fn, (x, wt, arg((9, c, 18)), arg((18,)), arg((c,)), arg(()),
                arg((c,)), arg((18,)), arg(()))


@pytest.mark.parametrize("datapath", DATAPATHS)
@pytest.mark.parametrize("shape", SHAPES,
                         ids=lambda s: "{}x{}x{}_s{}".format(*s))
def test_forward_kernel_compiles_for_v5e(one_chip, shape, datapath):
    fn, args = _program(datapath, *shape, one_chip)
    # chip_smoke.py runs under "highest": the kernels must pin their own
    # MXU precision (Mosaic refuses an fp32 contraction of int8).
    with jax.default_matmul_precision("highest"):
        compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
