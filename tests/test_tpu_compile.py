"""Compile-only checks of the bounded DCL forward kernels, and of the
serving engine's whole jitted forward, for TPU v5e.

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
import dataclasses
import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops, plan

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


def _program(datapath, h, w, c, s, dev, n_off=18):
    """The jitted per-layer program of one datapath — padding,
    quantization, weight blocking and the kernel, exactly as
    ``ops.deform_conv`` / ``ops.deform_conv_chain`` run it — and its
    argument shapes.  ``n_off=27`` makes the chain layer modulated
    (DCNv2)."""
    def arg(shape, dtype=jnp.float32, sharding=dev):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

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
    return fn, (x, wt, arg((9, c, n_off)), arg((n_off,)), arg((c,)),
                arg(()), arg((c,)), arg((n_off,)), arg(()))


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


@pytest.mark.parametrize("shape", SHAPES,
                         ids=lambda s: "{}x{}x{}_s{}".format(*s))
def test_modulated_chain_kernel_compiles_for_v5e(one_chip, shape):
    """The DCNv2 chain kernel (27 fused offset-conv outputs, the per-tap
    sigmoid mask) at the c3, c4 and c5 shapes of ResNet-101 at 512x512,
    under its own kernel name."""
    fn, args = _program("chain", *shape, one_chip, n_off=27)
    with jax.default_matmul_precision("highest"):
        text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    assert "%dcnv2_forward_kernel" in text


def _kernel_eqns(jaxpr):
    """Every ``pallas_call`` equation of ``jaxpr``, nested ones too."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _kernel_eqns(sub)


@pytest.mark.parametrize("n_off", (18, 27))
def test_modulation_is_traced_only_by_modulated_plans(n_off):
    """A DCNv1 plan traces the kernel it always did: no sigmoid in its
    body, 2*K*K offset columns read and its usual name; a modulated plan
    reads 3*K*K and applies the sigmoid (traced here, not compiled)."""
    fn, args = _program("chain", 32, 32, 256, 1, None, n_off=n_off)
    eqns = list(_kernel_eqns(jax.make_jaxpr(fn)(*args).jaxpr))
    assert len(eqns) == 1
    kernel = eqns[0].params["jaxpr"]
    assert ("logistic" in str(kernel)) == (n_off == 27)
    blocks = [bm.block_shape for bm in
              eqns[0].params["grid_mapping"].block_mappings]
    # The fused offset-conv weights, scales and bias: n_off columns.
    assert [getattr(b[-1], "block_size", b[-1]) for b in blocks[1:4]] \
        == [n_off] * 3
    name = eqns[0].params["name"]
    assert (name == "dcnv2_forward_kernel") == (n_off == 27)


@pytest.fixture
def mosaic_lowering(monkeypatch):
    """Lower the bounded kernels with Mosaic although the backend is the
    CPU (``launch.platform.set_platform`` refuses that without a chip);
    every cache that baked the platform is dropped on both sides."""
    from repro.launch import platform
    monkeypatch.setattr(platform, "_platform", "tpu")
    platform._invalidate_lowering_caches()
    yield
    monkeypatch.undo()
    platform._invalidate_lowering_caches()


def test_served_program_compiles_for_v5e(one_chip, mosaic_lowering):
    """The engine's jitted ``int8_chain`` forward of the published
    detector (512x512, batch 4, B=2) compiles for v5e as one program
    that holds all 12 chained DCL kernels."""
    from repro.configs.resnet50_dcn import CONFIG_BOUNDED
    from repro.models import resnet_dcn as R
    from repro.serve import (DCLServeConfig, DCLServingEngine,
                             bucket_layer_dims)

    res, slots = 512, BATCH
    cfg = dataclasses.replace(CONFIG_BOUNDED, use_kernel=True)
    params = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        jax.eval_shape(functools.partial(R.init_params, cfg=cfg),
                       jax.random.PRNGKey(0)))
    # Any scales on the table's layout: the program's shape, not its
    # numbers, is under test.
    table = {name: {"x_scale": 0.05, "y_scale": 0.05,
                    "w_scale": [0.01] * d["m"],
                    "w_offset_scale": [0.01] * 18}
             for name, d in bucket_layer_dims(cfg, res).items()}
    eng = DCLServingEngine(params, cfg,
                           DCLServeConfig(buckets=(res,), slots=slots),
                           scale_table=table)
    fwd, _ = eng._program(res, "int8_chain", eng._cfgs["int8_chain"])
    x = jax.ShapeDtypeStruct((slots, res, res, 3), jnp.float32,
                             sharding=one_chip)
    scales = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        eng._scales)
    with jax.default_matmul_precision("highest"), \
            ops.degradation_scope(False):
        text = fwd.lower(params, x, scales).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == \
        cfg.num_dcn
