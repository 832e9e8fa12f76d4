"""Parity suite for the band-pipeline emitter.

The four kernels (``deform_sample``, ``deform_conv_fused``,
``deform_conv_q``, ``deform_conv_bwd``) are built on the unified
``kernels/band_pipeline.py`` emitter (``BandSpec``/``DCLPlan`` + the
shared double-buffered band stager), across the ragged/stride-2/
dilation-2/clamp matrix and both ``cores`` settings of the Megacore
backward split:

* fp32 forward outputs and sampled patches match the ``kernels/ref.py``
  oracles within fp32 summation-order tolerance — the forward sampler
  sums the same four bilinear corners as the oracle's gather, through
  static shifted band windows instead of an in-VMEM gather, so only the
  rounding order of the fp32 sums can differ;
* all three gradients are **bit-identical** to the pre-refactor
  kernels — the golden CRCs below were captured from the original
  hand-written backward (commit ``ebe2ce7``);
* the int8 kernel stays within 1 LSB of the fake-quant oracle across
  the same matrix (``tests/test_quant.py`` carries that gate; the
  structural checks here make sure it runs through the emitter too).

The structural tests pin the acceptance criterion directly: no
duplicated band-DMA/double-buffer code remains outside the emitter.
"""
import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops
from repro.kernels.ref import deform_conv_fused_ref, deform_sample_ref

# (name, H, W, C, M, K, stride, dil, bound, tile_h, tile_w, tile_c,
#  off_scale) — explicit tiles so the goldens are chooser-independent;
# multi_c_chunk exercises the double-buffered C-step pipeline.
CASES = [
    ("ragged_h", 13, 16, 4, 8, 3, 1, 1, 2.0, 4, 8, None, 1.0),
    ("ragged_w", 16, 18, 4, 8, 3, 1, 1, 2.0, 4, 8, None, 1.0),
    ("ragged_hw", 11, 13, 4, 4, 3, 1, 1, 1.5, 4, 8, None, 1.0),
    ("stride2", 16, 16, 4, 8, 3, 2, 1, 2.0, 4, 4, None, 1.0),
    ("dilation2", 16, 16, 4, 8, 3, 1, 2, 2.0, 4, 8, None, 1.0),
    ("clamp_hit", 12, 12, 4, 8, 3, 1, 1, 1.0, 4, 8, None, 4.0),
    ("stride2_ragged_clamp", 15, 13, 4, 4, 3, 2, 1, 1.5, 4, 4, None, 4.0),
    ("multi_c_chunk", 16, 16, 8, 8, 3, 1, 1, 2.0, 4, 8, 4, 1.0),
]

# CRC32 of the raw fp32 gradient bytes, captured from the pre-refactor
# kernels (deterministic interpret-mode CPU execution).
GOLDEN = {
    "ragged_h": {"grad_c1": 3654088940, "grad_c2": 194592340},
    "ragged_w": {"grad_c1": 1238844957, "grad_c2": 1232594153},
    "ragged_hw": {"grad_c1": 528650090, "grad_c2": 118858786},
    "stride2": {"grad_c1": 446050605, "grad_c2": 3394195259},
    "dilation2": {"grad_c1": 2895363362, "grad_c2": 3514083084},
    "clamp_hit": {"grad_c1": 2547651994, "grad_c2": 3553168569},
    "stride2_ragged_clamp": {"grad_c1": 3973991461,
                             "grad_c2": 3273117865},
    "multi_c_chunk": {"grad_c1": 2036671525, "grad_c2": 718438290},
}

# fp32 tolerance of the forward parity checks: each output sums at most
# 4 * K^2 * C = 288 fp32 products of O(1) magnitude, so reordering the
# sums moves a result by a few hundred ulps of its largest term at most
# (~1e-5 absolute here); real indexing or weighting errors are O(0.1).
FWD_TOL = dict(rtol=1e-5, atol=1e-5)


def _case_arrays(name, h, w, c, m, k, s, d, off_scale):
    key = jax.random.PRNGKey(zlib.crc32(name.encode()) % (2 ** 31))
    x = jax.random.normal(key, (2, h, w, c), jnp.float32)
    pad = d * (k // 2)
    ho = (h + 2 * pad - d * (k - 1) - 1) // s + 1
    wo = (w + 2 * pad - d * (k - 1) - 1) // s + 1
    offs = jax.random.normal(jax.random.fold_in(key, 1),
                             (2, ho, wo, 2 * k * k), jnp.float32) * off_scale
    wgt = jax.random.normal(jax.random.fold_in(key, 2),
                            (k * k, c, m), jnp.float32) * 0.2
    return x, offs, wgt


def _digest(*arrs):
    h = 0
    for a in arrs:
        h = zlib.crc32(np.ascontiguousarray(np.asarray(a)).tobytes(), h)
    return h


@pytest.mark.parametrize("case", CASES, ids=lambda c: c[0])
def test_forward_bit_identical_to_pre_refactor(case):
    name, h, w, c, m, k, s, d, bound, th, tw, tc, off_scale = case
    x, offs, wgt = _case_arrays(name, h, w, c, m, k, s, d, off_scale)
    y = ops.deform_conv(x, offs, wgt, kernel_size=k, stride=s, dilation=d,
                        offset_bound=bound, tile_h=th, tile_w=tw, tile_c=tc)
    want = deform_conv_fused_ref(x, offs, wgt, kernel_size=k, stride=s,
                                 dilation=d, offset_bound=bound)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), **FWD_TOL,
                               err_msg=name)


@pytest.mark.parametrize("cores", [1, 2])
@pytest.mark.parametrize("case", CASES, ids=lambda c: c[0])
def test_grads_bit_identical_to_pre_refactor(case, cores):
    name, h, w, c, m, k, s, d, bound, th, tw, tc, off_scale = case
    x, offs, wgt = _case_arrays(name, h, w, c, m, k, s, d, off_scale)
    grads = jax.grad(
        lambda xx, oo, ww: jnp.sum(ops.deform_conv(
            xx, oo, ww, kernel_size=k, stride=s, dilation=d,
            offset_bound=bound, tile_h=th, tile_w=tw, tile_c=tc,
            cores=cores)), argnums=(0, 1, 2))(x, offs, wgt)
    assert _digest(*grads) == GOLDEN[name][f"grad_c{cores}"], (name, cores)


@pytest.mark.parametrize("case", CASES, ids=lambda c: c[0])
def test_sample_bit_identical_to_pre_refactor(case):
    name, h, w, c, m, k, s, d, bound, th, tw, tc, off_scale = case
    x, offs, _ = _case_arrays(name, h, w, c, m, k, s, d, off_scale)
    p = ops.deform_sample(x, offs, kernel_size=k, stride=s, dilation=d,
                          offset_bound=bound, tile_h=th, tile_w=tw,
                          tile_c=tc)
    want = deform_sample_ref(x, offs, kernel_size=k, stride=s, dilation=d,
                             offset_bound=bound)
    np.testing.assert_allclose(np.asarray(p), np.asarray(want), **FWD_TOL,
                               err_msg=name)


def test_sample_int8_band_emits_requantized_patches():
    """Sample-only plans accept int8 inputs: the emitter samples them
    in fp32 and re-rounds onto the activation grid (round-to-nearest —
    the quantized-datapath convention) into the patch block."""
    key = jax.random.PRNGKey(3)
    x = jnp.clip(jnp.round(jax.random.normal(key, (1, 12, 12, 4)) * 40),
                 -127, 127).astype(jnp.int8)
    offs = jax.random.normal(jax.random.fold_in(key, 1),
                             (1, 12, 12, 18), jnp.float32)
    got = ops.deform_sample(x, offs, offset_bound=2.0, tile_h=4, tile_w=4)
    assert got.dtype == jnp.int8
    want = jnp.round(deform_sample_ref(x.astype(jnp.float32), offs,
                                       offset_bound=2.0))
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want), atol=1)


# ---------------------------------------------------------------------------
# Structural: one emitter, no duplicated band-DMA/double-buffer code
# ---------------------------------------------------------------------------

def _kernel_source(module_name):
    import importlib
    import inspect
    return inspect.getsource(importlib.import_module(module_name))


def test_band_dma_lives_only_in_the_emitter():
    """Acceptance criterion: all four kernels are emitted through
    band_pipeline — no kernel module carries its own band-DMA /
    double-buffer implementation.  The backward's d_input
    read-modify-write is the one non-band DMA allowed outside."""
    for mod in ("repro.kernels.deform_sample",
                "repro.kernels.deform_conv_fused",
                "repro.kernels.deform_conv_q"):
        src = _kernel_source(mod)
        assert "make_async_copy(" not in src, mod
        assert "N_BUFFERS =" not in src, mod
    bwd = _kernel_source("repro.kernels.deform_conv_bwd")
    assert "make_band_dma" not in bwd.replace(
        "from .band_pipeline import", ""), \
        "bwd should stage bands via the shared BandStager"
    assert "N_BUFFERS =" not in bwd
    # the rmw DMA (d_input scatter flush) is the only raw async copy left
    assert bwd.count("pltpu.make_async_copy(") == 2


def test_all_forward_kernels_share_one_emitter():
    """The sample, fused-fp32, int8 and chain kernels are all
    ``band_pipeline.forward_call`` instantiations."""
    import repro.kernels.band_pipeline as bp
    import repro.kernels.deform_conv_fused as fused
    import repro.kernels.deform_conv_q as q
    import repro.kernels.deform_sample as ds
    assert ds.forward_call is bp.forward_call
    assert fused.forward_call is bp.forward_call
    assert q.forward_call is bp.forward_call


def test_plan_validation():
    from repro.kernels.band_pipeline import BandSpec, DCLPlan
    band = BandSpec(kernel_size=3, stride=1, dilation=1, offset_bound=2.0,
                    tile_h=4, tile_w=8)
    assert band.band_h == 4 - 1 + 2 + 2 * 2 + 2
    with pytest.raises(AssertionError):
        DCLPlan(band=band, tile_c=4, epilogue="nope")
    with pytest.raises(ValueError, match="unsupported band dtype"):
        DCLPlan(band=band, tile_c=4, band_dtype="int4")
    # fp16 inputs stage like bf16 (previously accepted — keep it so)
    assert DCLPlan(band=band, tile_c=4,
                   band_dtype="float16").jnp_band_dtype() == jnp.float16
    plan = DCLPlan(band=band, tile_c=4, tile_m=8, band_dtype="int8",
                   acc_dtype="int32", epilogue="requant")
    assert plan.contract and plan.jnp_acc_dtype() == jnp.int32
