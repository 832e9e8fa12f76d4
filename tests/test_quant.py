"""repro.quant: int8 datapath — QTensor primitives, the int8 zero-copy
kernel vs the fake-quant reference (<= 1 LSB of the output scale across
the edge-geometry matrix), dtype-aware tile budgets, calibration
observers, QAT through the Trainer, and this PR's modeled-traffic
acceptance gate (int8 >= 3x below fp32 zero-copy)."""
import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops
from repro.quant import (AbsMaxObserver, PercentileObserver, QMAX,
                         calibrate_resnet_dcn, compute_scale, fake_quant,
                         fake_quant_dcl_reference, quantize)

# (name, H, W, C, M, K, stride, dil, bound, off_scale) — the same
# geometry matrix as tests/test_kernel_geometry.py.  Offsets are drawn
# on a 1/8 grid: eighths are exact in fp32 in any coordinate frame, so
# the kernel's band-local bilinear and the reference's global-frame
# bilinear produce bit-identical pre-round patch values and the 1-LSB
# gate measures the datapaths, not knife-edge rounding of ties.
EDGE_CASES = [
    ("ragged_h", 13, 16, 4, 8, 3, 1, 1, 2.0, 1.0),
    ("ragged_w", 16, 18, 4, 8, 3, 1, 1, 2.0, 1.0),
    ("ragged_hw", 11, 13, 4, 4, 3, 1, 1, 1.5, 1.0),
    ("stride2", 16, 16, 4, 8, 3, 2, 1, 2.0, 1.0),
    ("dilation2", 16, 16, 4, 8, 3, 1, 2, 2.0, 1.0),
    ("clamp_hit", 12, 12, 4, 8, 3, 1, 1, 1.0, 4.0),
    ("stride2_ragged_clamp", 15, 13, 4, 4, 3, 2, 1, 1.5, 4.0),
    ("multi_c_chunk", 16, 16, 8, 8, 3, 1, 1, 2.0, 1.0),
]


def _case_arrays(name, h, w, c, m, k, s, d, off_scale):
    key = jax.random.PRNGKey(zlib.crc32(name.encode()) % (2 ** 31))
    x = jax.random.normal(key, (2, h, w, c), jnp.float32)
    pad = d * (k // 2)
    ho = (h + 2 * pad - d * (k - 1) - 1) // s + 1
    wo = (w + 2 * pad - d * (k - 1) - 1) // s + 1
    offs = jax.random.normal(jax.random.fold_in(key, 1),
                             (2, ho, wo, 2 * k * k), jnp.float32) * off_scale
    offs = jnp.round(offs * 8) / 8
    wgt = jax.random.normal(jax.random.fold_in(key, 2),
                            (k * k, c, m), jnp.float32) * 0.2
    return x, offs, wgt


# ---------------------------------------------------------------------------
# qtypes
# ---------------------------------------------------------------------------

def test_qtensor_roundtrip_error_bounded():
    x = jax.random.normal(jax.random.PRNGKey(0), (4, 8, 8, 16), jnp.float32)
    for axis in (None, -1):
        q = quantize(x, axis=axis)
        assert q.values.dtype == jnp.int8
        err = jnp.abs(q.dequantize() - x)
        # round-to-nearest onto the grid: error <= scale/2 everywhere
        assert float(jnp.max(err / q.scale)) <= 0.5 + 1e-6


def test_per_channel_beats_per_tensor():
    """Per-channel scales adapt to channel magnitude spread."""
    key = jax.random.PRNGKey(1)
    x = jax.random.normal(key, (64, 8), jnp.float32) \
        * jnp.logspace(-2, 0, 8)[None, :]
    e_t = float(jnp.mean(jnp.abs(quantize(x).dequantize() - x)))
    e_c = float(jnp.mean(jnp.abs(quantize(x, axis=-1).dequantize() - x)))
    assert e_c < e_t


def test_fake_quant_ste_gradients():
    scale = jnp.float32(0.1)
    x = jnp.array([0.03, -1.0, 12.8, -12.8, 5.0], jnp.float32)
    g = jax.grad(lambda v: jnp.sum(fake_quant(v, scale)))(x)
    # pass-through inside [-127*s, 127*s] = [-12.7, 12.7], zero outside
    np.testing.assert_allclose(np.asarray(g), [1, 1, 0, 0, 1])


# ---------------------------------------------------------------------------
# int8 kernel vs fake-quant reference (<= 1 LSB of the output scale)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("case", EDGE_CASES, ids=lambda c: c[0])
def test_int8_kernel_matches_fake_quant_reference(case):
    name, h, w, c, m, k, s, d, bound, off_scale = case
    x, offs, wgt = _case_arrays(name, h, w, c, m, k, s, d, off_scale)
    got = ops.deform_conv(x, offs, wgt, kernel_size=k, stride=s,
                          dilation=d, offset_bound=bound, precision="int8")
    want = fake_quant_dcl_reference(x, offs, wgt, kernel_size=k, stride=s,
                                    dilation=d, offset_bound=bound)
    # 1 LSB of the per-output-channel dequant scale s_x * s_w[m]
    lsb = (np.asarray(compute_scale(x))
           * np.asarray(compute_scale(wgt, axis=-1)).reshape(-1))
    err = np.abs(np.asarray(got) - np.asarray(want)) / lsb
    assert float(err.max()) <= 1.0, (name, float(err.max()))


def test_int8_kernel_close_to_fp32():
    """End-to-end sanity: the quantized kernel tracks the fp32 kernel to
    quantization accuracy (not bit parity — an 8-bit grid)."""
    x, offs, wgt = _case_arrays("vs_fp32", 16, 16, 8, 8, 3, 1, 1, 1.0)
    yq = ops.deform_conv(x, offs, wgt, offset_bound=2.0, precision="int8")
    yf = ops.deform_conv(x, offs, wgt, offset_bound=2.0)
    rel = float(jnp.linalg.norm(yq - yf) / jnp.linalg.norm(yf))
    assert rel < 0.05, rel


def test_int8_calibrated_scales_override():
    """Explicit (calibrated) scales are honored: quantizing with a 2x
    coarser activation scale changes the output accordingly."""
    x, offs, wgt = _case_arrays("scales", 12, 12, 4, 8, 3, 1, 1, 1.0)
    sx = float(compute_scale(x))
    sw = np.asarray(compute_scale(wgt, axis=-1)).reshape(-1)
    got = ops.deform_conv(x, offs, wgt, offset_bound=2.0, precision="int8",
                          x_scale=jnp.float32(2 * sx),
                          w_scale=jnp.asarray(sw))
    want = fake_quant_dcl_reference(x, offs, wgt, offset_bound=2.0,
                                    x_scale=jnp.float32(2 * sx),
                                    w_scale=jnp.asarray(sw))
    lsb = 2 * sx * sw
    err = np.abs(np.asarray(got) - np.asarray(want)) / lsb
    assert float(err.max()) <= 1.0


def test_int8_requires_bound_and_zero_copy():
    x, offs, wgt = _case_arrays("errs", 12, 12, 4, 8, 3, 1, 1, 1.0)
    with pytest.raises(ValueError, match="offset_bound"):
        ops.deform_conv(x, offs, wgt, precision="int8")
    with pytest.raises(ValueError, match="zero-copy"):
        ops.deform_conv(x, offs, wgt, offset_bound=2.0, precision="int8",
                        dataflow="banded")
    with pytest.raises(ValueError, match="precision"):
        ops.deform_conv(x, offs, wgt, offset_bound=2.0, precision="int4")


# ---------------------------------------------------------------------------
# Satellite: clear ValueError on indivisible channel chunks
# ---------------------------------------------------------------------------

def test_channel_chunk_value_error():
    x, offs, wgt = _case_arrays("chunks", 12, 12, 6, 8, 3, 1, 1, 1.0)
    for kwargs in ({"tile_c": 4}, {"dataflow": "banded", "tile_c": 4}):
        with pytest.raises(ValueError, match="tile_c=4 does not divide C=6"):
            ops.deform_conv(x, offs, wgt, offset_bound=2.0, tile_h=4,
                            tile_w=4, **kwargs)
    with pytest.raises(ValueError, match="tile_m=3 does not divide M=8"):
        ops.deform_conv(x, offs, wgt, offset_bound=2.0, tile_h=4,
                        tile_w=4, tile_m=3)
    with pytest.raises(ValueError, match="tile_c=4 does not divide C=6"):
        ops.deform_sample(x, offs, offset_bound=2.0, tile_h=4, tile_w=4,
                          tile_c=4)


# ---------------------------------------------------------------------------
# Dtype-aware tile budgets
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("budget", [1 << 20, 2 << 20])
def test_dtype_budget_monotone_tiles(budget):
    """Under a binding VMEM budget the chooser's Eq. 6 band working set
    (tile_h * tile_w * tile_c elements) must widen monotonically as
    bytes-per-element shrink — int8 packs 4x the band of fp32 into the
    same VMEM.  At 1 MiB fp32 admits no tile at all: its smallest
    kernel tile (whole 128-lane channel tiles, double-buffered weight
    blocks) needs more, so the chooser must refuse it (0 elements)
    while bf16 and int8 still fit."""
    from repro.core.tiling import LayerShape, choose_kernel_tiles
    shape = LayerShape(h=64, w=64, c_in=128, c_out=128, offset_bound=2.0)
    elems = {}
    for dtype in ("fp32", "bf16", "int8"):
        if dtype == "fp32" and budget == 1 << 20:
            with pytest.raises(ValueError, match="no zero-copy tile "
                                                 "configuration fits"):
                choose_kernel_tiles(shape, dtype=dtype, objective="forward",
                                    vmem_budget=budget)
            elems[dtype] = 0
            continue
        kt = choose_kernel_tiles(shape, dtype=dtype, objective="forward",
                                 vmem_budget=budget)
        elems[dtype] = kt.tile_h * kt.tile_w * kt.tile_c
    assert elems["fp32"] <= elems["bf16"] <= elems["int8"], elems
    assert elems["int8"] > elems["fp32"], elems


def test_dtype_budget_unconstrained_agree():
    """With VMEM unconstrained the traffic argmin is dtype-independent
    (traffic scales uniformly), so the chosen tiles coincide."""
    from repro.core.tiling import LayerShape, choose_kernel_tiles
    shape = LayerShape(h=32, w=32, c_in=64, c_out=64, offset_bound=2.0)
    tiles = {d: choose_kernel_tiles(shape, dtype=d, objective="forward")
             for d in ("fp32", "int8")}
    assert tiles["fp32"] == tiles["int8"], tiles


def test_dtype_bytes_helper():
    from repro.core.tiling import dtype_bytes
    assert dtype_bytes("int8") == 1
    assert dtype_bytes("bf16") == 2
    assert dtype_bytes("fp32") == 4
    assert dtype_bytes(jnp.int8) == 1
    with pytest.raises(ValueError):
        dtype_bytes(None)


# ---------------------------------------------------------------------------
# Acceptance gate: modeled int8 traffic
# ---------------------------------------------------------------------------

def test_int8_traffic_acceptance_gate():
    """This PR's acceptance: modeled zero-copy HBM input traffic for the
    bounded 3x3 reference layer (H=W=64, C=M=128, batch=4, tile_h=8)
    drops >= 3x under int8 vs fp32 — and the PR-1/2 fp32 gates must not
    regress (the dw-flush cadence fix only lowers zero-copy bwd)."""
    from repro.core.perf_model import dataflow_traffic_report
    rep = dataflow_traffic_report(h=64, w=64, c=128, m=128, batch=4,
                                  tile_h=8, offset_bound=2.0)
    assert rep["q_ratio"] >= 3.0, rep
    assert rep["q_total_ratio"] >= 2.0, rep
    assert rep["ratio"] >= 2.0, rep
    assert rep["bwd_ratio"] >= 2.0, rep
    assert rep["train_ratio"] >= 2.0, rep


# ---------------------------------------------------------------------------
# Calibration
# ---------------------------------------------------------------------------

def test_observers():
    key = jax.random.PRNGKey(3)
    xs = [jax.random.normal(jax.random.fold_in(key, i), (1024,)) * 3
          for i in range(4)]
    am, pc = AbsMaxObserver(), PercentileObserver(99.0)
    for x in xs:
        am.update(x)
        pc.update(x)
    s_am, s_pc = am.scale(), pc.scale()
    amax = max(float(jnp.max(jnp.abs(x))) for x in xs)
    assert s_am == pytest.approx(amax / QMAX)
    # clipping the top 1% of mass gives a strictly finer grid
    assert 0 < s_pc < s_am
    with pytest.raises(ValueError):
        from repro.quant import make_observer
        make_observer("minmax")


def _mini_model():
    from repro.models import resnet_dcn as R
    cfg = R.ResNetDCNConfig(
        stage_sizes=(1, 1, 1, 1), widths=(16, 32, 64, 128), stem_width=8,
        num_dcn=2, num_classes=4, img_size=32, offset_bound=2.0)
    params = R.init_params(jax.random.PRNGKey(0), cfg)
    return R, cfg, params


def _mini_batches(n=2):
    from repro.data import DetectionDataConfig, detection_batch
    data = DetectionDataConfig(img_size=32, global_batch=2, num_classes=4,
                               seed=3)
    return [detection_batch(data, i) for i in range(n)]


def test_calibrate_resnet_dcn_scale_table(tmp_path):
    from repro.quant import load_scale_table, save_scale_table
    R, cfg, params = _mini_model()
    batches = _mini_batches()
    table = calibrate_resnet_dcn(params, cfg, batches)
    layers = [k for k in table if k != "_meta"]
    assert len(layers) == cfg.num_dcn, table.keys()
    for name in layers:
        assert table[name]["x_scale"] > 0
        cout = params[name]["dcl"]["w_deform"].shape[-1]
        assert len(table[name]["w_scale"]) == cout
    # percentile observer clips outliers -> scale no larger than absmax
    table_p = calibrate_resnet_dcn(params, cfg, batches,
                                   observer="percentile", percentile=99.0)
    for name in layers:
        assert table_p[name]["x_scale"] <= table[name]["x_scale"] + 1e-12
    path = tmp_path / "scales.json"
    save_scale_table(table, str(path))
    assert load_scale_table(str(path))[layers[0]]["x_scale"] \
        == pytest.approx(table[layers[0]]["x_scale"])


# ---------------------------------------------------------------------------
# QAT + PTQ through the model / Trainer
# ---------------------------------------------------------------------------

def test_qat_grads_flow_through_kernel_path():
    """cfg.quant='qat' + use_kernel: fake-quant STE composes with the
    custom-VJP zero-copy backward — full-parameter gradient is finite
    and non-zero, and the QAT loss sits near the fp32 loss."""
    import dataclasses

    from jax.flatten_util import ravel_pytree
    R, cfg, params = _mini_model()
    batch = {k: jnp.asarray(v) for k, v in _mini_batches(1)[0].items()}
    cfg_qat = dataclasses.replace(cfg, quant="qat", use_kernel=True)
    l_fp = R.train_loss(params, cfg, batch, lam=0.1)[0]
    l_q, g = jax.value_and_grad(
        lambda p: R.train_loss(p, cfg_qat, batch, lam=0.1)[0])(params)
    flat, _ = ravel_pytree(g)
    assert bool(jnp.all(jnp.isfinite(flat)))
    assert float(jnp.linalg.norm(flat)) > 0
    assert float(jnp.abs(l_q - l_fp)) < 0.2 * float(jnp.abs(l_fp))


def test_qat_trains_through_trainer():
    """The production Trainer runs QAT end-to-end (fake-quant DCLs over
    the custom-VJP kernel path) — steps complete, loss stays finite."""
    import dataclasses
    import tempfile

    from repro.optim import constant, sgd
    from repro.train import Trainer, TrainerConfig
    R, cfg, params = _mini_model()
    cfg_qat = dataclasses.replace(cfg, quant="qat", use_kernel=True)
    batches = _mini_batches(3)
    with tempfile.TemporaryDirectory() as tmp:
        tr = Trainer(
            loss_fn=lambda p, b: R.train_loss(p, cfg_qat, b, lam=0.1),
            params=params,
            optimizer=sgd(constant(0.05), momentum=0.9), mesh=None,
            param_specs=None,
            batch_fn=lambda s: {k: jnp.asarray(v) for k, v in
                                batches[s % len(batches)].items()},
            config=TrainerConfig(total_steps=3, ckpt_every=100,
                                 ckpt_dir=tmp, log_every=1))
        history = tr.run()
    losses = [h["loss"] for h in history if "loss" in h]
    assert len(tr.step_seconds) == 3
    assert all(np.isfinite(losses)), losses


def test_ptq_int8_model_matches_fp32_closely():
    """Post-training int8 (calibrated scales, kernel datapath) tracks
    the fp32 model output; kernel and fake-quant reference paths agree
    far tighter (same quantization grid)."""
    import dataclasses
    R, cfg, params = _mini_model()
    batches = _mini_batches()
    table = calibrate_resnet_dcn(params, cfg, batches)
    images = jnp.asarray(batches[0]["images"])
    out_fp, _ = R.forward(params, cfg, images)
    cfg_q = dataclasses.replace(cfg, quant="int8", use_kernel=True)
    out_q, _ = R.forward(params, cfg_q, images, quant_scales=table)
    rel = float(jnp.linalg.norm(out_q["cls"] - out_fp["cls"])
                / jnp.linalg.norm(out_fp["cls"]))
    assert rel < 0.05, rel
    cfg_qr = dataclasses.replace(cfg, quant="int8", use_kernel=False)
    out_qr, _ = R.forward(params, cfg_qr, images, quant_scales=table)
    rel_kernel_vs_ref = float(
        jnp.linalg.norm(out_qr["cls"] - out_q["cls"])
        / jnp.linalg.norm(out_q["cls"]))
    assert rel_kernel_vs_ref < 1e-4, rel_kernel_vs_ref
