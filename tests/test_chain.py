"""int8 layer-chaining datapath (``quant="int8_chain"``): the fused
offset-conv stage, int8 output emission with per-channel requant, the
two-layer int8 -> int8 handoff, the friendly incompatibility errors,
the modeled-traffic acceptance gate, and chain-mode training through
the production Trainer."""
import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops
from repro.kernels.ref import deform_sample_ref
from repro.quant import QMAX, compute_scale, fake_quant_dcl_chain_reference

# (name, H, W, C, M, K, stride, dil, bound) — chain needs C == M only
# across the handoff; single-layer cases may differ.
GEOMS = [
    ("base", 16, 16, 8, 8, 3, 1, 1, 2.0),
    ("ragged", 13, 15, 4, 8, 3, 1, 1, 2.0),
    ("stride2", 16, 16, 4, 8, 3, 2, 1, 2.0),
    ("dilation2", 16, 16, 4, 4, 3, 1, 2, 1.5),
]


def _layer(name, c, m, k, scale=0.2):
    key = jax.random.PRNGKey(zlib.crc32(name.encode()) % (2 ** 31))
    k2 = k * k
    return {
        "w": jax.random.normal(key, (k2, c, m), jnp.float32) * scale,
        "w_off": jax.random.normal(jax.random.fold_in(key, 1),
                                   (k2, c, 2 * k2), jnp.float32) * 0.1,
        "b_off": jax.random.normal(jax.random.fold_in(key, 2),
                                   (2 * k2,), jnp.float32) * 0.5,
        "b": jax.random.normal(jax.random.fold_in(key, 3),
                               (m,), jnp.float32) * 0.1,
    }


def _int_reference(x, lay, *, k, s, d, bound, sx, sy=None):
    """Exact-integer oracle of the chain kernel: integer-valued fp32
    arithmetic (|q| <= 127, K^2*C-term sums < 2^24 — exact in fp32)
    mirrors the kernel's int32 MXU accumulation bit-for-bit."""
    from repro.core.deform_conv import conv2d
    k2 = k * k
    c, m = lay["w"].shape[1], lay["w"].shape[2]
    sw = np.asarray(compute_scale(lay["w"], axis=-1)).reshape(-1)
    swo = np.asarray(compute_scale(lay["w_off"], axis=-1)).reshape(-1)
    xq = jnp.clip(jnp.round(x / sx), -QMAX, QMAX)
    wq = jnp.clip(jnp.round(lay["w"] / sw.reshape(1, 1, -1)), -QMAX, QMAX)
    woq = jnp.clip(jnp.round(lay["w_off"] / swo.reshape(1, 1, -1)),
                   -QMAX, QMAX)
    offs = conv2d(xq, woq.reshape(k, k, c, 2 * k2), stride=s, dilation=d,
                  padding=d * (k // 2))
    offs = offs * (sx * swo.reshape(1, 1, 1, -1)) + lay["b_off"]
    patches = jnp.round(deform_sample_ref(
        xq, offs, kernel_size=k, stride=s, dilation=d, offset_bound=bound))
    acc = jnp.einsum("nhwkc,kcm->nhwm", patches, wq,
                     preferred_element_type=jnp.float32)
    if sy is None:
        return acc * (sx * sw).reshape(1, 1, 1, -1) + lay["b"]
    return jnp.clip(jnp.round(acc * (sx * sw / sy).reshape(1, 1, 1, -1)
                              + (lay["b"] / sy).reshape(1, 1, 1, -1)),
                    -127, 127)


@pytest.mark.parametrize("geom", GEOMS, ids=lambda g: g[0])
def test_chain_kernel_matches_integer_reference(geom):
    """The fused offset-conv stage + requant epilogue reproduce the
    exact-integer oracle to within one step of the int8 output grid.

    Both accumulate int32-exactly and dequant/requant through the same
    fp32 expressions, but the offset dequant (``acc * scale + bias``)
    and the bilinear weights are separate fp32 roundings that a
    compiler may contract differently (fused multiply-add) on either
    side.  A value that lands within an ulp of a .5 rounding boundary
    can then round either way: +-1 on the int8 grid, and rarely — the
    count is bounded too, so a real indexing or scaling error (which
    moves most outputs by many steps) still fails."""
    name, h, w, c, m, k, s, d, bound = geom
    key = jax.random.PRNGKey(zlib.crc32(name.encode()) % (2 ** 31))
    x = jax.random.normal(key, (2, h, w, c), jnp.float32)
    lay = _layer(name, c, m, k)
    sx = float(compute_scale(x))
    sy = 0.9 * sx
    sw = np.asarray(compute_scale(lay["w"], axis=-1)).reshape(-1)
    swo = np.asarray(compute_scale(lay["w_off"], axis=-1)).reshape(-1)
    got = ops.deform_conv_chain(
        x, lay["w"], lay["w_off"], lay["b_off"], lay["b"], kernel_size=k,
        stride=s, dilation=d, offset_bound=bound, x_scale=sx,
        w_scale=jnp.asarray(sw), w_offset_scale=jnp.asarray(swo),
        y_scale=sy, emit="int8")
    want = _int_reference(x, lay, k=k, s=s, d=d, bound=bound, sx=sx, sy=sy)
    assert got.dtype == jnp.int8
    delta = np.abs(np.asarray(got, np.float32) - np.asarray(want))
    assert delta.max() <= 1, (name, delta.max())
    assert np.count_nonzero(delta) <= 0.005 * delta.size, \
        (name, np.count_nonzero(delta))


def test_chain_kernel_m_tiled_reuses_staged_band():
    """With tile_m < M the chained kernel revisits each spatial tile
    once per M-tile; the staged band and the fused offsets are computed
    at mm == 0 and reused (VMEM scratch persists across the sequential
    M axis) — the M-tiled emission must equal the untiled one exactly."""
    name, h, w, c, m, k, s, d, bound = GEOMS[0]
    key = jax.random.PRNGKey(9)
    x = jax.random.normal(key, (2, h, w, c), jnp.float32)
    lay = _layer(name, c, m, k)
    sx = float(compute_scale(x))
    kw = dict(kernel_size=k, stride=s, dilation=d, offset_bound=bound,
              x_scale=sx, y_scale=0.8 * sx, emit="int8")
    full = ops.deform_conv_chain(x, lay["w"], lay["w_off"], lay["b_off"],
                                 lay["b"], tile_m=m, **kw)
    tiled = ops.deform_conv_chain(x, lay["w"], lay["w_off"], lay["b_off"],
                                  lay["b"], tile_m=m // 2, **kw)
    np.testing.assert_array_equal(np.asarray(full), np.asarray(tiled))


def test_chain_int8_input_consumed_verbatim():
    """An int8 input on the x_scale grid produces the same emission as
    the fp32 head quantized in-op — the handoff is lossless."""
    name, h, w, c, m, k, s, d, bound = GEOMS[0]
    key = jax.random.PRNGKey(5)
    x = jax.random.normal(key, (2, h, w, c), jnp.float32)
    lay = _layer(name, c, m, k)
    sx = float(compute_scale(x))
    kw = dict(kernel_size=k, stride=s, dilation=d, offset_bound=bound,
              x_scale=sx, y_scale=0.5 * sx, emit="int8")
    y_fp_head = ops.deform_conv_chain(x, lay["w"], lay["w_off"],
                                      lay["b_off"], lay["b"], **kw)
    x_q = jnp.clip(jnp.round(x / sx), -QMAX, QMAX).astype(jnp.int8)
    y_q_head = ops.deform_conv_chain(x_q, lay["w"], lay["w_off"],
                                     lay["b_off"], lay["b"], **kw)
    np.testing.assert_array_equal(np.asarray(y_fp_head),
                                  np.asarray(y_q_head))


def _two_layer_setup(h=16, w=16, c=8, k=3, bound=2.0):
    key = jax.random.PRNGKey(11)
    x = jax.random.normal(key, (2, h, w, c), jnp.float32)
    lays = [_layer(f"lay{i}", c, c, k) for i in range(2)]
    params = [{"w_deform": lay["w"].reshape(k, k, c, c),
               "w_offset": lay["w_off"].reshape(k, k, c, 2 * k * k),
               "b_offset": lay["b_off"], "b_deform": lay["b"]}
              for lay in lays]
    # Calibrate the exchange grid from the STE reference sweep: layer
    # 0's output observer IS layer 1's input scale.
    sx0 = float(compute_scale(x))
    y0, _ = fake_quant_dcl_chain_reference(
        x, lays[0]["w"], lays[0]["w_off"], lays[0]["b_off"], lays[0]["b"],
        kernel_size=k, offset_bound=bound, x_scale=sx0)
    sy0 = float(compute_scale(y0))
    scales = [{"x_scale": sx0,
               "w_scale": [float(v) for v in np.asarray(
                   compute_scale(lays[0]["w"], axis=-1)).reshape(-1)],
               "w_offset_scale": [float(v) for v in np.asarray(
                   compute_scale(lays[0]["w_off"], axis=-1)).reshape(-1)],
               "y_scale": sy0},
              {"x_scale": sy0,
               "w_scale": [float(v) for v in np.asarray(
                   compute_scale(lays[1]["w"], axis=-1)).reshape(-1)],
               "w_offset_scale": [float(v) for v in np.asarray(
                   compute_scale(lays[1]["w_off"], axis=-1)).reshape(-1)]}]
    return x, lays, params, scales, bound


def test_two_layer_chain_matches_fake_quant_reference():
    """int8 -> int8 two-layer parity: the kernel chain (layer 0 emits a
    QTensor consumed verbatim by layer 1) tracks the STE fake-quant
    reference to <= 1 LSB of the final per-channel output grid."""
    from repro.models.layers import dcl_chain_apply
    x, lays, params, scales, bound = _two_layer_setup()
    y_k, o_k = dcl_chain_apply(params, x, scales_seq=scales,
                               offset_bound=bound, use_kernel=True)
    y_r, o_r = dcl_chain_apply(params, x, scales_seq=scales,
                               offset_bound=bound, use_kernel=False)
    assert y_k.dtype == jnp.float32         # tail has no y_scale
    assert o_k == [None, None]              # fused offsets stay in VMEM
    assert all(v is not None for v in o_r)  # reference observes o_max
    lsb = (scales[1]["x_scale"]
           * np.asarray(scales[1]["w_scale"]).reshape(1, 1, 1, -1))
    err = np.abs(np.asarray(y_k) - np.asarray(y_r)) / lsb
    assert float(err.max()) <= 1.0, float(err.max())


def test_two_layer_chain_intermediate_is_int8():
    """The inter-layer tensor really is the int8 emission: feeding
    layer 1 the QTensor layer 0 emitted equals the monolithic chain."""
    from repro.models.layers import dcl_apply
    from repro.quant.qtypes import QTensor
    x, lays, params, scales, bound = _two_layer_setup()
    y0, _ = dcl_apply(params[0], x, offset_bound=bound, use_kernel=True,
                      quant="int8_chain", quant_scales=scales[0])
    assert isinstance(y0, QTensor) and y0.values.dtype == jnp.int8
    y1, _ = dcl_apply(params[1], y0, offset_bound=bound, use_kernel=True,
                      quant="int8_chain", quant_scales=scales[1])
    from repro.models.layers import dcl_chain_apply
    y_chain, _ = dcl_chain_apply(params, x, scales_seq=scales,
                                 offset_bound=bound, use_kernel=True)
    np.testing.assert_array_equal(np.asarray(y1), np.asarray(y_chain))


# ---------------------------------------------------------------------------
# Friendly incompatibility errors
# ---------------------------------------------------------------------------

def test_chain_value_errors():
    x, lays, params, scales, bound = _two_layer_setup()
    lay = lays[0]
    with pytest.raises(ValueError, match="y_scale"):
        ops.deform_conv_chain(x, lay["w"], lay["w_off"], lay["b_off"],
                              offset_bound=bound, x_scale=1.0, emit="int8")
    with pytest.raises(ValueError, match="x_scale"):
        ops.deform_conv_chain(x, lay["w"], lay["w_off"], lay["b_off"],
                              offset_bound=bound, x_scale=None, emit="fp32")
    with pytest.raises(ValueError, match="tile_c=4 is incompatible"):
        ops.deform_conv_chain(x, lay["w"], lay["w_off"], lay["b_off"],
                              offset_bound=bound, x_scale=1.0, tile_c=4,
                              emit="fp32")
    with pytest.raises(ValueError, match="offset_bound"):
        ops.deform_conv_chain(x, lay["w"], lay["w_off"], lay["b_off"],
                              offset_bound=None, x_scale=1.0, emit="fp32")


def test_chain_layer_compat_errors():
    from repro.models.layers import check_chain_compat, dcl_chain_apply
    x, lays, params, scales, bound = _two_layer_setup()
    # producer without an emission grid
    broken = [dict(scales[0]), dict(scales[1])]
    del broken[0]["y_scale"]
    with pytest.raises(ValueError, match="has no y_scale"):
        dcl_chain_apply(params, x, scales_seq=broken, offset_bound=bound)
    # producer/consumer grid mismatch, named in the error
    broken = [dict(scales[0]), dict(scales[1])]
    broken[1]["x_scale"] = broken[1]["x_scale"] * 2
    with pytest.raises(ValueError, match="disagree on the exchange grid"):
        dcl_chain_apply(params, x, scales_seq=broken, offset_bound=bound)
    # channel handoff mismatch
    with pytest.raises(ValueError, match="C_out=8 channels .* C_in=4"):
        check_chain_compat(scales, couts=[8, 8], cins=[8, 4])
    # table length mismatch
    with pytest.raises(ValueError, match="scale-table entries"):
        dcl_chain_apply(params, x, scales_seq=scales[:1],
                        offset_bound=bound)
    # chain mode without calibration
    from repro.models.layers import dcl_apply
    with pytest.raises(ValueError, match="quant_scales"):
        dcl_apply(params[0], x, offset_bound=bound, quant="int8_chain")
    with pytest.raises(ValueError, match="offset_bound"):
        dcl_apply(params[0], x, quant="int8_chain",
                  quant_scales=scales[0])
    # configuration the chained datapath cannot honor fails loudly
    with pytest.raises(ValueError, match="zero-copy"):
        dcl_apply(params[0], x, offset_bound=bound, quant="int8_chain",
                  quant_scales=scales[0], dataflow="banded")
    with pytest.raises(ValueError, match="shard_batch"):
        dcl_apply(params[0], x, offset_bound=bound, quant="int8_chain",
                  quant_scales=scales[0], shard_batch=True)
    with pytest.raises(ValueError, match="cores"):
        dcl_apply(params[0], x, offset_bound=bound, quant="int8_chain",
                  quant_scales=scales[0], cores=2)
    # a handed-over QTensor on the wrong grid (eager call — under jit
    # the static check_chain_compat guard covers this instead)
    from repro.quant.qtypes import QTensor
    wrong = QTensor(values=jnp.zeros(x.shape, jnp.int8),
                    scale=jnp.float32(2 * scales[0]["x_scale"]))
    with pytest.raises(ValueError, match="emitted on scale"):
        dcl_apply(params[0], wrong, offset_bound=bound, use_kernel=True,
                  quant="int8_chain", quant_scales=scales[0])


# ---------------------------------------------------------------------------
# Modeled-traffic acceptance gate
# ---------------------------------------------------------------------------

def test_chain_traffic_acceptance_gate():
    """PR acceptance: the modeled two-layer HBM traffic of the chained
    int8 datapath sits >= 1.3x below per-layer int8 at the bounded 3x3
    reference layer — and the earlier fp32/int8 gates must not
    regress."""
    from repro.core.perf_model import dataflow_traffic_report
    rep = dataflow_traffic_report(h=64, w=64, c=128, m=128, batch=4,
                                  tile_h=8, offset_bound=2.0)
    assert rep["chain_ratio"] >= 1.3, rep
    assert rep["chain_bytes"] < rep["chain_per_layer_bytes"]
    # single-layer kernel-only view: fusing the offsets + int8 emission
    # strictly reduces the whole-layer total
    assert rep["total_bytes_q_fused_offsets"] \
        < rep["zero_copy_total_bytes_q"]
    # earlier acceptance gates stay intact
    assert rep["q_ratio"] >= 3.0, rep
    assert rep["ratio"] >= 2.0 and rep["train_ratio"] >= 2.0, rep


def test_chain_requires_square_channels():
    from repro.core.tiling import (LayerShape, TileConfig,
                                   dcl_chain_hbm_bytes)
    shape = LayerShape(h=32, w=32, c_in=32, c_out=64, offset_bound=2.0)
    with pytest.raises(ValueError, match="C_in"):
        dcl_chain_hbm_bytes(shape, TileConfig(8, 8, 32, 64))


# ---------------------------------------------------------------------------
# Model + Trainer threading
# ---------------------------------------------------------------------------

def _mini_model():
    from repro.models import resnet_dcn as R
    cfg = R.ResNetDCNConfig(
        stage_sizes=(1, 1, 1, 1), widths=(16, 32, 64, 128), stem_width=8,
        num_dcn=2, num_classes=4, img_size=32, offset_bound=2.0)
    return R, cfg, R.init_params(jax.random.PRNGKey(0), cfg)


def _mini_batches(n=2):
    from repro.data import DetectionDataConfig, detection_batch
    data = DetectionDataConfig(img_size=32, global_batch=2, num_classes=4,
                               seed=3)
    return [detection_batch(data, i) for i in range(n)]


def test_calibration_records_chain_scales():
    from repro.quant import calibrate_resnet_dcn
    R, cfg, params = _mini_model()
    table = calibrate_resnet_dcn(params, cfg, _mini_batches())
    layers = [k for k in table if k != "_meta"]
    assert len(layers) == cfg.num_dcn
    for name in layers:
        entry = table[name]
        assert entry["y_scale"] > 0                  # output observer
        k2 = 9
        assert len(entry["w_offset_scale"]) == 2 * k2
        assert "/out" not in name                    # folded, not split


def test_resnet_chain_mode_kernel_vs_reference():
    import dataclasses
    from repro.quant import calibrate_resnet_dcn
    R, cfg, params = _mini_model()
    batches = _mini_batches()
    table = calibrate_resnet_dcn(params, cfg, batches)
    images = jnp.asarray(batches[0]["images"])
    out_fp, _ = R.forward(params, cfg, images)
    cfg_k = dataclasses.replace(cfg, quant="int8_chain", use_kernel=True)
    out_k, o_maxes = R.forward(params, cfg_k, images, quant_scales=table)
    assert o_maxes == {}        # fused offsets never leave VMEM
    rel = float(jnp.linalg.norm(out_k["cls"] - out_fp["cls"])
                / jnp.linalg.norm(out_fp["cls"]))
    assert rel < 0.05, rel      # quantization accuracy, not bit parity
    cfg_r = dataclasses.replace(cfg, quant="int8_chain", use_kernel=False)
    out_r, o_ref = R.forward(params, cfg_r, images, quant_scales=table)
    assert len(o_ref) == cfg.num_dcn
    rel_kr = float(jnp.linalg.norm(out_r["cls"] - out_k["cls"])
                   / jnp.linalg.norm(out_k["cls"]))
    assert rel_kr < 1e-3, rel_kr


def test_chain_mode_trains_through_trainer():
    """quant='int8_chain' threads through the production Trainer: the
    differentiable STE chain reference (use_kernel=False) trains under
    the Eq. 5 objective — steps complete, loss stays finite."""
    import dataclasses
    import tempfile

    from repro.optim import constant, sgd
    from repro.quant import calibrate_resnet_dcn
    from repro.train import Trainer, TrainerConfig
    R, cfg, params = _mini_model()
    batches = _mini_batches(3)
    table = calibrate_resnet_dcn(params, cfg, batches)
    cfg_c = dataclasses.replace(cfg, quant="int8_chain", use_kernel=False)
    with tempfile.TemporaryDirectory() as tmp:
        tr = Trainer(
            loss_fn=lambda p, b: R.train_loss(p, cfg_c, b, lam=0.1,
                                              quant_scales=table),
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
