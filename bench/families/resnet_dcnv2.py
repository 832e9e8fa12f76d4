"""The ResNet-DCNv2 detector served by ``repro.serve.DCLServingEngine``.

The ResNet-DCN family (``bench/families/resnet_dcn.py``) with modulated
DCLs (DCNv2): every DCL's offset convolution has 3*K*K outputs, the
last K*K mask logits.  What differs from that family is overridden
here, and the rest is its code:

* ``param_shapes``: 27 offset channels per DCL.  The seeded weights are
  drawn as that family draws its offset convolutions, so the mask
  logits come out about N(0, 1) and the masks spread over (0, 1): a
  zero mask bias would make every mask 0.5 and hide a kernel that drops
  it;
* the plain reference is ``bench/reference/resnet_dcnv2.py``
  (calibration, ``correct``, the control);
* ``layer_work``: a modulated DCL's work, ``2 N Ho Wo K^2 C (M + 3 K^2)``
  operations and the mask's offset-conv weights on top of the bytes;
* the engine's model config has ``modulated=True``.
"""
from __future__ import annotations

import functools
import json
import math
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench.families import resnet_dcn as v1
from bench.reference import resnet_dcnv2 as ref

K2 = ref.K * ref.K
MASK_CHANNELS = K2        # offset-conv outputs a DCNv2 layer adds
model_key = v1.model_key


def param_shapes(cfg) -> dict:
    """The served model's parameter tree, as shapes."""
    tree = v1.param_shapes(cfg)
    for name, *_, is_dcn in ref.blocks(cfg):
        if is_dcn:
            dcl = tree[name]["dcl"]
            kh, kw, c, n = dcl["w_offset"]
            dcl["w_offset"] = (kh, kw, c, n + MASK_CHANNELS)
            dcl["b_offset"] = (n + MASK_CHANNELS,)
    return tree


@functools.lru_cache(maxsize=None)
def _maker(key: str):
    shapes = param_shapes(json.loads(key))
    flat, tree = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=lambda s: isinstance(s, tuple))
    names = [path[-1].key for path, _ in flat]
    dims = [s for _, s in flat]
    counts = [math.prod(s) for s in dims]

    @jax.jit
    def make(key):
        z = jax.random.normal(key, (sum(counts),), jnp.float32)
        leaves, at = [], 0
        for name, shape, n in zip(names, dims, counts):
            leaves.append(v1._init_leaf(name, shape,
                                        z[at:at + n].reshape(shape)))
            at += n
        return tree.unflatten(leaves)
    return make


def init_params(cfg, seed: int):
    """Every weight, on the device, from one jitted call."""
    return _maker(model_key(cfg))(v1.key_of(seed))


@functools.lru_cache(maxsize=None)
def _absmax_forward(key: str):
    cfg = json.loads(key)

    def run(params, images):
        amax = {}
        ref.forward(params, cfg, images,
                    tap=lambda n, a: amax.__setitem__(n, jnp.max(jnp.abs(a))))
        return amax
    return jax.jit(run)


def calibration_table(params, cfg, images) -> dict:
    """The scale table the int8 rungs read: per-tensor activation scales
    from the reference's activations, exact per-channel weight scales
    (27 of them for each offset convolution)."""
    amax = jax.device_get(_absmax_forward(model_key(cfg))(params, images))

    def chan(w):
        a = np.abs(np.asarray(w, np.float32)).reshape(-1, w.shape[-1])
        return [float(max(v, ref.EPS) / 127.0) for v in a.max(axis=0)]
    table = {}
    for name, *_, is_dcn in ref.blocks(cfg):
        if not is_dcn:
            continue
        p = jax.device_get(params[name]["dcl"])
        table[name] = {
            "x_scale": float(max(amax[name], ref.EPS) / 127.0),
            "y_scale": float(max(amax[f"{name}/out"], ref.EPS) / 127.0),
            "w_scale": chan(p["w_deform"]),
            "w_offset_scale": chan(p["w_offset"])}
    return table


_dcl_int = jax.jit(ref.dcl_int, static_argnames=("stride", "bound", "bits"))


@functools.lru_cache(maxsize=None)
def _reference(key: str, dcl: str, precision: str):
    cfg = json.loads(key)
    return jax.jit(lambda params, x, scales: ref.forward(
        params, cfg, x, dcl=dcl, scales=scales, precision=precision))


def layer_work(cfg, res: int) -> list[dict]:
    """``resnet_dcn.layer_work`` with modulated DCLs: the offset
    convolution's K^2 more outputs make a DCL's operations
    ``2 N Ho Wo K^2 C (M + 3 K^2)`` and add ``K^2 C K^2`` weights read."""
    mids = {name: width // 4
            for name, _, width, _, is_dcn in ref.blocks(cfg) if is_dcn}
    out = []
    for w in v1.layer_work(cfg, res):
        if w["kind"] == "dcl":
            c = m = mids[w["name"]]
            w = dict(w, ops=w["ops"] // (m + 2 * K2) * (m + 3 * K2),
                     elems=w["elems"] + K2 * c * MASK_CHANNELS)
        out.append(w)
    return out


class System(v1.System):
    """One DCNv2 configuration, built from the seed and served by the
    engine."""

    def __init__(self, cfg: dict, seed: int, tap=None, log=lambda msg: None):
        t0 = time.monotonic()
        from repro.models.resnet_dcn import ResNetDCNConfig
        from repro.serve import DCLServeConfig, DCLServingEngine

        self.cfg = cfg
        self.rung = cfg["rung"]
        self.bucket = cfg["bucket"]
        self.slots = cfg["slots"]
        # First, so that a program without modulated DCLs refuses the
        # configuration before any weight is made.
        model_cfg = ResNetDCNConfig(
            name=cfg["arch"], stage_sizes=tuple(cfg["stage_sizes"]),
            widths=tuple(cfg["widths"]), stem_width=cfg["stem_width"],
            num_dcn=cfg["num_dcn"], offset_bound=cfg["offset_bound"],
            num_classes=cfg["num_classes"], img_size=cfg["img_size"],
            use_kernel=self.rung != "fp32_ref", modulated=True)
        self.params = jax.block_until_ready(init_params(cfg, seed))
        t1 = time.monotonic()
        log(f"set-up phase program import and weights: {t1 - t0:.3f} s")
        self.table = None
        if self.rung in v1.INT8_RUNGS:
            rng = np.random.default_rng([seed % 2**64, 0])
            calib = rng.standard_normal(
                (self.slots, self.bucket, self.bucket, 3), np.float32)
            self.table = calibration_table(self.params, cfg, calib)
        t2 = time.monotonic()
        log(f"set-up phase calibration: {t2 - t1:.3f} s")
        self.engine = DCLServingEngine(
            self.params, model_cfg,
            DCLServeConfig(buckets=(self.bucket,), slots=self.slots,
                           quant=self.rung, batch_window=0.0),
            scale_table=self.table, tap=tap)
        log(f"set-up phase engine start: {time.monotonic() - t2:.3f} s")

    def work_per_image(self) -> list[dict]:
        out = []
        for w in layer_work(self.cfg, self.bucket):
            int8 = w["kind"] == "dcl" and self.rung in v1.INT8_RUNGS
            out.append(dict(w, datapath="int8_ops" if int8 else "bf16_flops",
                            bytes=v1.work.at_width(w, 1 if int8 else 4)))
        return out

    def reference(self, images, *, dcl="fp32", precision="highest"):
        fwd = _reference(model_key(self.cfg), dcl, precision)
        scales = v1._arrays(self.table)
        outs = []
        for i in range(0, len(images), self.slots):
            chunk = images[i:i + self.slots]
            x = np.zeros((self.slots,) + chunk[0].shape, np.float32)
            x[:len(chunk)] = chunk
            y = jax.device_get(fwd(self.params, x, scales))
            outs += [{k: y[k][j] for k in ("cls", "box")}
                     for j in range(len(chunk))]
        return outs

    def dcl_reference(self, taps, bits="int8"):
        out = {}
        for name, _, _, stride, is_dcn in ref.blocks(self.cfg):
            if not is_dcn or name not in taps:
                continue
            q, sy = _dcl_int(self.params[name]["dcl"], taps[name],
                             v1._arrays(self.table)[name],
                             stride=stride,
                             bound=self.cfg["offset_bound"], bits=bits)
            out[name] = (np.asarray(q), float(sy))
        return out
