"""The ResNet-DCN detector served by ``repro.serve.DCLServingEngine``.

What the harness needs of this family, found by the ``family`` key of a
configuration file:

* ``System(cfg, seed, tap, log)``: seeded weights made on the device
  in one jitted call, a calibration table from the plain reference
  (int8 rungs), and the engine, built as ``repro.launch.serve`` builds
  it: one bucket, the configuration's slots, ``batch_window=0``;
* ``System.numbers(sample, served, taps)``: the numbers that decide
  ``correct``, from the served results against the plain reference
  (``bench/reference/resnet_dcn.py``), which takes nothing the program
  made;
* ``System.control(sample, taps)``: the reference put in the program's
  place at the precision below the one the configuration states;
* ``layer_work(cfg, res)``: operations and bytes of every convolution
  and DCL of one image, from the layer shapes.
"""
from __future__ import annotations

import functools
import json
import math
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import checks, work
from bench.reference import resnet_dcn as ref

INT8_RUNGS = ("int8_chain",)
# Offsets of the seeded offset convolutions have about this standard
# deviation in pixels (the served model's own init is zero, which would
# leave every DCL a plain convolution).
OFFSET_STD_PX = 1.0


def key_of(seed: int):
    """A PRNG key from any whole seed, including ones over 32 bits."""
    k = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(k, seed >> 32)


def param_shapes(cfg) -> dict:
    """The served model's parameter tree, as shapes."""
    def gn(c):
        return {"scale": (c,), "bias": (c,)}
    tree = {"stem": {"conv": (7, 7, 3, cfg["stem_width"]),
                     "gn": gn(cfg["stem_width"])}}
    for name, cin, width, _, is_dcn in ref.blocks(cfg):
        mid = width // 4
        b = {"conv1": (1, 1, cin, mid), "gn1": gn(mid), "gn2": gn(mid),
             "conv3": (1, 1, mid, width), "gn3": gn(width)}
        if is_dcn:
            b["dcl"] = {"w_offset": (3, 3, mid, 18), "b_offset": (18,),
                        "w_deform": (3, 3, mid, mid), "b_deform": (mid,)}
        else:
            b["conv2"] = (3, 3, mid, mid)
        if name.endswith("b0") or cin != width:
            b["proj"] = (1, 1, cin, width)
            b["gn_proj"] = gn(width)
        tree[name] = b
    c = cfg["widths"][-1]
    tree["head"] = {"conv": (3, 3, c, 256), "gn": gn(256),
                    "cls": (1, 1, 256, cfg["num_classes"] + 1),
                    "box": (1, 1, 256, 4)}
    return tree


def _init_leaf(name, shape, z):
    """One leaf from standard normals ``z`` of its shape."""
    if name == "scale":
        return 1.0 + 0.1 * z
    if name in ("bias", "b_deform"):
        return 0.1 * z
    if name == "b_offset":
        return 0.5 * OFFSET_STD_PX * z
    fan_in = math.prod(shape[:-1])
    if name == "w_offset":
        # GroupNorm + ReLU inputs have a second moment of about 1/2.
        return z * OFFSET_STD_PX / math.sqrt(fan_in / 2)
    return z * math.sqrt(2.0 / fan_in)


MODEL_KEYS = ("stage_sizes", "widths", "stem_width", "num_dcn", "num_classes",
              "offset_bound")


def model_key(cfg) -> str:
    """The sizes of ``cfg`` as a hashable key of the jitted programs."""
    return json.dumps({k: cfg[k] for k in MODEL_KEYS}, sort_keys=True)


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
            leaves.append(_init_leaf(name, shape, z[at:at + n].reshape(shape)))
            at += n
        return tree.unflatten(leaves)
    return make


def init_params(cfg, seed: int):
    """Every weight, on the device, from one jitted call: one draw of
    standard normals, cut into the leaves and scaled."""
    return _maker(model_key(cfg))(key_of(seed))


@functools.lru_cache(maxsize=None)
def _absmax_forward(key: str):
    """Jitted fp32 reference that returns each DCL input's and output's
    absolute maximum: the calibration sweep."""
    cfg = json.loads(key)

    def run(params, images):
        amax = {}
        ref.forward(params, cfg, images,
                    tap=lambda n, a: amax.__setitem__(n, jnp.max(jnp.abs(a))))
        return amax
    return jax.jit(run)


def calibration_table(params, cfg, images) -> dict:
    """The scale table the int8 rungs read: per-tensor activation scales
    from the reference's activations, exact per-channel weight scales."""
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


def _arrays(table):
    return None if table is None else {
        name: {k: np.asarray(v, np.float32) for k, v in s.items()}
        for name, s in table.items()}


def layer_work(cfg, res: int) -> list[dict]:
    """Every convolution and DCL of one image's forward at
    ``res``x``res``: kind ("conv" or "dcl"), operations, and elements
    read and written (``work.at_width`` makes them bytes)."""
    n, out = 1, []
    e = res // 2
    out.append(work.conv("stem", n, e, e, 7, 3, cfg["stem_width"], h=res))
    e //= 2                                   # max pool
    for name, cin, width, stride, is_dcn in ref.blocks(cfg):
        mid, eo = width // 4, e // stride
        out.append(work.conv(f"{name}/conv1", n, e, e, 1, cin, mid))
        if is_dcn:
            out.append(work.dcl(name, n, e, e, eo, eo, mid, mid))
        else:
            out.append(work.conv(f"{name}/conv2", n, eo, eo, 3, mid, mid,
                                 h=e))
        out.append(work.conv(f"{name}/conv3", n, eo, eo, 1, mid, width))
        if name.endswith("b0") or cin != width:
            out.append(work.conv(f"{name}/proj", n, eo, eo, 1, cin, width,
                                 h=e))
        e = eo
    c = cfg["widths"][-1]
    out.append(work.conv("head/conv", n, e, e, 3, c, 256))
    out.append(work.conv("head/cls", n, e, e, 1, 256, cfg["num_classes"] + 1))
    out.append(work.conv("head/box", n, e, e, 1, 256, 4))
    return out


class System:
    """One configuration, built from the seed and served by the engine."""

    def __init__(self, cfg: dict, seed: int, tap=None, log=lambda msg: None):
        t0 = time.monotonic()
        from repro.models.resnet_dcn import ResNetDCNConfig
        from repro.serve import DCLServeConfig, DCLServingEngine

        self.cfg = cfg
        self.rung = cfg["rung"]
        self.bucket = cfg["bucket"]
        self.slots = cfg["slots"]
        self.params = jax.block_until_ready(init_params(cfg, seed))
        t1 = time.monotonic()
        log(f"set-up phase program import and weights: {t1 - t0:.3f} s")
        self.table = None
        if self.rung in INT8_RUNGS:
            rng = np.random.default_rng([seed % 2**64, 0])
            calib = rng.standard_normal(
                (self.slots, self.bucket, self.bucket, 3), np.float32)
            self.table = calibration_table(self.params, cfg, calib)
        t2 = time.monotonic()
        log(f"set-up phase calibration: {t2 - t1:.3f} s")
        model_cfg = ResNetDCNConfig(
            name=cfg["arch"], stage_sizes=tuple(cfg["stage_sizes"]),
            widths=tuple(cfg["widths"]), stem_width=cfg["stem_width"],
            num_dcn=cfg["num_dcn"], offset_bound=cfg["offset_bound"],
            num_classes=cfg["num_classes"], img_size=cfg["img_size"],
            use_kernel=self.rung != "fp32_ref")
        self.engine = DCLServingEngine(
            self.params, model_cfg,
            DCLServeConfig(buckets=(self.bucket,), slots=self.slots,
                           quant=self.rung, batch_window=0.0),
            scale_table=self.table, tap=tap)
        log(f"set-up phase engine start: {time.monotonic() - t2:.3f} s")

    # -- what the window measures ------------------------------------
    def work_per_image(self) -> list[dict]:
        """Each layer of one image with the peak it is held to: the int8
        DCLs at the int8 peak, everything else, float32 included, at the
        bf16 peak (so no share can pass 100%)."""
        out = []
        for w in layer_work(self.cfg, self.bucket):
            int8 = w["kind"] == "dcl" and self.rung in INT8_RUNGS
            out.append(dict(w, datapath="int8_ops" if int8 else "bf16_flops",
                            bytes=work.at_width(w, 1 if int8 else 4)))
        return out

    def queue_wait(self) -> tuple[float, int]:
        h = self.engine.metrics.histogram("serve_queue_wait_seconds")
        b = str(self.bucket)
        return h.sum(bucket=b), h.count(bucket=b)

    # -- correctness ---------------------------------------------------
    def reference(self, images, *, dcl="fp32", precision="highest"):
        """Reference outputs for a list of images, in batches of slots."""
        fwd = _reference(model_key(self.cfg), dcl, precision)
        scales = _arrays(self.table)
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
        """Each tapped DCL recomputed by the reference on the input the
        engine fed it: {name: (integer outputs, scale)}."""
        out = {}
        for name, _, _, stride, is_dcn in ref.blocks(self.cfg):
            if not is_dcn or name not in taps:
                continue
            q, sy = _dcl_int(self.params[name]["dcl"], taps[name],
                             _arrays(self.table)[name],
                             stride=stride,
                             bound=self.cfg["offset_bound"], bits=bits)
            out[name] = (np.asarray(q), float(sy))
        return out

    def numbers(self, images, served, taps) -> dict[str, float]:
        """The compared numbers, from served results ``served`` (one
        {"cls", "box"} per image) and the tapped layers of one batch."""
        fp32 = self.reference(images)
        if self.rung not in INT8_RUNGS:
            return {"fp32_max": checks.worst_max_rel(served, fp32),
                    "fp32_l2": checks.worst_l2_rel(served, fp32)}
        chain = self.reference(images, dcl="int8")
        want = self.dcl_reference(taps)
        got = {n: np.asarray(taps[f"{n}/out"]) for n in want}
        dcls = [b[0] for b in ref.blocks(self.cfg) if b[-1]]
        # A layer the tap never saw is a layer not checked: all of it
        # counts as differing.
        flips = checks.flip_share(got, want) if len(want) == len(dcls) \
            else 1.0
        return {"chain_l2": checks.worst_l2_rel(served, chain),
                "fp32_l2": checks.worst_l2_rel(served, fp32),
                "dcl_flip_share": flips}

    def control(self, images, taps):
        """The reference in the program's place, one precision down:
        ``high`` (three bf16 passes) for float32 at ``highest``, int4
        for the int8 DCLs.  Returns (served, taps) to feed ``numbers``."""
        if self.rung not in INT8_RUNGS:
            return self.reference(images, precision="high"), {}
        served = self.reference(images, dcl="int4")
        low = self.dcl_reference(taps, bits="int4")
        taps = dict(taps)
        for n, (q, sy) in low.items():
            # int4 outputs on the int8 grid of the layer: what a 4-bit
            # layer would hand on, read back as the served tap is read.
            taps[f"{n}/out"] = q * sy
        return served, taps
