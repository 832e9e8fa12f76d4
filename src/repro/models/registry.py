"""Architecture registry: --arch <id> -> config + input specs + step fns.

Every assigned architecture registers an ``ArchSpec`` with its exact
published configuration and its own input-shape set.  ``input_specs``
returns weak-type-correct ``ShapeDtypeStruct`` stand-ins (no device
allocation) — the dry-run lowers against these.
"""
from __future__ import annotations

import dataclasses
import importlib
from typing import Any, Callable

import jax
import jax.numpy as jnp

from .transformer import (ModelConfig, abstract_cache, abstract_params,
                          cache_specs, param_specs)
from .resnet_dcn import ResNetDCNConfig

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class ShapeSpec:
    kind: str               # train | prefill | decode | train_det | infer_det
    seq_len: int = 0
    global_batch: int = 1
    note: str = ""


# The LM-family shape set shared by all 10 assigned architectures.
LM_SHAPES: dict[str, ShapeSpec] = {
    "train_4k": ShapeSpec("train", 4096, 256),
    "prefill_32k": ShapeSpec("prefill", 32768, 32),
    "decode_32k": ShapeSpec("decode", 32768, 128),
    "long_500k": ShapeSpec("decode", 524288, 1,
                           note="sub-quadratic archs only"),
}


@dataclasses.dataclass(frozen=True)
class ArchSpec:
    name: str
    family: str                       # dense | moe | ssm | hybrid | audio | vlm | cnn
    config: Any                       # ModelConfig or ResNetDCNConfig
    shapes: dict[str, ShapeSpec]
    long_context_ok: bool = False     # may run long_500k
    source: str = ""
    notes: str = ""
    # per-arch logical->mesh rule overrides (e.g. dbrx: expert parallelism
    # because its 16 experts divide the 16-way model axis; §Perf E)
    rules_overrides: dict | None = None


_REGISTRY: dict[str, ArchSpec] = {}

ARCH_MODULES = [
    "command_r_35b", "tinyllama_1_1b", "glm4_9b", "deepseek_7b",
    "recurrentgemma_9b", "musicgen_medium", "pixtral_12b", "rwkv6_3b",
    "dbrx_132b", "grok_1_314b", "resnet50_dcn", "resnet101_dcnv2",
]


def register(spec: ArchSpec) -> ArchSpec:
    _REGISTRY[spec.name] = spec
    return spec


def _ensure_loaded() -> None:
    if _REGISTRY:
        return
    for mod in ARCH_MODULES:
        importlib.import_module(f"repro.configs.{mod}")


def get(name: str) -> ArchSpec:
    _ensure_loaded()
    if name not in _REGISTRY:
        raise KeyError(f"unknown arch {name!r}; have {sorted(_REGISTRY)}")
    return _REGISTRY[name]


def names() -> list[str]:
    _ensure_loaded()
    return sorted(_REGISTRY)


def runnable_cells() -> list[tuple[str, str]]:
    """All (arch, shape) dry-run cells, honouring the long-context skip."""
    _ensure_loaded()
    cells = []
    for name in names():
        spec = _REGISTRY[name]
        for shape_name, shape in spec.shapes.items():
            if shape_name == "long_500k" and not spec.long_context_ok:
                continue
            cells.append((name, shape_name))
    return cells


def skipped_cells() -> list[tuple[str, str, str]]:
    _ensure_loaded()
    out = []
    for name in names():
        spec = _REGISTRY[name]
        for shape_name in spec.shapes:
            if shape_name == "long_500k" and not spec.long_context_ok:
                out.append((name, shape_name,
                            "full-attention arch: 0.5M-token dense KV/attn "
                            "per step is out of scope by design (DESIGN.md)"))
    return out


def reduced_config(arch: ArchSpec):
    """Small same-family config for CPU smoke tests: same mixer pattern,
    same GQA ratio, same MoE routing — tiny widths.  The FULL configs are
    exercised only via the dry-run (ShapeDtypeStruct, no allocation)."""
    import dataclasses
    import jax.numpy as jnp
    cfg = arch.config
    if isinstance(cfg, ResNetDCNConfig):
        return dataclasses.replace(
            cfg, stage_sizes=(1, 1, 1, 1), widths=(32, 64, 128, 256),
            stem_width=16, num_dcn=2, num_classes=8, img_size=64)
    plen = len(cfg.pattern)
    n_layers = plen * 2 + (cfg.n_layers % plen)
    kv = max(1, (4 * cfg.kv_heads) // cfg.n_heads)
    kw = dict(
        n_layers=n_layers, d_model=64, n_heads=4, kv_heads=kv,
        head_dim=16, d_ff=128, vocab=128, dtype=jnp.float32, remat="none",
        name=cfg.name + "-reduced")
    if cfg.window is not None:
        kw["window"] = 16
    if cfg.moe is not None:
        from .moe import MoEConfig
        kw["moe"] = MoEConfig(d_model=64, d_ff=128, num_experts=4,
                              top_k=min(2, cfg.moe.top_k),
                              kind=cfg.moe.kind)
    if cfg.rwkv is not None:
        from .rwkv6 import RWKVConfig
        kw["rwkv"] = RWKVConfig(d_model=64, d_ff=128, head_dim=16,
                                decay_lora_rank=8)
    if cfg.rglru is not None:
        from .rglru import RGLRUConfig
        kw["rglru"] = RGLRUConfig(d_model=64, d_rnn=64)
    return dataclasses.replace(cfg, **kw)


# ---------------------------------------------------------------------------
# Input specs (ShapeDtypeStruct stand-ins; no allocation)
# ---------------------------------------------------------------------------

def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


def input_specs(arch: ArchSpec, shape_name: str) -> dict[str, Any]:
    """Abstract inputs for the step function of (arch, shape)."""
    shape = arch.shapes[shape_name]
    cfg = arch.config

    if isinstance(cfg, ResNetDCNConfig):
        b, hw = shape.global_batch, cfg.img_size
        hc = hw // 32
        batch = {
            "images": _sds((b, hw, hw, 3), jnp.float32),
            "obj": _sds((b, hc, hc), jnp.float32),
            "cls": _sds((b, hc, hc), jnp.int32),
            "box": _sds((b, hc, hc, 4), jnp.float32),
        }
        return {"batch": batch}

    b, s = shape.global_batch, shape.seq_len
    tok_shape = (b, s) if cfg.codebooks == 1 else (b, s, cfg.codebooks)
    if shape.kind == "train":
        batch = {"tokens": _sds(tok_shape, jnp.int32),
                 "targets": _sds(tok_shape, jnp.int32)}
        if cfg.frontend_embeds:
            batch["frontend"] = _sds((b, 256, cfg.d_model), cfg.dtype)
        return {"batch": batch}
    if shape.kind == "prefill":
        out = {"tokens": _sds(tok_shape, jnp.int32)}
        if cfg.frontend_embeds:
            out["frontend"] = _sds((b, 256, cfg.d_model), cfg.dtype)
        return out
    if shape.kind == "decode":
        tok = (b,) if cfg.codebooks == 1 else (b, cfg.codebooks)
        return {
            "tokens": _sds(tok, jnp.int32),
            "pos": _sds((b,), jnp.int32),
            "caches": abstract_cache(cfg, b, s),
        }
    raise ValueError(shape.kind)


def input_shardings(arch: ArchSpec, shape_name: str, mesh) -> dict[str, Any]:
    """PartitionSpec tree matching ``input_specs`` (under active rules)."""
    from jax.sharding import PartitionSpec as P
    from repro.distributed.sharding import logical_spec
    shape = arch.shapes[shape_name]
    cfg = arch.config

    def bs(shp, axes):
        return logical_spec(shp, axes, mesh=mesh)

    if isinstance(cfg, ResNetDCNConfig):
        b, hw = shape.global_batch, cfg.img_size
        hc = hw // 32
        return {"batch": {
            # images spatially partitioned on H over the model axis;
            # GSPMD inserts the conv halo exchanges (the TPU analogue of
            # the paper's Eq. 6 row-band + halo dataflow, at mesh scale).
            "images": bs((b, hw, hw, 3), ("batch", "spatial", None, None)),
            "obj": bs((b, hc, hc), ("batch", "spatial", None)),
            "cls": bs((b, hc, hc), ("batch", "spatial", None)),
            "box": bs((b, hc, hc, 4), ("batch", "spatial", None, None)),
        }}

    b, s = shape.global_batch, shape.seq_len
    tok_axes = ("batch", "seq") if cfg.codebooks == 1 \
        else ("batch", "seq", None)
    tok_shape = (b, s) if cfg.codebooks == 1 else (b, s, cfg.codebooks)
    if shape.kind == "train":
        out = {"batch": {"tokens": bs(tok_shape, tok_axes),
                         "targets": bs(tok_shape, tok_axes)}}
        if cfg.frontend_embeds:
            out["batch"]["frontend"] = bs((b, 256, cfg.d_model),
                                          ("batch", None, None))
        return out
    if shape.kind == "prefill":
        out = {"tokens": bs(tok_shape, tok_axes)}
        if cfg.frontend_embeds:
            out["frontend"] = bs((b, 256, cfg.d_model),
                                 ("batch", None, None))
        return out
    if shape.kind == "decode":
        tok = (b,) if cfg.codebooks == 1 else (b, cfg.codebooks)
        tok_ax = ("batch",) if cfg.codebooks == 1 else ("batch", None)
        return {
            "tokens": bs(tok, tok_ax),
            "pos": bs((b,), ("batch",)),
            "caches": cache_specs(cfg, b, s),
        }
    raise ValueError(shape.kind)
