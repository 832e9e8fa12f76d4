"""Shared neural-net primitives for every architecture in the registry.

Params are plain nested dicts of jnp arrays.  Each module declares its
parameters once as a ``ParamDef`` tree (shape + init + logical sharding
axes); ``init_tree`` materializes arrays and ``spec_tree`` materializes
``PartitionSpec``s from the *same* declaration, so the sharding layout
can never drift from the parameter structure.

All apply functions are pure; activations carry sharding hints via
``repro.distributed.logical_constraint`` (no-ops off-mesh).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable, Mapping, Sequence

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.distributed.sharding import logical_constraint, logical_spec

Array = jax.Array


# ---------------------------------------------------------------------------
# Parameter definitions
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ParamDef:
    """Declarative parameter: shape, init scheme, logical sharding axes."""
    shape: tuple[int, ...]
    axes: tuple[str | None, ...]
    init: str = "normal"          # normal | zeros | ones | embed | uniform
    scale: float | None = None    # stddev override (default: fan-in)
    dtype: Any = jnp.float32

    def __post_init__(self):
        assert len(self.shape) == len(self.axes), (self.shape, self.axes)


def _fan_in(shape: tuple[int, ...]) -> int:
    return int(shape[0]) if len(shape) <= 1 else int(
        math.prod(shape[:-1]))


def init_param(key: Array, d: ParamDef) -> Array:
    if d.init == "zeros":
        return jnp.zeros(d.shape, d.dtype)
    if d.init == "ones":
        return jnp.ones(d.shape, d.dtype)
    scale = d.scale
    if d.init == "embed":
        scale = scale if scale is not None else 1.0
        return jax.random.normal(key, d.shape, d.dtype) * scale
    if d.init == "uniform":
        lim = scale if scale is not None else 1.0 / math.sqrt(_fan_in(d.shape))
        return jax.random.uniform(key, d.shape, d.dtype, -lim, lim)
    scale = scale if scale is not None else 1.0 / math.sqrt(_fan_in(d.shape))
    return jax.random.normal(key, d.shape, d.dtype) * scale


def init_tree(key: Array, defs) -> Any:
    """Materialize a ParamDef pytree into arrays (stable key derivation:
    one fold per leaf path hash, so insertion order doesn't matter)."""
    leaves, treedef = jax.tree_util.tree_flatten(
        defs, is_leaf=lambda x: isinstance(x, ParamDef))
    keys = jax.random.split(key, max(len(leaves), 1))
    arrs = [init_param(k, d) for k, d in zip(keys, leaves)]
    return jax.tree_util.tree_unflatten(treedef, arrs)


def spec_tree(defs) -> Any:
    """ParamDef pytree -> PartitionSpec pytree (uses the active rules)."""
    return jax.tree_util.tree_map(
        lambda d: logical_spec(d.shape, d.axes),
        defs, is_leaf=lambda x: isinstance(x, ParamDef))


def abstract_tree(defs, dtype=None) -> Any:
    """ParamDef pytree -> ShapeDtypeStruct pytree (no allocation)."""
    return jax.tree_util.tree_map(
        lambda d: jax.ShapeDtypeStruct(d.shape, dtype or d.dtype),
        defs, is_leaf=lambda x: isinstance(x, ParamDef))


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------

def rms_norm(x: Array, scale: Array, *, eps: float = 1e-6) -> Array:
    dt = x.dtype
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    return (y * (1.0 + scale.astype(jnp.float32))).astype(dt)


def layer_norm(x: Array, scale: Array, bias: Array | None = None,
               *, eps: float = 1e-5) -> Array:
    dt = x.dtype
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)
    if bias is not None:
        y = y + bias.astype(jnp.float32)
    return y.astype(dt)


def norm_def(d_model: int, kind: str) -> dict[str, ParamDef]:
    if kind == "rms":
        return {"scale": ParamDef((d_model,), (None,), init="zeros")}
    return {"scale": ParamDef((d_model,), (None,), init="ones"),
            "bias": ParamDef((d_model,), (None,), init="zeros")}


def apply_norm(params: Mapping[str, Array], x: Array, kind: str) -> Array:
    if kind == "rms":
        return rms_norm(x, params["scale"])
    return layer_norm(x, params["scale"], params.get("bias"))


# ---------------------------------------------------------------------------
# Rotary position embeddings (half-split / llama convention)
# ---------------------------------------------------------------------------

def rope_freqs(head_dim: int, *, theta: float = 10000.0,
               fraction: float = 1.0) -> Array:
    rot = int(head_dim * fraction) // 2 * 2
    return 1.0 / (theta ** (jnp.arange(0, rot, 2, dtype=jnp.float32) / rot))


def apply_rope(x: Array, positions: Array, *, theta: float = 10000.0,
               fraction: float = 1.0) -> Array:
    """x: (B, S, H, Dh); positions: (B, S) int32."""
    b, s, h, dh = x.shape
    inv = rope_freqs(dh, theta=theta, fraction=fraction)
    rot = inv.shape[0] * 2
    ang = positions.astype(jnp.float32)[..., None] * inv    # (B, S, rot/2)
    sin = jnp.sin(ang)[:, :, None, :]
    cos = jnp.cos(ang)[:, :, None, :]
    xr = x[..., :rot].astype(jnp.float32)
    x1, x2 = jnp.split(xr, 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    return jnp.concatenate([out.astype(x.dtype), x[..., rot:]], -1) \
        if rot < dh else out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Attention (GQA, causal, optional sliding window, optional KV cache)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class AttnConfig:
    d_model: int
    n_heads: int
    kv_heads: int
    head_dim: int
    rope_theta: float = 10000.0
    rope_fraction: float = 1.0
    use_rope: bool = True
    qkv_bias: bool = False
    out_bias: bool = False
    window: int | None = None          # sliding-window size (None = full)
    softcap: float | None = None       # grok-style tanh soft-capping
    qk_norm: bool = False              # per-head RMS on q/k (stability)

    @property
    def group(self) -> int:
        return self.n_heads // self.kv_heads


def heads_tp_size() -> int:
    """Size of the mesh axis the 'heads' logical axis maps to (1 off-mesh)."""
    from repro.distributed.sharding import current_rules
    ctx = current_rules()
    if not ctx or ctx[1] is None:
        return 1
    rules, mesh = ctx
    target = rules.get("heads")
    if target is None:
        return 1
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    axes = (target,) if isinstance(target, str) else tuple(target)
    n = 1
    for ax in axes:
        n *= sizes.get(ax, 1)
    return n


def effective_kv_heads(cfg: AttnConfig) -> int:
    """KV-head count actually carried through attention / the KV cache.

    §Perf hillclimb: with kv_heads < TP degree the (kv, group) einsum
    split loses head sharding entirely — the fp32 score tensor then
    replicates over the model axis (measured 16x memory-term inflation
    on command-r).  Megatron's fix: replicate KV per query group so the
    FLAT head dim shards.  Applied whenever kv doesn't divide TP but
    n_heads does; a pure function of (cfg, active mesh), so the cache
    layout and every mode agree.
    """
    tp = heads_tp_size()
    if tp > 1 and cfg.kv_heads % tp != 0 and cfg.n_heads % tp == 0:
        return cfg.n_heads
    return cfg.kv_heads


def seq_parallel_attention(cfg: AttnConfig) -> bool:
    """Neither kv nor n_heads shardable (e.g. musicgen's 24 heads on a
    16-way axis): fall back to sharding the QUERY-sequence dim of the
    attention computation over the model axis (sequence parallelism)."""
    tp = heads_tp_size()
    return tp > 1 and cfg.n_heads % tp != 0


def attn_def(cfg: AttnConfig) -> dict[str, ParamDef]:
    d, h, kv, dh = cfg.d_model, cfg.n_heads, cfg.kv_heads, cfg.head_dim
    defs: dict[str, ParamDef] = {
        "wq": ParamDef((d, h, dh), ("embed", "heads", None)),
        "wk": ParamDef((d, kv, dh), ("embed", "kv", None)),
        "wv": ParamDef((d, kv, dh), ("embed", "kv", None)),
        "wo": ParamDef((h, dh, d), ("heads", None, "embed")),
    }
    if cfg.qkv_bias:
        defs["bq"] = ParamDef((h, dh), ("heads", None), init="zeros")
        defs["bk"] = ParamDef((kv, dh), ("kv", None), init="zeros")
        defs["bv"] = ParamDef((kv, dh), ("kv", None), init="zeros")
    if cfg.out_bias:
        defs["bo"] = ParamDef((d,), (None,), init="zeros")
    if cfg.qk_norm:
        defs["q_norm"] = ParamDef((dh,), (None,), init="zeros")
        defs["k_norm"] = ParamDef((dh,), (None,), init="zeros")
    return defs


def _qkv(params, x: Array, cfg: AttnConfig, positions: Array):
    """Returns (q, k, v) with k/v expanded to ``effective_kv_heads``
    (see above) so downstream sharding always has a shardable head dim
    when one exists."""
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"].astype(x.dtype))
    k = jnp.einsum("bsd,dhk->bshk", x, params["wk"].astype(x.dtype))
    v = jnp.einsum("bsd,dhk->bshk", x, params["wv"].astype(x.dtype))
    if cfg.qkv_bias:
        q = q + params["bq"].astype(x.dtype)
        k = k + params["bk"].astype(x.dtype)
        v = v + params["bv"].astype(x.dtype)
    if cfg.qk_norm:
        q = rms_norm(q, params["q_norm"])
        k = rms_norm(k, params["k_norm"])
    if cfg.use_rope:
        q = apply_rope(q, positions, theta=cfg.rope_theta,
                       fraction=cfg.rope_fraction)
        k = apply_rope(k, positions, theta=cfg.rope_theta,
                       fraction=cfg.rope_fraction)
    ekv = effective_kv_heads(cfg)
    if ekv != cfg.kv_heads:
        rep = ekv // cfg.kv_heads
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    if seq_parallel_attention(cfg):
        q = logical_constraint(q, "batch", "seq_sp", None, None)
        k = logical_constraint(k, "batch", None, None, None)
        v = logical_constraint(v, "batch", None, None, None)
    else:
        q = logical_constraint(q, "batch", "seq", "heads", None)
        k = logical_constraint(k, "batch", "seq", "kv", None)
        v = logical_constraint(v, "batch", "seq", "kv", None)
    return q, k, v


def _scores_mask(q_pos: Array, k_pos: Array, window: int | None) -> Array:
    """(.., Sq, Sk) boolean keep-mask: causal (+ sliding window)."""
    m = k_pos[..., None, :] <= q_pos[..., :, None]
    if window is not None:
        m &= k_pos[..., None, :] > (q_pos[..., :, None] - window)
    return m


def _sdpa(q: Array, k: Array, v: Array, mask: Array,
          softcap: float | None) -> Array:
    """q: (B,Sq,KV,G,Dh); k/v: (B,Sk,KV,Dh); mask: (B,Sq,Sk)."""
    dh = q.shape[-1]
    scores = jnp.einsum("bqhgd,bkhd->bhgqk", q, k,
                        preferred_element_type=jnp.float32)
    scores = scores / math.sqrt(dh)
    if softcap is not None:
        scores = jnp.tanh(scores / softcap) * softcap
    scores = jnp.where(mask[:, None, None], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    return jnp.einsum("bhgqk,bkhd->bqhgd", probs, v)


def _sdpa_chunked(q: Array, k: Array, v: Array, q_pos: Array, k_pos: Array,
                  window: int | None, softcap: float | None,
                  block: int = 1024) -> Array:
    """Online-softmax (flash-style) attention, scanning KV blocks.

    Never materializes the (Sq, Sk) score matrix — per step only
    (B, KV, G, Sq, block) lives.  Exact (same math as `_sdpa`).
    q: (B,Sq,KV,G,Dh); k,v: (B,Sk,KV,Dh); q_pos: (B,Sq); k_pos: (B,Sk).
    """
    b, sq, kv, g, dh = q.shape
    sk = k.shape[1]
    pad = (-sk) % block
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        k_pos = jnp.pad(k_pos, ((0, 0), (0, pad)),
                        constant_values=jnp.iinfo(jnp.int32).max)
    nb = (sk + pad) // block
    kb = jnp.moveaxis(k.reshape(b, nb, block, kv, dh), 1, 0)
    vb = jnp.moveaxis(v.reshape(b, nb, block, kv, dh), 1, 0)
    kpb = jnp.moveaxis(k_pos.reshape(b, nb, block), 1, 0)
    qf = q.astype(jnp.float32)
    scale = 1.0 / math.sqrt(dh)

    def body(carry, inp):
        m, l, acc = carry
        kc, vc, kp = inp
        s = jnp.einsum("bqhgd,bkhd->bhgqk", qf, kc.astype(jnp.float32))
        s = s * scale
        if softcap is not None:
            s = jnp.tanh(s / softcap) * softcap
        keep = _scores_mask(q_pos, kp, window)           # (B, Sq, block)
        s = jnp.where(keep[:, None, None], s, -1e30)
        bm = jnp.max(s, axis=-1)
        m2 = jnp.maximum(m, bm)
        p = jnp.exp(s - m2[..., None])
        corr = jnp.exp(m - m2)
        l2 = l * corr + jnp.sum(p, axis=-1)
        acc2 = acc * corr[..., None] + jnp.einsum(
            "bhgqk,bkhd->bhgqd", p, vc.astype(jnp.float32))
        return (m2, l2, acc2), None

    m0 = jnp.full((b, kv, g, sq), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((b, kv, g, sq), jnp.float32)
    a0 = jnp.zeros((b, kv, g, sq, dh), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(body, (m0, l0, a0), (kb, vb, kpb))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return jnp.moveaxis(out, 3, 1).astype(q.dtype)       # (B,Sq,KV,G,Dh)


def _sdpa_window_blocks(q: Array, k: Array, v: Array, q_pos: Array,
                        k_pos: Array, window: int,
                        softcap: float | None) -> Array:
    """Sliding-window attention in diagonal blocks of width `window`:
    query block i attends KV blocks (i-1, i) only — FLOPs O(S*2W)
    instead of O(S^2).  Exact for causal windows."""
    b, sq, kv, g, dh = q.shape
    assert k.shape[1] == sq, "window-block path expects self-attention"
    w = window
    pad = (-sq) % w
    if pad:
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0), (0, 0)))
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        q_pos = jnp.pad(q_pos, ((0, 0), (0, pad)),
                        constant_values=-1)
        k_pos = jnp.pad(k_pos, ((0, 0), (0, pad)),
                        constant_values=jnp.iinfo(jnp.int32).max)
    nb = q.shape[1] // w
    qb = jnp.moveaxis(q.reshape(b, nb, w, kv, g, dh), 1, 0)
    kb = jnp.moveaxis(k.reshape(b, nb, w, kv, dh), 1, 0)
    vb = jnp.moveaxis(v.reshape(b, nb, w, kv, dh), 1, 0)
    qpb = jnp.moveaxis(q_pos.reshape(b, nb, w), 1, 0)
    kpb = jnp.moveaxis(k_pos.reshape(b, nb, w), 1, 0)
    # previous block (zeros/sentinel for block 0)
    kprev = jnp.concatenate([jnp.zeros_like(kb[:1]), kb[:-1]], 0)
    vprev = jnp.concatenate([jnp.zeros_like(vb[:1]), vb[:-1]], 0)
    kpprev = jnp.concatenate(
        [jnp.full_like(kpb[:1], jnp.iinfo(jnp.int32).max), kpb[:-1]], 0)

    def body(_, inp):
        qc, qp, kc, vc, kp, kc2, vc2, kp2 = inp
        kcat = jnp.concatenate([kc2, kc], axis=1)        # (B, 2W, KV, Dh)
        vcat = jnp.concatenate([vc2, vc], axis=1)
        kpcat = jnp.concatenate([kp2, kp], axis=1)
        mask = _scores_mask(qp, kpcat, window)
        out = _sdpa(qc, kcat, vcat, mask, softcap)
        return None, out

    _, outs = jax.lax.scan(
        body, None, (qb, qpb, kb, vb, kpb, kprev, vprev, kpprev))
    out = jnp.moveaxis(outs, 0, 1).reshape(b, nb * w, kv, g, dh)
    return out[:, :sq]


DENSE_ATTN_MAX_KV = 4096


def attention(q: Array, k: Array, v: Array, q_pos: Array, k_pos: Array,
              *, window: int | None, softcap: float | None,
              impl: str = "auto") -> Array:
    """Dispatch between dense, chunked (flash-style) and window-block
    attention.  All paths are exact; selection is purely a memory/FLOP
    trade (recorded per cell in EXPERIMENTS.md §Roofline)."""
    sk = k.shape[1]
    if impl == "auto":
        if window is not None and sk > 2 * window and q.shape[1] == sk:
            impl = "window"
        elif sk > DENSE_ATTN_MAX_KV:
            impl = "chunked"
        else:
            impl = "dense"
    if impl == "window":
        return _sdpa_window_blocks(q, k, v, q_pos, k_pos, window, softcap)
    if impl == "chunked":
        return _sdpa_chunked(q, k, v, q_pos, k_pos, window, softcap)
    mask = _scores_mask(q_pos, k_pos, window)
    return _sdpa(q, k, v, mask, softcap)


def attn_apply(params, x: Array, cfg: AttnConfig, *, positions: Array,
               mask: Array | None = None) -> Array:
    """Full-sequence attention (training / prefill).

    x: (B, S, D); positions: (B, S).  mask overrides the default
    causal(+window) mask when given (e.g. VLM prefix blocks).
    """
    b, s, d = x.shape
    q, k, v = _qkv(params, x, cfg, positions)
    ekv = k.shape[2]
    q = q.reshape(b, s, ekv, cfg.n_heads // ekv, cfg.head_dim)
    if mask is None:
        mask = _scores_mask(positions, positions, cfg.window)
    out = _sdpa(q, k, v, mask, cfg.softcap)
    out = out.reshape(b, s, cfg.n_heads, cfg.head_dim)
    y = jnp.einsum("bshk,hkd->bsd", out, params["wo"].astype(x.dtype))
    if cfg.out_bias:
        y = y + params["bo"].astype(x.dtype)
    return logical_constraint(y, "batch", "seq", "embed_no_fsdp")


def attn_decode(params, x: Array, cfg: AttnConfig, *, cache: dict,
                pos: Array) -> tuple[Array, dict]:
    """Single-token decode with a KV cache.

    x: (B, 1, D); cache: {'k','v': (B, S_cache, KV, Dh), 'offset': ()};
    pos: (B,) absolute positions of the new token.  For windowed
    attention the cache is a ring buffer of size >= window.
    """
    b, _, d = x.shape
    s_cache = cache["k"].shape[1]
    q, k, v = _qkv(params, x, cfg, pos[:, None])
    ekv = k.shape[2]
    assert cache["k"].shape[2] == ekv, (
        "cache kv-head layout disagrees with the active sharding rules; "
        "allocate it under the same mesh/rules (effective_kv_heads)")
    slot = pos % s_cache if cfg.window is not None else pos
    k_cache = jax.vmap(
        lambda c, u, i: jax.lax.dynamic_update_slice(c, u, (i, 0, 0)))(
        cache["k"], k.astype(cache["k"].dtype), slot)
    v_cache = jax.vmap(
        lambda c, u, i: jax.lax.dynamic_update_slice(c, u, (i, 0, 0)))(
        cache["v"], v.astype(cache["v"].dtype), slot)

    q = q.reshape(b, 1, ekv, cfg.n_heads // ekv, cfg.head_dim)
    # Absolute position of each cache slot (ring-aware).
    idx = jnp.arange(s_cache)[None, :]
    if cfg.window is not None:
        wraps = (pos[:, None] // s_cache)
        k_pos = jnp.where(idx <= (pos[:, None] % s_cache), wraps * s_cache + idx,
                          (wraps - 1) * s_cache + idx)
    else:
        k_pos = idx
    mask = _scores_mask(pos[:, None], k_pos, cfg.window)
    out = _sdpa(q, k_cache.astype(q.dtype), v_cache.astype(q.dtype),
                mask, cfg.softcap)
    out = out.reshape(b, 1, cfg.n_heads, cfg.head_dim)
    y = jnp.einsum("bshk,hkd->bsd", out, params["wo"].astype(x.dtype))
    if cfg.out_bias:
        y = y + params["bo"].astype(x.dtype)
    return y, {"k": k_cache, "v": v_cache}


def attn_cache_def(cfg: AttnConfig, batch: int, max_len: int,
                   dtype=jnp.bfloat16) -> dict[str, ParamDef]:
    s = min(max_len, cfg.window) if cfg.window is not None else max_len
    ekv = effective_kv_heads(cfg)    # expanded layout shards on 'kv'
    return {
        "k": ParamDef((batch, s, ekv, cfg.head_dim),
                      ("batch", None, "kv", None), init="zeros", dtype=dtype),
        "v": ParamDef((batch, s, ekv, cfg.head_dim),
                      ("batch", None, "kv", None), init="zeros", dtype=dtype),
    }


# ---------------------------------------------------------------------------
# Deformable convolution layer (shared conv-backbone primitive)
# ---------------------------------------------------------------------------

def dcl_def(cin: int, cout: int, k: int = 3, *,
            modulated: bool = False) -> dict[str, ParamDef]:
    """Parameter tree of one DCL: offset conv (zero-init, the paper's
    'offsets start at the regular grid') + deform conv weights.  A
    modulated (DCNv2) layer's offset conv has 3*K*K outputs: the 2*K*K
    offsets, then K*K mask logits (zero-init: every mask starts at
    sigmoid(0) = 0.5, as in DCNv2)."""
    n_off = (3 if modulated else 2) * k * k
    return {
        "w_offset": ParamDef((k, k, cin, n_off), (None, None, None, None),
                             init="zeros"),
        "b_offset": ParamDef((n_off,), (None,), init="zeros"),
        "w_deform": ParamDef((k, k, cin, cout),
                             (None, None, None, "conv_out")),
        "b_deform": ParamDef((cout,), (None,), init="zeros"),
    }


def dcl_apply(params: Mapping[str, Array], x: Array, *,
              kernel_size: int = 3, stride: int = 1, dilation: int = 1,
              offset_bound: float | None = None, use_kernel: bool = False,
              dataflow: str = "zero_copy", quant: str = "none",
              quant_scales: Mapping[str, Any] | None = None,
              cores: int = 1, shard_batch: bool | None = None,
              shard_spatial: bool | None = None,
              dtype: Any = jnp.float32) -> tuple[Array, Array]:
    """One DCL forward pass -> (y, o_max).

    ``use_kernel=True`` with a trained ``offset_bound`` routes through
    the fused Pallas kernel (``repro.kernels.ops.deform_conv``) under
    the requested dataflow — ``"zero_copy"`` (double-buffered in-kernel
    band DMAs, the default) or ``"banded"`` (legacy HBM-materialized
    bands).  Tile sizes come from the Sec. 3.2 chooser (combined
    fwd+bwd traffic objective).  Since PR 2 the kernel path is fully
    differentiable — ``jax.grad`` routes through the fused backward
    kernel (``kernels.deform_conv_bwd``, a ``jax.custom_vjp``), so
    bounded *training* runs zero-copy end-to-end; the pure-JAX gather
    path (``dcl_forward``) remains the parity reference.  ``o_max``
    (the Eq. 5 statistic) is computed from the raw offsets outside the
    kernel, so the regularizer gradient flows through XLA either way.

    Parallel training (PR 4): ``cores`` splits the backward kernel's
    batch grid per Megacore core, and ``shard_batch`` controls the
    data-parallel ``shard_map`` wrap of the kernel path over the active
    mesh's batch axes (None = auto when a mesh is live under
    ``distributed.sharding.use_rules``; True requires it and raises a
    clear ``ValueError`` on non-dividing batches) — both forwarded to
    ``ops.deform_conv``, including under ``quant="qat"`` (the STE
    wrappers act on replicated values outside the kernel, so the
    sharded VJP's d_weights psum is exactly the cotangent they need).

    Spatial sharding (PR 10): ``shard_spatial=True`` splits the height
    axis across the mesh axis mapped from logical ``"spatial"`` with a
    bounded halo exchange per layer (see ``distributed.spatial``) —
    forwarded to ``ops.deform_conv`` on the fp32-kernel, ``"qat"`` and
    ``"int8"`` kernel branches.  It requires the kernel path (the
    reference paths have no shard_map wrap) and is rejected by
    ``"int8_chain"``: the chained plan stages full-height fused-offset
    bands, so spatial serve buckets ladder from ``"int8"``.

    A ``w_offset`` with 3*K*K outputs makes the layer modulated
    (DCNv2, Zhu et al. 2019): the last K*K are mask logits, and every
    path below scales each sampled tap by their sigmoid.  Modulated
    layers refuse ``shard_spatial`` and the kernel path's gradient
    (train them with ``use_kernel=False``).

    ``quant`` selects the int8 datapath modes of ``repro.quant``:

    * ``"qat"`` — fake-quantize the deform-conv operands (activation
      per-tensor, weights per-channel, STE backward) and run the
      normal fp32 machinery on the quantized grid; the offset conv and
      every gradient stay fp32, so the Trainer's custom-VJP zero-copy
      backward is untouched.  Scales are dynamic absmax unless
      ``quant_scales`` ({"x_scale": .., "w_scale": [..]}) pins them.
    * ``"int8"`` — inference: the kernel path dispatches
      ``ops.deform_conv(precision="int8")`` (int8 band DMA + int8 MXU
      with fused dequant); the non-kernel path runs the bit-level
      fake-quant reference (including the patch requantization the
      kernel performs before its MXU step).
    * ``"int8_chain"`` — the layer-chaining datapath: the offset conv
      is fused into the kernel (quantized, computed from the staged
      Eq. 6 band — no separate fp32 pass) and, when the calibration
      table carries a ``y_scale``, the output is emitted int8 on that
      grid (a ``repro.quant.QTensor``) with the deform bias folded
      into the fused requant — back-to-back DCLs chain int8 -> int8
      with no fp32 HBM round-trip (see ``dcl_chain_apply``).  Requires
      calibrated ``quant_scales`` (``calibrate_resnet_dcn`` records
      x/w/w_offset/y scales) and a trained ``offset_bound``.  The
      kernel path is inference (``o_max`` is None — the fused offsets
      never leave VMEM); with ``use_kernel=False`` the differentiable
      STE chain reference runs instead, so chained configs *train*
      through the production ``Trainer`` unchanged.
    """
    from repro.core.deform_conv import (DCLConfig, conv2d, dcl_forward,
                                        is_modulated, offset_abs_max)
    if quant not in ("none", "qat", "int8", "int8_chain"):
        raise ValueError(
            f"unknown quant mode {quant!r}; expected 'none', 'qat', "
            f"'int8' or 'int8_chain'")
    # The Eq. 3 statistic reads the offsets, not a modulated layer's
    # mask logits after them.
    n_off = 2 * kernel_size * kernel_size
    if shard_spatial and is_modulated(params["w_offset"].shape[-1],
                                      kernel_size * kernel_size):
        from repro.kernels.ops import SPATIAL_DCNV2
        raise ValueError(SPATIAL_DCNV2)
    if quant == "int8_chain":
        # Fail loudly on configuration the chained datapath cannot
        # honor (mirroring the int8 branch's dataflow passthrough
        # contract) instead of silently running zero-copy/unsharded.
        if dataflow != "zero_copy":
            raise ValueError(
                f"quant='int8_chain' supports only the zero-copy "
                f"dataflow (got {dataflow!r}); the fused offset stage "
                f"and int8 emission are band-pipeline plans")
        if shard_batch:
            raise ValueError(
                "shard_batch=True is not supported by the chained int8 "
                "inference datapath (it partitions via GSPMD like the "
                "int8 branch); train chain configs via the STE "
                "reference (use_kernel=False)")
        if cores != 1:
            raise ValueError(
                f"cores={cores} applies to the fp32 training backward "
                f"only — the chained int8 datapath is inference, pass "
                f"cores=1")
        if shard_spatial:
            raise ValueError(
                "shard_spatial=True is not supported by the chained int8 "
                "datapath — the fused offset stage computes offsets from "
                "the staged band, so halo rows alone cannot reproduce "
                "them at shard seams; use quant='int8' for spatially "
                "sharded buckets")
        return _dcl_chain_layer(params, x, kernel_size=kernel_size,
                                stride=stride, dilation=dilation,
                                offset_bound=offset_bound,
                                use_kernel=use_kernel,
                                quant_scales=quant_scales, dtype=dtype)
    if shard_spatial and not (use_kernel and offset_bound is not None):
        raise ValueError(
            "shard_spatial=True requires the bounded kernel path "
            "(use_kernel=True with a trained offset_bound) — the "
            "reference paths have no spatial shard_map wrap")
    cin = x.shape[-1]
    cout = params["w_deform"].shape[-1]
    cfg = DCLConfig(in_channels=cin, out_channels=cout,
                    kernel_size=kernel_size, stride=stride,
                    dilation=dilation, offset_bound=offset_bound,
                    dtype=dtype)
    k = cfg.kernel_size

    if quant != "none":
        from repro.kernels import ops, ref
        from repro.quant.qat import (fake_quant_dcl_reference,
                                     qat_quantize_inputs)
        xc = x.astype(dtype)
        # Offsets always generate at full precision (the address path
        # of the accelerator — never quantized).
        offsets = conv2d(xc, params["w_offset"].astype(xc.dtype),
                         stride=stride, dilation=dilation, padding=cfg.pad)
        offsets = offsets + params["b_offset"].astype(xc.dtype)
        o_max = offset_abs_max(offsets[..., :n_off])
        w = params["w_deform"].astype(xc.dtype).reshape(k * k, cin, cout)
        x_scale = quant_scales.get("x_scale") if quant_scales else None
        w_scale = quant_scales.get("w_scale") if quant_scales else None
        kernel_ok = use_kernel and offset_bound is not None
        if quant == "qat":
            xq, wq = qat_quantize_inputs(xc, w, x_scale=x_scale,
                                         w_scale=w_scale)
            if kernel_ok:
                y = ops.deform_conv(xq, offsets, wq, kernel_size=k,
                                    stride=stride, dilation=dilation,
                                    offset_bound=offset_bound,
                                    dataflow=dataflow, cores=cores,
                                    shard_batch=shard_batch,
                                    shard_spatial=shard_spatial)
            else:
                y = ref.deform_conv_fused_ref(xq, offsets, wq,
                                              kernel_size=k, stride=stride,
                                              dilation=dilation,
                                              offset_bound=offset_bound)
        else:  # int8 (inference datapath)
            if kernel_ok:
                ws = None if w_scale is None \
                    else jnp.asarray(w_scale, jnp.float32)
                # dataflow passes through so a banded config raises
                # ops' ValueError instead of silently running zero-copy
                y = ops.deform_conv(xc, offsets, w, kernel_size=k,
                                    stride=stride, dilation=dilation,
                                    offset_bound=offset_bound,
                                    dataflow=dataflow,
                                    precision="int8", x_scale=x_scale,
                                    w_scale=ws,
                                    shard_spatial=shard_spatial)
            else:
                y = fake_quant_dcl_reference(xc, offsets, w, kernel_size=k,
                                             stride=stride,
                                             dilation=dilation,
                                             offset_bound=offset_bound,
                                             x_scale=x_scale,
                                             w_scale=w_scale)
        return y + params["b_deform"].astype(xc.dtype), o_max

    if use_kernel and offset_bound is not None:
        from repro.kernels import ops
        offsets = conv2d(x, params["w_offset"].astype(x.dtype),
                         stride=stride, dilation=dilation, padding=cfg.pad)
        offsets = offsets + params["b_offset"].astype(x.dtype)
        o_max = offset_abs_max(offsets[..., :n_off])
        w = params["w_deform"].astype(x.dtype).reshape(k * k, cin, cout)
        y = ops.deform_conv(x, offsets, w, kernel_size=k, stride=stride,
                            dilation=dilation, offset_bound=offset_bound,
                            dataflow=dataflow, cores=cores,
                            shard_batch=shard_batch,
                            shard_spatial=shard_spatial)
        return y + params["b_deform"].astype(x.dtype), o_max
    y, stats = dcl_forward(params, x, cfg)
    return y, stats["o_max"]


def _dcl_chain_layer(params: Mapping[str, Array], x, *, kernel_size: int,
                     stride: int, dilation: int,
                     offset_bound: float | None, use_kernel: bool,
                     quant_scales: Mapping[str, Any] | None, dtype: Any):
    """``quant="int8_chain"`` body of ``dcl_apply`` — one chained DCL.

    x may be a fp32 array (the chain head, quantized onto the table's
    ``x_scale``) or a ``QTensor`` handed over by the previous chained
    layer (consumed directly — no dequant/requant round-trip).  Returns
    ``(y, o_max)`` where y is a ``QTensor`` on the ``y_scale`` grid
    (kernel path with a calibrated ``y_scale``) or a fp32 array (the
    chain tail, or the differentiable STE reference path).
    """
    from repro.quant.qat import fake_quant_dcl_chain_reference
    from repro.quant.qtypes import QTensor

    if offset_bound is None:
        raise ValueError(
            "quant='int8_chain' requires a trained offset_bound — the "
            "fused offset-conv stage exists because Eq. 6 bounds the "
            "band (train with the Eq. 5 regularizer first)")
    scales = quant_scales or {}
    x_scale = scales.get("x_scale")
    if x_scale is None:
        raise ValueError(
            "quant='int8_chain' requires calibrated quant_scales with at "
            "least x_scale (repro.quant.calibrate_resnet_dcn records "
            "x/w/w_offset/y scales per DCL block): chained layers "
            "exchange int8 values on a pinned activation grid, so "
            "dynamic absmax would break the producer/consumer contract")
    w_scale = scales.get("w_scale")
    wo_scale = scales.get("w_offset_scale")
    y_scale = scales.get("y_scale")
    cin = x.shape[-1]
    cout = params["w_deform"].shape[-1]
    k = kernel_size
    w = params["w_deform"].astype(jnp.float32).reshape(k * k, cin, cout)
    w_off = params["w_offset"].astype(jnp.float32) \
        .reshape(k * k, cin, params["w_offset"].shape[-1])

    if use_kernel:
        from repro.kernels import ops
        if isinstance(x, QTensor):
            # A handed-over QTensor carries the grid it was emitted on;
            # decoding it with a different table scale would be silently
            # wrong.  The check needs a concrete value — under jit the
            # scale is a tracer and the static check_chain_compat guard
            # (dcl_chain_apply) is the line of defense instead.
            try:
                carried = float(x.scale)
            except (jax.errors.ConcretizationTypeError, TypeError):
                carried = None
            if carried is not None and not math.isclose(
                    carried, float(x_scale), rel_tol=1e-6):
                raise ValueError(
                    f"int8 input was emitted on scale {carried} but the "
                    f"layer's calibration table decodes x_scale="
                    f"{float(x_scale)} — the consumer's x_scale must BE "
                    f"the producer's y_scale (recalibrate the pair "
                    f"together)")
        xin = x.values if isinstance(x, QTensor) else x.astype(dtype)
        ws = None if w_scale is None else jnp.asarray(w_scale, jnp.float32)
        wos = None if wo_scale is None \
            else jnp.asarray(wo_scale, jnp.float32)
        emit = "int8" if y_scale is not None else "fp32"
        y = ops.deform_conv_chain(
            xin, w, w_off, params["b_offset"], params["b_deform"],
            kernel_size=k, stride=stride, dilation=dilation,
            offset_bound=offset_bound, x_scale=x_scale, w_scale=ws,
            w_offset_scale=wos, y_scale=y_scale, emit=emit)
        if emit == "int8":
            y = QTensor(values=y, scale=jnp.asarray(y_scale, jnp.float32))
        # The fused offsets never leave VMEM — there is no o_max to
        # observe on the inference datapath (training uses the STE
        # reference below, which returns the real statistic).
        return y, None

    xin = x.dequantize(dtype) if isinstance(x, QTensor) else x.astype(dtype)
    y, offsets = fake_quant_dcl_chain_reference(
        xin, w, w_off, params["b_offset"], params["b_deform"],
        kernel_size=k, stride=stride, dilation=dilation,
        offset_bound=offset_bound, x_scale=x_scale, w_scale=w_scale,
        w_offset_scale=wo_scale, y_scale=y_scale)
    from repro.core.deform_conv import offset_abs_max
    return y, offset_abs_max(offsets[..., :2 * k * k])


def check_chain_compat(scales_seq: Sequence[Mapping[str, Any]],
                       couts: Sequence[int] | None = None,
                       cins: Sequence[int] | None = None) -> None:
    """Validate that adjacent chained layers can actually hand each
    other int8 tensors — a clear ``ValueError`` naming the layer pair
    instead of a silent wrong-grid dequant (scales) or a deep kernel
    shape error (tiles/channels).

    Producer ``i`` emits on its ``y_scale`` grid; consumer ``i+1``
    decodes on its ``x_scale`` grid — they must be the same number.
    When channel extents are given, producer C_out must equal consumer
    C_in (the chained kernel stages the whole C extent per band).
    """
    for i in range(len(scales_seq) - 1):
        ys = scales_seq[i].get("y_scale")
        xs = scales_seq[i + 1].get("x_scale")
        if ys is None:
            raise ValueError(
                f"chained layer {i} has no y_scale: the int8 emission "
                f"grid must be calibrated (calibrate_resnet_dcn records "
                f"it from the DCL output observer) before layer {i + 1} "
                f"can consume the tensor")
        if xs is None or not math.isclose(float(ys), float(xs),
                                          rel_tol=1e-6):
            raise ValueError(
                f"adjacent chained layers disagree on the exchange "
                f"grid: layer {i} emits on y_scale={ys} but layer "
                f"{i + 1} decodes on x_scale={xs} — recalibrate the "
                f"pair together (the consumer's x_scale IS the "
                f"producer's y_scale)")
        if couts is not None and cins is not None \
                and couts[i] != cins[i + 1]:
            raise ValueError(
                f"chained layer {i} emits C_out={couts[i]} channels but "
                f"layer {i + 1} expects C_in={cins[i + 1]} — int8 "
                f"chaining hands the tensor over verbatim, so the "
                f"channel extents must match")


def dcl_chain_apply(params_seq: Sequence[Mapping[str, Array]], x: Array, *,
                    scales_seq: Sequence[Mapping[str, Any]],
                    kernel_size: int = 3, stride: int = 1,
                    dilation: int = 1, offset_bound: float | None = None,
                    use_kernel: bool = True,
                    dtype: Any = jnp.float32) -> tuple[Array, list]:
    """Run back-to-back DCLs chained int8 -> int8.

    Layer ``i`` emits a ``QTensor`` on its calibrated ``y_scale`` grid
    and layer ``i+1`` consumes it verbatim (its ``x_scale`` — validated
    equal by ``check_chain_compat``): between chained layers the
    activation touches HBM only as int8, the offsets never touch it at
    all, and the single fp32 boundary is the chain head (quantized
    once) and tail (the last layer's table has no ``y_scale``, or the
    caller dequantizes).  With ``use_kernel=False`` the differentiable
    STE chain reference runs layer by layer — the training path.

    Returns ``(y, o_maxes)``; o_maxes entries are None on the kernel
    path (the fused offsets never leave VMEM).
    """
    if len(params_seq) != len(scales_seq):
        raise ValueError(
            f"got {len(params_seq)} chained layers but "
            f"{len(scales_seq)} scale-table entries")
    check_chain_compat(
        scales_seq,
        couts=[p["w_deform"].shape[-1] for p in params_seq],
        cins=[p["w_deform"].shape[-2] for p in params_seq])
    o_maxes = []
    y = x
    for params, scales in zip(params_seq, scales_seq):
        y, o_max = dcl_apply(params, y, kernel_size=kernel_size,
                             stride=stride, dilation=dilation,
                             offset_bound=offset_bound,
                             use_kernel=use_kernel, quant="int8_chain",
                             quant_scales=scales, dtype=dtype)
        o_maxes.append(o_max)
    return y, o_maxes


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------

ACTS: dict[str, Callable[[Array], Array]] = {
    "gelu": jax.nn.gelu,
    "silu": jax.nn.silu,
    "relu": jax.nn.relu,
    "relu2": lambda x: jnp.square(jax.nn.relu(x)),
}


@dataclasses.dataclass(frozen=True)
class MLPConfig:
    d_model: int
    d_ff: int
    kind: str = "swiglu"          # swiglu | geglu | gelu | relu2
    bias: bool = False


def mlp_def(cfg: MLPConfig) -> dict[str, ParamDef]:
    d, f = cfg.d_model, cfg.d_ff
    defs = {"w_out": ParamDef((f, d), ("ff", "embed"))}
    if cfg.kind in ("swiglu", "geglu"):
        defs["w_gate"] = ParamDef((d, f), ("embed", "ff"))
        defs["w_up"] = ParamDef((d, f), ("embed", "ff"))
    else:
        defs["w_in"] = ParamDef((d, f), ("embed", "ff"))
    if cfg.bias:
        defs["b_in"] = ParamDef((f,), ("ff",), init="zeros")
        defs["b_out"] = ParamDef((d,), (None,), init="zeros")
    return defs


def mlp_apply(params, x: Array, cfg: MLPConfig) -> Array:
    if cfg.kind in ("swiglu", "geglu"):
        act = jax.nn.silu if cfg.kind == "swiglu" else jax.nn.gelu
        g = jnp.einsum("bsd,df->bsf", x, params["w_gate"].astype(x.dtype))
        u = jnp.einsum("bsd,df->bsf", x, params["w_up"].astype(x.dtype))
        if cfg.bias:
            g = g + params["b_in"].astype(x.dtype)
        h = act(g) * u
    else:
        act = ACTS["gelu" if cfg.kind == "gelu" else "relu2"]
        h = jnp.einsum("bsd,df->bsf", x, params["w_in"].astype(x.dtype))
        if cfg.bias:
            h = h + params["b_in"].astype(x.dtype)
        h = act(h)
    h = logical_constraint(h, "batch", "seq", "ff")
    y = jnp.einsum("bsf,fd->bsd", h, params["w_out"].astype(x.dtype))
    if cfg.bias:
        y = y + params["b_out"].astype(x.dtype)
    return logical_constraint(y, "batch", "seq", "embed_no_fsdp")


# ---------------------------------------------------------------------------
# Embedding / logits
# ---------------------------------------------------------------------------

def embed_def(vocab: int, d_model: int) -> dict[str, ParamDef]:
    # Sharded on vocab ONLY: FSDP-sharding the embed dim makes the token
    # gather un-partitionable (SPMD falls back to full replication of the
    # gathered activations — measured +4.3 GB/device temp at 1B scale).
    return {"embedding": ParamDef((vocab, d_model), ("vocab", None),
                                  init="embed", scale=0.02)}


def embed_apply(params, tokens: Array, dtype=jnp.bfloat16) -> Array:
    emb = params["embedding"].astype(dtype)
    out = jnp.take(emb, tokens, axis=0)
    return logical_constraint(out, "batch", "seq", "embed_no_fsdp")


def logits_apply(params, x: Array, *, softcap: float | None = None) -> Array:
    """Project to vocab with the (possibly tied) embedding matrix."""
    emb = params["embedding"].astype(x.dtype)
    logits = jnp.einsum("bsd,vd->bsv", x, emb,
                        preferred_element_type=jnp.float32)
    if softcap is not None:
        logits = jnp.tanh(logits / softcap) * softcap
    return logical_constraint(logits, "batch", "seq", "vocab")


def unembed_def(vocab: int, d_model: int) -> dict[str, ParamDef]:
    # vocab (output) dim sharded; contracting dim replicated so the logit
    # matmul partitions without a cross-'data' reduction.
    return {"unembedding": ParamDef((d_model, vocab), (None, "vocab"))}


def unembed_apply(params, x: Array, *, softcap: float | None = None) -> Array:
    w = params["unembedding"].astype(x.dtype)
    logits = jnp.einsum("bsd,dv->bsv", x, w,
                        preferred_element_type=jnp.float32)
    if softcap is not None:
        logits = jnp.tanh(logits / softcap) * softcap
    return logical_constraint(logits, "batch", "seq", "vocab")


def chunked_cross_entropy(x: Array, w: Array, targets: Array,
                          mask: Array | None = None, *, tied: bool,
                          logit_scale: float = 1.0,
                          softcap: float | None = None,
                          chunk: int = 1024) -> Array:
    """Mean CE without materializing (B, S, V) logits.

    Scans token chunks; each step computes a (B, chunk, V) logit block,
    reduces it to per-token NLL, and discards it (checkpointed, so the
    backward pass recomputes the block instead of saving it).  At
    command-r scale the full logits tensor is ~1 TB fp32 — this is the
    difference between compiling and not.

    x: (B, S, D) final hidden; w: embedding (V, D) if tied else (D, V).
    """
    b, s, d = x.shape
    pad = (-s) % chunk
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
        targets = jnp.pad(targets, ((0, 0), (0, pad)))
        mask = jnp.pad(mask, ((0, 0), (0, pad))) if mask is not None else \
            jnp.pad(jnp.ones((b, s), jnp.float32), ((0, 0), (0, pad)))
    elif mask is None:
        mask = jnp.ones((b, s), jnp.float32)
    nb = (s + pad) // chunk
    xc = jnp.moveaxis(x.reshape(b, nb, chunk, d), 1, 0)
    tc = jnp.moveaxis(targets.reshape(b, nb, chunk), 1, 0)
    mc = jnp.moveaxis(mask.reshape(b, nb, chunk), 1, 0)
    wt = w.astype(x.dtype)

    @jax.checkpoint
    def body(carry, inp):
        nll_sum, m_sum = carry
        xb, tb, mb = inp
        eq = "bsd,vd->bsv" if tied else "bsd,dv->bsv"
        logits = jnp.einsum(eq, xb, wt, preferred_element_type=jnp.float32)
        logits = logits * logit_scale
        if softcap is not None:
            logits = jnp.tanh(logits / softcap) * softcap
        logits = logical_constraint(logits, "batch", "seq", "vocab")
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(
            logits, tb[..., None].astype(jnp.int32), axis=-1)[..., 0]
        mb = mb.astype(jnp.float32)
        return (nll_sum + jnp.sum((lse - gold) * mb),
                m_sum + jnp.sum(mb)), None

    (nll, m), _ = jax.lax.scan(
        body, (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32)),
        (xc, tc, mc))
    return nll / jnp.maximum(m, 1.0)


def cross_entropy(logits: Array, targets: Array,
                  mask: Array | None = None) -> Array:
    """Mean CE over (possibly masked) targets; logits fp32."""
    logits = logits.astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, targets[..., None].astype(jnp.int32),
                               axis=-1)[..., 0]
    nll = lse - gold
    if mask is None:
        return jnp.mean(nll)
    mask = mask.astype(jnp.float32)
    return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
