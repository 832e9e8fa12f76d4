"""Flash-attention Pallas kernel: blockwise online-softmax on the MXU.

The §Roofline memory term of every dense-attention cell is dominated by
the (Sq, Sk) score tensor round-tripping HBM (pre-fusion accounting; on
TPU, XLA fuses part of the chain but still materializes scores at long
S).  This kernel is the structural fix — the same insight as the paper's
bounded-RF dataflow, applied to attention: *hold a (block_q, block_k)
score tile in VMEM, never writing scores to HBM at all*, carrying the
online-softmax statistics (running max m, normalizer l, accumulator acc)
in VMEM scratch across the K-block grid axis.

Layout: head-major (BH, S, Dh) so every block is a clean 2-D MXU tile.
Causal masking is positional (absolute indices from the block ids);
fully-masked K-blocks are skipped with ``pl.when`` — the causal schedule
does half the work of the rectangular one.

Validated against ``ref.flash_attention_ref`` over shape/dtype sweeps in
``tests/test_flash_attention.py`` (interpret mode on CPU; the TPU is the
lowering target).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


Array = jax.Array

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  scale: float, block_q: int, block_k: int, k_steps: int,
                  causal: bool, softcap: float | None, sk_valid: int):
    qb = pl.program_id(1)
    kb = pl.program_id(2)

    @pl.when(kb == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def compute():
        q = q_ref[0].astype(jnp.float32)                 # (bq, dh)
        k = k_ref[0].astype(jnp.float32)                 # (bk, dh)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # (bq, bk)
        if softcap is not None:
            s = jnp.tanh(s / softcap) * softcap
        k_idx = kb * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        keep = k_idx < sk_valid                          # tail padding
        if causal:
            q_idx = qb * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            keep &= k_idx <= q_idx
        s = jnp.where(keep, s, NEG_INF)

        m_prev = m_ref[...]
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_cur[:, None])
        corr = jnp.exp(m_prev - m_cur)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1)
        v = v_ref[0].astype(jnp.float32)
        acc_ref[...] = acc_ref[...] * corr[:, None] + jax.lax.dot(
            p, v, preferred_element_type=jnp.float32)
        m_ref[...] = m_cur

    if causal:
        # K-blocks entirely above the diagonal contribute nothing.
        pl.when(kb * block_k <= qb * block_q + block_q - 1)(compute)
    else:
        compute()

    @pl.when(kb == k_steps - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "softcap", "block_q", "block_k", "interpret"))
def flash_attention_bh(q: Array, k: Array, v: Array, *, causal: bool = True,
                       softcap: float | None = None, block_q: int = 128,
                       block_k: int = 128,
                       interpret: bool = True) -> Array:
    """Head-major flash attention.

    q: (BH, Sq, Dh); k, v: (BH, Sk, Dh).  Returns (BH, Sq, Dh).
    Shapes are padded to the block grid internally.
    """
    bh, sq, dh = q.shape
    sk = k.shape[1]
    bq = min(block_q, sq)
    bk = min(block_k, sk)
    pq, pk = (-sq) % bq, (-sk) % bk
    if pq:
        q = jnp.pad(q, ((0, 0), (0, pq), (0, 0)))
    if pk:
        k = jnp.pad(k, ((0, 0), (0, pk), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pk), (0, 0)))
    k_steps = (sk + pk) // bk

    out = pl.pallas_call(
        functools.partial(
            _flash_kernel, scale=1.0 / math.sqrt(dh), block_q=bq,
            block_k=bk, k_steps=k_steps, causal=causal, softcap=softcap,
            sk_valid=sk),
        grid=(bh, (sq + pq) // bq, k_steps),
        in_specs=[
            pl.BlockSpec((1, bq, dh), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, dh), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bk, dh), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, dh), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq + pq, dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq, dh), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v)
    return out[:, :sq]


def flash_attention(q: Array, k: Array, v: Array, *, causal: bool = True,
                    softcap: float | None = None, block_q: int = 128,
                    block_k: int = 128, interpret: bool | None = None) -> Array:
    """GQA layout wrapper.

    q: (B, Sq, KV, G, Dh); k, v: (B, Sk, KV, Dh) — the layout used by
    ``repro.models.layers``.  KV heads are broadcast across the group.
    Returns (B, Sq, KV, G, Dh).
    """
    if interpret is None:
        from .ops import default_interpret
        interpret = default_interpret()
    b, sq, kv, g, dh = q.shape
    sk = k.shape[1]
    qh = q.transpose(0, 2, 3, 1, 4).reshape(b * kv * g, sq, dh)
    kh = jnp.broadcast_to(k[:, :, :, None], (b, sk, kv, g, dh)) \
        .transpose(0, 2, 3, 1, 4).reshape(b * kv * g, sk, dh)
    vh = jnp.broadcast_to(v[:, :, :, None], (b, sk, kv, g, dh)) \
        .transpose(0, 2, 3, 1, 4).reshape(b * kv * g, sk, dh)
    oh = flash_attention_bh(qh, kh, vh, causal=causal, softcap=softcap,
                            block_q=block_q, block_k=block_k,
                            interpret=interpret)
    return oh.reshape(b, kv, g, sq, dh).transpose(0, 3, 1, 2, 4)
