"""Tiled MXU matmul — the TPU analogue of the paper's systolic array.

The paper's computation engine (Fig. 4/5) is a 2-D systolic array fed by
double-buffered on-chip tiles.  On TPU the MXU *is* the systolic array;
this kernel supplies the tiling/dataflow around it: (bm, bk) x (bk, bn)
VMEM blocks, fp32 accumulation in a VMEM scratch register across the
contraction grid axis, result written once on the last K step.

Used by the offset-generating convolution (as im2col matmul) and as the
generic building block everywhere a plain matmul is the hot spot.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


Array = jax.Array


def _matmul_kernel(x_ref, w_ref, o_ref, acc_ref, *, k_steps: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(x_ref[...], w_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(2) == k_steps - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("block_m", "block_n", "block_k", "interpret"))
def matmul(x: Array, w: Array, *, block_m: int = 256, block_n: int = 256,
           block_k: int = 256, interpret: bool = True) -> Array:
    """``x @ w`` with explicit VMEM tiling and fp32 accumulation.

    x: (M, K), w: (K, N) -> (M, N) in x.dtype.  Shapes are padded to the
    block grid and un-padded on return, so arbitrary sizes work.
    """
    m, k = x.shape
    k2, n = w.shape
    assert k == k2, (x.shape, w.shape)

    bm, bn, bk = min(block_m, m), min(block_n, n), min(block_k, k)
    # MXU alignment: sublane multiples of 8, lane multiples of 128 where
    # the operand allows it (small operands keep their natural size).
    pad_m, pad_n, pad_k = (-m) % bm, (-n) % bn, (-k) % bk
    xp = jnp.pad(x, ((0, pad_m), (0, pad_k))) if pad_m or pad_k else x
    wp = jnp.pad(w, ((0, pad_k), (0, pad_n))) if pad_k or pad_n else w
    mp, kp = xp.shape
    np_ = wp.shape[1]
    k_steps = kp // bk

    out = pl.pallas_call(
        functools.partial(_matmul_kernel, k_steps=k_steps),
        grid=(mp // bm, np_ // bn, k_steps),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), x.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(xp, wp)
    return out[:m, :n]
