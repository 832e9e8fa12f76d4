"""Loop-tiling buffer model (paper Eq. 6/7) and roofline-driven tile chooser.

The paper sizes its on-chip buffers for the deformable convolutional
layer (DCL) as

    Input buffer size  = RF * (S*T_W + RF - S) * T_N          (Eq. 6)
    Output buffer size = T_W * T_N * 2 * K_C^2                (Eq. 7)

where ``RF = K_C + 2*ceil(B)`` is the (bounded) receptive field, ``S``
the stride, ``T_W``/``T_N`` the tile width / input-channel tile, and the
output buffer holds offsets + interpolated inputs (the factor ``2*K^2``:
2 offset planes and the K^2-tap patch tensor share it double-buffered).

On TPU the same algebra sizes the **VMEM** working set of the Pallas
kernels in ``repro.kernels``: the input tile + halo must fit VMEM next
to the weight tile and the output accumulator.  ``choose_tiles`` solves
the paper's roofline-based tiling (Sec. 3.2, following Zhang FPGA'15)
against TPU constants instead of FPGA BRAM:

    attainable = min(peak_flops, CTC * hbm_bandwidth)

maximising compute-to-communication ratio (CTC) subject to the Eq. 6/7
VMEM bound and MXU alignment (8 sublanes x 128 lanes).
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Iterable

# ---------------------------------------------------------------------------
# Hardware constants (TPU v5e target; the container only dry-runs these).
# ---------------------------------------------------------------------------

V5E_VMEM_BYTES = 128 * 1024 * 1024        # 128 MiB VMEM per core
# Scoped VMEM limit of every bounded DCL kernel (``vmem_limit_bytes`` of
# its ``pallas_call``) and the default budget of the tile choosers below:
# one number, so a tile the chooser accepts is one Mosaic accepts.  The
# 32 MiB left of the physical VMEM is Mosaic's own headroom (internal
# scratch, spilled row values of the sampler).
VMEM_LIMIT_BYTES = 96 * 1024 * 1024
V5E_PEAK_FLOPS_BF16 = 197e12              # 197 TFLOP/s bf16
V5E_HBM_BW = 819e9                        # 819 GB/s
V5E_ICI_BW = 50e9                         # ~50 GB/s per link
MXU_LANE = 128                            # lane (minor) alignment
MXU_SUBLANE = 8                           # sublane alignment (fp32)

# Datapath element widths understood by the dtype-aware budgets below.
# ``int8`` is the quantized DCL datapath (``repro.quant``): every VMEM
# byte holds 4x more of the Eq. 6 band than fp32, so the same budget
# admits wider tiles — the paper's fixed-point argument on TPU.
DTYPE_BYTES = {"int8": 1, "bf16": 2, "fp32": 4}


def dtype_bytes(dtype) -> int:
    """Bytes per element of a datapath dtype: accepts the string names
    of ``DTYPE_BYTES`` or anything ``jnp.dtype`` understands."""
    if dtype is None:
        raise ValueError("dtype is None; pass 'int8' | 'bf16' | 'fp32'")
    if isinstance(dtype, str) and dtype in DTYPE_BYTES:
        return DTYPE_BYTES[dtype]
    import numpy as np
    return int(np.dtype(dtype).itemsize)


# ---------------------------------------------------------------------------
# Paper buffer algebra
# ---------------------------------------------------------------------------

def receptive_field(kernel_size: int, offset_bound: float) -> int:
    """Eq. 4 (duplicated here so tiling is importable standalone)."""
    return int(kernel_size + 2 * math.ceil(float(offset_bound)))


def input_buffer_size(rf: int, stride: int, t_w: int, t_n: int,
                      *, bytes_per_elem: int = 4) -> int:
    """Eq. 6: bytes of input tile (+halo) needed for stall-free sampling."""
    return rf * (stride * t_w + rf - stride) * t_n * bytes_per_elem


def output_buffer_size(t_w: int, t_n: int, kernel_size: int,
                       *, bytes_per_elem: int = 4) -> int:
    """Eq. 7: bytes of output buffer (offsets + interpolated inputs)."""
    return t_w * t_n * 2 * kernel_size * kernel_size * bytes_per_elem


def weight_buffer_size(kernel_size: int, t_n: int, t_m: int,
                       *, bytes_per_elem: int = 4) -> int:
    """Weight tile for the dynamic-convolution stage (not in Eq. 6/7 —
    the paper holds all weights of the tile on chip; we account for it
    explicitly because VMEM is shared)."""
    return kernel_size * kernel_size * t_n * t_m * bytes_per_elem


@dataclasses.dataclass(frozen=True)
class TileConfig:
    """One loop-tiling point (the paper fixes T_N=512, T_M=64, T_H=1, T_W=8)."""
    t_h: int
    t_w: int
    t_n: int   # input-channel tile
    t_m: int   # output-channel tile

    def vmem_bytes(self, rf: int, stride: int, kernel_size: int,
                   *, bytes_per_elem: int = 4) -> int:
        band_h = rf + stride * (self.t_h - 1)              # Eq. 6 row extent
        inp = band_h * (stride * self.t_w + rf - stride) * self.t_n \
            * bytes_per_elem
        out = output_buffer_size(self.t_w * self.t_h, self.t_n, kernel_size,
                                 bytes_per_elem=bytes_per_elem)
        wgt = weight_buffer_size(kernel_size, self.t_n, self.t_m,
                                 bytes_per_elem=bytes_per_elem)
        acc = self.t_h * self.t_w * self.t_m * 4           # fp32 accumulator
        return inp + out + wgt + acc


PAPER_TILES = TileConfig(t_h=1, t_w=8, t_n=512, t_m=64)


# ---------------------------------------------------------------------------
# Roofline-driven tile chooser (Sec. 3.2 methodology on TPU constants)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class LayerShape:
    """Shape of one DCL invocation used to evaluate a tiling point."""
    h: int
    w: int
    c_in: int
    c_out: int
    kernel_size: int = 3
    stride: int = 1
    offset_bound: float = 2.0

    @property
    def rf(self) -> int:
        return receptive_field(self.kernel_size, self.offset_bound)


def _align(v: int, a: int) -> int:
    return max(a, (v // a) * a)


def tile_candidates(shape: LayerShape) -> Iterable[TileConfig]:
    """Enumerate MXU-aligned tile points that divide the layer cleanly
    enough (we allow ragged edges; alignment matters more than divisibility)."""
    for t_h in (1, 2, 4, 8):
        for t_w in (8, 16, 32, 64):
            for t_n in (128, 256, 512):
                for t_m in (64, 128, 256):
                    if t_n > shape.c_in * 2 or t_m > shape.c_out * 2:
                        continue
                    yield TileConfig(t_h, t_w, min(t_n, _align(shape.c_in, MXU_LANE)),
                                     min(t_m, _align(shape.c_out, MXU_SUBLANE)))


def tile_flops(shape: LayerShape, t: TileConfig) -> int:
    """MACs*2 of one tile of the dynamic-convolution stage + bilinear stage."""
    k2 = shape.kernel_size ** 2
    conv = 2 * t.t_h * t.t_w * t.t_m * k2 * t.t_n
    bilinear = t.t_h * t.t_w * k2 * t.t_n * 8      # 4 corners * (mul+add)
    return conv + bilinear


def tile_hbm_bytes(shape: LayerShape, t: TileConfig,
                   *, bytes_per_elem: int = 2) -> int:
    """HBM traffic per tile: input band (+halo), weight tile, output tile.

    In the fused kernel the interpolated patches never travel to HBM —
    this is the beyond-paper saving; ``two_stage_extra_bytes`` accounts
    for the paper-faithful dataflow that round-trips them.
    """
    rf, s = shape.rf, shape.stride
    band_h = rf + s * (t.t_h - 1)
    inp = band_h * (s * t.t_w + rf - s) * t.t_n * bytes_per_elem
    wgt = shape.kernel_size ** 2 * t.t_n * t.t_m * bytes_per_elem
    out = t.t_h * t.t_w * t.t_m * bytes_per_elem
    return inp + wgt + out


def two_stage_extra_bytes(shape: LayerShape, t: TileConfig,
                          *, bytes_per_elem: int = 2) -> int:
    """Patches written + re-read by the paper's two-stage dataflow."""
    k2 = shape.kernel_size ** 2
    return 2 * t.t_h * t.t_w * k2 * t.t_n * bytes_per_elem


@dataclasses.dataclass(frozen=True)
class TileChoice:
    tile: TileConfig
    ctc: float                 # compute-to-communication ratio (flops/byte)
    attainable_flops: float    # roofline-attainable performance
    vmem_bytes: int

    @property
    def fits(self) -> bool:
        return self.vmem_bytes <= VMEM_LIMIT_BYTES


def evaluate_tile(shape: LayerShape, t: TileConfig, *, fused: bool = True,
                  vmem_budget: int = VMEM_LIMIT_BYTES) -> TileChoice:
    flops = tile_flops(shape, t)
    traffic = tile_hbm_bytes(shape, t)
    if not fused:
        traffic += two_stage_extra_bytes(shape, t)
    ctc = flops / max(traffic, 1)
    attainable = min(V5E_PEAK_FLOPS_BF16, ctc * V5E_HBM_BW)
    vmem = t.vmem_bytes(shape.rf, shape.stride, shape.kernel_size,
                        bytes_per_elem=2)
    return TileChoice(tile=t, ctc=ctc, attainable_flops=attainable,
                      vmem_bytes=vmem)


def choose_tiles(shape: LayerShape, *, fused: bool = True,
                 vmem_budget: int = VMEM_LIMIT_BYTES) -> TileChoice:
    """Pick the tiling point with the highest roofline-attainable perf
    among those whose Eq. 6/7 working set fits VMEM (paper Sec. 3.2)."""
    best: TileChoice | None = None
    for t in tile_candidates(shape):
        c = evaluate_tile(shape, t, fused=fused, vmem_budget=vmem_budget)
        if c.vmem_bytes > vmem_budget:
            continue
        if best is None or (c.attainable_flops, c.ctc) > (best.attainable_flops, best.ctc):
            best = c
    if best is None:
        raise ValueError(
            f"no tile configuration fits VMEM budget {vmem_budget} for {shape}; "
            f"receptive field {shape.rf} too large — train with a larger lambda")
    return best


# ---------------------------------------------------------------------------
# Dataflow-level HBM traffic (zero-copy vs materialized-band) and the
# kernel tile chooser used by ``repro.kernels.ops``.
# ---------------------------------------------------------------------------

def band_extent(tile: int, *, kernel_size: int, stride: int,
                dilation: int = 1, offset_bound: float) -> int:
    """Eq. 6 band extent along one axis for an output tile of ``tile``
    positions, matching ``kernels.deform_sample.band_geometry`` exactly
    (the +2 covers the bilinear x0+1 corner on each side)."""
    hb = int(math.ceil(float(offset_bound)))
    return (tile - 1) * stride + (kernel_size - 1) * dilation + 2 * hb + 2


def staged_width(band_w: int) -> int:
    """Columns a forward kernel DMAs per Eq. 6 band: ``band_w`` rounded
    up to whole sublane tiles — Mosaic refuses int8 band copies of a
    ragged width.  The extra columns are zero padding the sampler never
    weights."""
    return -(-band_w // MXU_SUBLANE) * MXU_SUBLANE


def out_hw(h: int, w: int, *, kernel_size: int, stride: int,
           dilation: int = 1) -> tuple[int, int]:
    """'Same'-padded output spatial dims of one DCL invocation."""
    pad = dilation * (kernel_size // 2)
    ho = (h + 2 * pad - dilation * (kernel_size - 1) - 1) // stride + 1
    wo = (w + 2 * pad - dilation * (kernel_size - 1) - 1) // stride + 1
    return ho, wo


def dcl_dataflow_hbm_bytes(shape: LayerShape, t: TileConfig, *,
                           dataflow: str = "zero_copy", batch: int = 1,
                           dilation: int = 1,
                           bytes_per_elem: int = 4) -> int:
    """Input-dataflow HBM bytes for one whole DCL layer.

    ``zero_copy``: the padded input stays in HBM; the kernel DMAs one
    (band_h, staged_width(band_w)) window per (row-tile, width-tile,
    M-tile, C-chunk) grid step — halo rows are re-read at tile boundaries, nothing is
    duplicated.

    ``materialized_band``: the legacy XLA path reads the padded input
    once, *writes* every overlapping full-width row band back to HBM
    (a band_h/(tile_h*stride) duplication of the input), and the kernel
    re-reads those full-width bands per (M-tile, C-chunk) pass.
    """
    k, s, b = shape.kernel_size, shape.stride, shape.offset_bound
    c, m = shape.c_in, shape.c_out
    ho, wo = out_hw(shape.h, shape.w, kernel_size=k, stride=s,
                    dilation=dilation)
    h_tiles = -(-ho // t.t_h)
    w_tiles = -(-wo // t.t_w)
    m_passes = -(-m // t.t_m)
    band_h = band_extent(t.t_h, kernel_size=k, stride=s, dilation=dilation,
                         offset_bound=b)
    hb = int(math.ceil(float(b)))
    pad = dilation * (k // 2)
    # Padded full-width extent (what the legacy path stages per band).
    w_full = wo * s + band_extent(1, kernel_size=k, stride=s,
                                  dilation=dilation, offset_bound=b) - s
    if dataflow == "zero_copy":
        band_w = staged_width(band_extent(t.t_w, kernel_size=k, stride=s,
                                          dilation=dilation, offset_bound=b))
        reads = h_tiles * w_tiles * m_passes * band_h * band_w * c
        return batch * reads * bytes_per_elem
    if dataflow == "materialized_band":
        hp = shape.h + 2 * (pad + hb) + 1
        x_read = hp * w_full * c                      # the jnp.take source
        band_elems = h_tiles * band_h * w_full * c    # duplicated bands
        kernel_reads = band_elems * m_passes          # per M-tile pass
        return batch * (x_read + band_elems + kernel_reads) * bytes_per_elem
    raise ValueError(f"unknown dataflow {dataflow!r}")


def dcl_total_hbm_bytes(shape: LayerShape, t: TileConfig, *,
                        dataflow: str = "zero_copy", batch: int = 1,
                        dilation: int = 1, bytes_per_elem: int = 4,
                        offset_bytes_per_elem: int | None = None,
                        out_bytes_per_elem: int | None = None,
                        fused_offsets: bool = False) -> int:
    """Whole-layer HBM traffic: input dataflow + offsets + weights + out.

    Weight blocks are re-fetched per (row-tile, width-tile) because the
    C/M grid axes cycle inside each spatial tile (same for both
    dataflows); offsets and output travel once.

    ``offset_bytes_per_elem`` / ``out_bytes_per_elem`` override the
    element width of the offset planes and the output tensor — the int8
    datapath keeps both at fp32 (address generation is full precision
    and the fused dequant epilogue emits fp32) while the input band and
    weight blocks travel at 1 byte/elem.

    ``fused_offsets`` models the chained datapath's in-kernel offset
    stage (``band_pipeline.offset_conv_stage``): the offsets never
    exist in HBM (the term drops entirely) and the offset-conv weight
    blocks are re-fetched per spatial tile alongside the deform blocks
    instead.
    """
    k2 = shape.kernel_size ** 2
    off_b = offset_bytes_per_elem or bytes_per_elem
    out_b = out_bytes_per_elem or bytes_per_elem
    ho, wo = out_hw(shape.h, shape.w, kernel_size=shape.kernel_size,
                    stride=shape.stride, dilation=dilation)
    h_tiles = -(-ho // t.t_h)
    w_tiles = 1 if dataflow == "materialized_band" else -(-wo // t.t_w)
    inp = dcl_dataflow_hbm_bytes(shape, t, dataflow=dataflow, batch=batch,
                                 dilation=dilation,
                                 bytes_per_elem=bytes_per_elem)
    if fused_offsets:
        offs = batch * h_tiles * w_tiles * k2 * shape.c_in * 2 * k2 \
            * bytes_per_elem
    else:
        offs = batch * ho * wo * 2 * k2 * off_b
    wgt = batch * h_tiles * w_tiles * k2 * shape.c_in * shape.c_out \
        * bytes_per_elem
    out = batch * ho * wo * shape.c_out * out_b
    return inp + offs + wgt + out


def dcl_chain_hbm_bytes(shape: LayerShape, t: TileConfig, *,
                        layers: int = 2, batch: int = 1,
                        dilation: int = 1,
                        chained: bool = True) -> int:
    """Whole-stack HBM bytes of ``layers`` back-to-back int8 DCLs —
    the chained-layer accounting behind the ``quant="int8_chain"``
    datapath (requires ``shape.c_in == shape.c_out``: chaining hands
    the tensor over verbatim).

    ``chained=False`` models the per-layer int8 datapath, charging each
    layer everything it actually costs end to end:

    * the XLA offset pass reads the fp32 input plane and writes the
      fp32 offsets, which the kernel then re-reads;
    * the quantize pass reads the fp32 plane again and writes the int8
      plane the band DMA consumes;
    * the kernel streams int8 bands + weight blocks and emits the
      output fp32 — which is exactly the fp32 plane the NEXT layer's
      offset/quantize passes re-read (no double counting: each layer
      owns its own input prep).

    ``chained=True`` models the fused datapath: the input arrives int8
    (the head is quantized once — charged to the first layer), the
    offset conv runs in-kernel over the already-staged band (its only
    extra HBM traffic is the int8 offset-weight blocks, re-fetched per
    spatial tile like the deform blocks; the offsets themselves never
    exist in HBM), and each inter-layer tensor is emitted int8 on the
    next layer's grid — crossing HBM once, at 1 byte/elem.  The chain
    TAIL is priced honestly at fp32 (the last layer has no ``y_scale``
    and emits through the dequant epilogue — exactly the ``emit="fp32"``
    configuration the benchmarks time), so both sides of the ratio end
    in the same fp32 tensor.

    The modeled chained/per-layer ratio is gated >= 1.3x in
    ``tests/test_chain.py`` and ``benchmarks/run.py`` (the PR
    acceptance number reported by ``perf_model.dataflow_traffic_report``
    ``chain_*`` keys).
    """
    if shape.c_in != shape.c_out:
        raise ValueError(
            f"chained layers hand the tensor over verbatim, so C_in "
            f"must equal C_out (got {shape.c_in} != {shape.c_out})")
    k2 = shape.kernel_size ** 2
    c, m = shape.c_in, shape.c_out
    ho, wo = out_hw(shape.h, shape.w, kernel_size=shape.kernel_size,
                    stride=shape.stride, dilation=dilation)
    h_tiles = -(-ho // t.t_h)
    w_tiles = -(-wo // t.t_w)
    plane = shape.h * shape.w * c                  # input plane elems
    off_elems = ho * wo * 2 * k2
    band_q = dcl_dataflow_hbm_bytes(shape, t, dataflow="zero_copy",
                                    batch=batch, dilation=dilation,
                                    bytes_per_elem=1)
    wgt_q = batch * h_tiles * w_tiles * k2 * c * m            # int8 blocks
    if not chained:
        per_layer = (
            band_q
            + batch * (plane * 4                  # offset pass: fp32 read
                       + off_elems * 4            # offsets written fp32
                       + off_elems * 4            # ... re-read by kernel
                       + plane * 4 + plane        # quantize: read + write
                       + ho * wo * m * 4)         # fp32 output emission
            + wgt_q)
        return layers * per_layer
    woff_q = batch * h_tiles * w_tiles * k2 * c * 2 * k2      # int8 blocks
    head_quant = batch * (plane * 4 + plane)      # quantize once, layer 0
    per_layer = band_q + wgt_q + woff_q + batch * ho * wo * m  # int8 out
    tail_fp32 = batch * ho * wo * m * 3           # last emission 4B, not 1B
    return layers * per_layer + head_quant + tail_fp32


def spatial_halo_rows(*, kernel_size: int, dilation: int = 1,
                      offset_bound: float) -> int:
    """Input rows each height-shard neighbor must contribute for the
    spatially sharded bounded DCL (``distributed.spatial``).

    An output row ``t`` of the bounded kernel samples original input
    rows in ``[t*s - (pad + hb), t*s + pad + hb + 1]`` where
    ``pad = dilation*(K//2)`` is the 'same' conv padding, ``hb =
    ceil(B)`` the Eq. 5 offset bound, and the ``+1`` the bilinear
    ``x0+1`` corner.  The symmetric halo that covers both directions is

        halo = dilation*(K//2) + ceil(B) + 1

    which for ``dilation=1`` and odd ``K`` is exactly the paper-derived
    ``ceil(B) + ceil(K/2)`` bound — the same Eq. 6 locality argument
    that sizes the on-chip band, applied across devices.  This is the
    single source of the halo algebra: ``distributed.spatial`` and the
    traffic model below both delegate here so the exchange and its
    model can never disagree.
    """
    if kernel_size < 1 or dilation < 1:
        raise ValueError(f"kernel_size={kernel_size}/dilation={dilation} "
                         f"must be >= 1")
    return dilation * (kernel_size // 2) \
        + int(math.ceil(float(offset_bound))) + 1


def spatial_halo_bytes(shape: LayerShape, *, shards: int,
                       dilation: int = 1, bytes_per_elem: int = 4) -> int:
    """Per-device halo-exchange bytes of one height-sharded DCL layer:
    ``2 * halo_rows * W * C`` (one up + one down ``lax.ppermute`` per
    layer; edge shards receive zeros for free).  Zero at 1 shard —
    there is no exchange to pay."""
    if shards < 1:
        raise ValueError(f"shards={shards} must be >= 1")
    if shards == 1:
        return 0
    halo = spatial_halo_rows(kernel_size=shape.kernel_size,
                             dilation=dilation,
                             offset_bound=shape.offset_bound)
    return 2 * halo * shape.w * shape.c_in * bytes_per_elem


def dcl_spatial_hbm_bytes(shape: LayerShape, t: TileConfig, *,
                          shards: int, dataflow: str = "zero_copy",
                          batch: int = 1, dilation: int = 1,
                          bytes_per_elem: int = 4) -> int:
    """Per-device whole-layer traffic of the height-sharded bounded DCL:
    the per-shard layer traffic (the shard's ``H/shards`` rows through
    ``dcl_total_hbm_bytes``) plus the ``2*halo_rows*W*C`` halo-exchange
    bytes of the one up/down ``ppermute`` pair.  ``shape`` is the
    GLOBAL layer shape; divisibility follows the runtime's
    ``check_height_split`` contract (``H % (stride*shards) == 0``)."""
    if shards < 1:
        raise ValueError(f"shards={shards} must be >= 1")
    if shape.h % (shape.stride * shards) != 0:
        raise ValueError(
            f"shards={shards} does not evenly divide H={shape.h} at "
            f"stride={shape.stride}; the spatial shard_map needs equal "
            f"per-device row blocks (H % (stride*shards) == 0)")
    local = dataclasses.replace(shape, h=shape.h // shards)
    return (dcl_total_hbm_bytes(local, t, dataflow=dataflow, batch=batch,
                                dilation=dilation,
                                bytes_per_elem=bytes_per_elem)
            + spatial_halo_bytes(shape, shards=shards, dilation=dilation,
                                 bytes_per_elem=bytes_per_elem))


def dcl_backward_hbm_bytes(shape: LayerShape, t: TileConfig, *,
                           dataflow: str = "zero_copy", batch: int = 1,
                           dilation: int = 1,
                           bytes_per_elem: int = 4,
                           cores: int = 1,
                           per_core: bool = False) -> int:
    """HBM bytes of one whole-layer DCL *backward* pass.

    ``cores`` models the Megacore batch split of
    ``kernels.deform_conv_bwd`` (zero-copy only): every batch-indexed
    term — the band recompute read, the d_input read-modify-write, the
    cotangent/weight fetches, the d_offsets writes — is owned by
    exactly one core, so the *per-core* traffic (``per_core=True``) is
    those "dw-stationary" terms divided by ``cores`` plus one full
    partial-``d_weights`` flush.  The default total view charges every
    core's partial flush plus the reduce epilogue (read ``cores``
    partials, write one reduced block); the batch-indexed terms are
    unchanged in aggregate — cores split *work*, they don't shrink it.

    ``zero_copy`` models ``kernels.deform_conv_bwd``: per (row-tile,
    width-tile, C-chunk) grid step the kernel re-reads one Eq. 6
    (band_h, band_w) input band chunk (cheap recompute of the sampled
    patches — no patch residual is ever saved), reads the cotangent tile
    and weight block, read-modify-writes the same-shaped ``d_input``
    band region, and flushes the fp32 ``d_weights`` accumulator block.
    The output-channel axis is untiled in backward, so input bands are
    NOT re-read per M-pass (unlike forward).

    ``materialized_band`` models the pre-PR training path: XLA
    differentiates a two-stage (sample -> einsum) reference, which
    (a) materializes overlapping full-width row bands in HBM for the
    recompute, (b) materializes an equal-sized banded scatter buffer
    for ``d_input`` that is written, re-read, and reduced into the
    input gradient, and (c) — the dominant term — round-trips the
    (N, Ho, Wo, K^2, C) patch tensor through HBM: the einsum VJP reads
    the saved patch residual and writes+reads ``d_patches`` before the
    sampling VJP can consume it.  The fused backward kernel contracts
    both in VMEM; neither tensor ever exists in HBM.  Each dataflow is
    charged its own cotangent/weight cadence (the fused kernel
    re-fetches per tile and over-flushes d_weights; the XLA baseline
    streams those once) so the ratio reflects the dataflows, not a
    shared-term artifact.
    """
    k, s, b = shape.kernel_size, shape.stride, shape.offset_bound
    k2 = k * k
    c, m = shape.c_in, shape.c_out
    ho, wo = out_hw(shape.h, shape.w, kernel_size=k, stride=s,
                    dilation=dilation)
    h_tiles = -(-ho // t.t_h)
    w_tiles = -(-wo // t.t_w)
    c_steps = -(-c // t.t_n)
    band_h = band_extent(t.t_h, kernel_size=k, stride=s, dilation=dilation,
                         offset_bound=b)
    hb = int(math.ceil(float(b)))
    pad = dilation * (k // 2)
    w_full = wo * s + band_extent(1, kernel_size=k, stride=s,
                                  dilation=dilation, offset_bound=b) - s

    doff_writes = ho * wo * 2 * k2
    if cores < 1:
        raise ValueError(f"cores={cores} must be >= 1")
    if dataflow != "zero_copy" and (cores != 1 or per_core):
        raise ValueError(
            f"cores={cores}/per_core={per_core} model the Megacore "
            f"split of the zero-copy backward kernel only (got "
            f"dataflow={dataflow!r})")

    if dataflow == "zero_copy":
        # Per-grid-step costs of the fused backward kernel: the
        # cotangent tile is fetched once per spatial tile (its
        # BlockSpec index is constant in the C-chunk axis), the weight
        # block per C-chunk step, and the fp32 d_weights accumulator
        # flushes once per C-chunk block on the LAST spatial grid step
        # (the kernel keeps the every-step flush only under interpret
        # mode; see ``deform_conv_bwd._bwd_zerocopy_kernel``) — the
        # h_tiles*w_tiles*batch over-flush factor of the PR-2 cadence
        # is gone: the last-step condition includes the batch grid
        # axis, so dw_writes is a whole-layer constant, NOT per-batch.
        g_reads = ho * wo * m
        w_reads = h_tiles * w_tiles * k2 * c * m
        dw_writes = k2 * c * m
        band_w = band_extent(t.t_w, kernel_size=k, stride=s,
                             dilation=dilation, offset_bound=b)
        band_elems = h_tiles * w_tiles * band_h * band_w * c
        inp = band_elems          # recompute read
        dx_rmw = 2 * band_elems   # d_input band read + write per step
        batch_terms = inp + dx_rmw + g_reads + w_reads + doff_writes
        if per_core:
            # One core's share: its batch shard's terms + its own full
            # partial-d_weights flush (the dw-stationary terms drop
            # exactly cores x; dw does not — each core carries a whole
            # partial).
            return (-(-batch // cores) * batch_terms
                    + dw_writes) * bytes_per_elem
        if cores > 1:
            # Aggregate: per-core partial flushes + the sum epilogue
            # (read cores partials, write the reduced block).
            dw_total = cores * dw_writes + (cores + 1) * dw_writes
            return (batch * batch_terms + dw_total) * bytes_per_elem
        return (batch * batch_terms + dw_writes) * bytes_per_elem
    if dataflow == "materialized_band":
        # XLA autodiff of the two-stage reference is NOT spatially
        # tiled: it reads g twice (d_weights and d_patches einsums),
        # reads w and writes dw once *per layer* (the einsum VJP
        # contracts the batch axis) — charged at those once-through
        # sizes outside the batch multiplier, not the fused kernel's
        # per-tile re-fetch cadence.
        g_reads = 2 * ho * wo * m
        w_reads = k2 * c * m
        dw_writes = k2 * c * m
        hp = shape.h + 2 * (pad + hb) + 1
        x_read = hp * w_full * c
        band_elems = h_tiles * band_h * w_full * c
        # recompute staging: bands written then re-read by the kernel
        inp = x_read + 2 * band_elems
        # d_input: banded scatter buffer written, re-read, reduced
        dx_bands = 2 * band_elems + hp * w_full * c
        # two-stage patch round-trip: patch residual read + d_patches
        # written by the einsum VJP + read by the sampling VJP
        patches = 3 * ho * wo * k2 * c
        return (batch * (inp + dx_bands + patches + g_reads + doff_writes)
                + w_reads + dw_writes) * bytes_per_elem
    raise ValueError(f"unknown dataflow {dataflow!r}")


def dcl_train_hbm_bytes(shape: LayerShape, t: TileConfig, *,
                        dataflow: str = "zero_copy", batch: int = 1,
                        dilation: int = 1, bytes_per_elem: int = 4,
                        cores: int = 1) -> int:
    """Combined fwd+bwd whole-layer HBM traffic — the objective the
    kernel tile chooser minimizes for training (``objective='training'``).
    ``cores`` charges the Megacore backward's extra partial-d_weights
    flushes + reduce epilogue (zero-copy only)."""
    bwd_cores = cores if dataflow == "zero_copy" else 1
    return (dcl_total_hbm_bytes(shape, t, dataflow=dataflow, batch=batch,
                                dilation=dilation,
                                bytes_per_elem=bytes_per_elem)
            + dcl_backward_hbm_bytes(shape, t, dataflow=dataflow,
                                     batch=batch, dilation=dilation,
                                     bytes_per_elem=bytes_per_elem,
                                     cores=bwd_cores))


def zerocopy_vmem_bytes(shape: LayerShape, t: TileConfig, *,
                        dilation: int = 1, bytes_per_elem: int = 4,
                        aux_bytes_per_elem: int | None = None,
                        fuse_offsets: bool = False,
                        offset_channels: int | None = None) -> int:
    """VMEM working set of the zero-copy fused forward kernel — the
    buffers ``kernels.band_pipeline.forward_call`` allocates:

    * the staged Eq. 6 band (``band_h`` x ``staged_width(band_w)``),
      double-buffered at the datapath width (one slot for fused-offset
      plans, which stage it once per spatial tile);
    * its lane-chunked fp32 copy, which the sampler reads;
    * the sampled patch tile (``tile_h*tile_w`` x ``K^2*tile_c``, int8
      on the 1-byte datapath, else fp32), the spill slots Mosaic gives
      it when the MXU contraction reads it as one value, and the 4-byte
      accumulator;
    * the double-buffered pipeline blocks: weights, offsets (or, fused,
      the int8 offset-conv weights plus an fp32 offset scratch) and the
      output tile.

    ``aux_bytes_per_elem`` sizes the offsets and the output tile
    separately from the datapath — the int8 kernel keeps both fp32
    (addresses are full precision; the dequant epilogue emits fp32).
    ``offset_channels`` defaults to 2*K*K; a modulated (DCNv2) layer
    reads 3*K*K.
    """
    k2 = shape.kernel_size ** 2
    oc = offset_channels or 2 * k2
    aux_b = aux_bytes_per_elem or bytes_per_elem
    band_h = band_extent(t.t_h, kernel_size=shape.kernel_size,
                         stride=shape.stride, dilation=dilation,
                         offset_bound=shape.offset_bound)
    band_w = staged_width(band_extent(t.t_w, kernel_size=shape.kernel_size,
                                      stride=shape.stride, dilation=dilation,
                                      offset_bound=shape.offset_bound))
    band_elems = band_h * band_w * t.t_n
    n_buf = 1 if fuse_offsets else 2
    band = n_buf * band_elems * bytes_per_elem + band_elems * 4
    pixels = t.t_h * t.t_w
    patches = pixels * k2 * t.t_n * (1 if bytes_per_elem == 1 else 4)
    # Spill slots of the contraction's patch operand, from compiles for
    # v5e: 4.2x the fp32 tile (78.4 MiB for an 18.0 MiB tile, 150.8 MiB
    # for 36.0 MiB — the HIGHEST-precision operand is split into bf16
    # parts), budgeted at 4.5x; the int8 operand is read once.
    spill = patches if bytes_per_elem == 1 else patches * 9 // 2
    acc = pixels * t.t_m * 4
    wgt = 2 * k2 * t.t_n * t.t_m * bytes_per_elem
    if fuse_offsets:
        offs = 2 * k2 * t.t_n * oc + pixels * oc * 4
    else:
        offs = 2 * pixels * oc * aux_b
    out = 2 * pixels * t.t_m * aux_b
    return band + patches + spill + acc + wgt + offs + out


def zerocopy_bwd_vmem_bytes(shape: LayerShape, t: TileConfig, *,
                            dilation: int = 1,
                            bytes_per_elem: int = 2) -> int:
    """VMEM working set of the fused zero-copy *backward* kernel
    (``kernels.deform_conv_bwd``): double-buffered Eq. 6 input band +
    same-shaped d_input read-modify-write band + the full fp32
    d_weights accumulator (K^2 * C * M — the M axis is untiled in
    backward) + cotangent tile + weight block + fp32 d_offsets
    accumulator."""
    k2 = shape.kernel_size ** 2
    band_h = band_extent(t.t_h, kernel_size=shape.kernel_size,
                         stride=shape.stride, dilation=dilation,
                         offset_bound=shape.offset_bound)
    band_w = band_extent(t.t_w, kernel_size=shape.kernel_size,
                         stride=shape.stride, dilation=dilation,
                         offset_bound=shape.offset_bound)
    band = 2 * band_h * band_w * t.t_n * bytes_per_elem   # double buffer
    rmw = band_h * band_w * t.t_n * bytes_per_elem        # d_input band
    dw_acc = k2 * shape.c_in * shape.c_out * 4            # fp32, all chunks
    g_tile = t.t_h * t.t_w * shape.c_out * bytes_per_elem
    wgt = k2 * t.t_n * shape.c_out * bytes_per_elem
    doff_acc = t.t_h * t.t_w * 2 * k2 * 4
    return band + rmw + dw_acc + g_tile + wgt + doff_acc


def _divisor_at_most(n: int, cap: int) -> int:
    """Largest divisor of ``n`` that is <= cap (>= 1)."""
    cap = max(1, min(cap, n))
    for d in range(cap, 0, -1):
        if n % d == 0:
            return d
    return 1


def _channel_tiles(n: int, caps: tuple[int, ...]) -> list[int]:
    """Channel-tile candidates: divisors of ``n`` near ``caps``, kept
    only where Mosaic can window them — the whole extent or whole
    128-lane multiples."""
    return sorted({d for d in (_divisor_at_most(n, cap) for cap in
                               (*caps, n))
                   if d == n or d % MXU_LANE == 0})


@dataclasses.dataclass(frozen=True)
class KernelTiles:
    """Concrete (divisor-snapped) tile sizes for the Pallas kernels."""
    tile_h: int
    tile_w: int
    tile_c: int
    tile_m: int


@functools.lru_cache(maxsize=512)
def choose_kernel_tiles(shape: LayerShape, *, batch: int = 1,
                        dilation: int = 1,
                        objective: str = "training",
                        dtype: str | None = None,
                        cores: int = 1,
                        vmem_budget: int = VMEM_LIMIT_BYTES) -> KernelTiles:
    """Pick (tile_h, tile_w, tile_c, tile_m) for the zero-copy fused
    kernels: minimize modeled whole-layer HBM traffic among tile points
    whose double-buffered working set fits VMEM, then snap the channel
    tiles to divisors of (C, M) as the kernels require.

    ``objective`` selects the traffic term: ``"forward"`` minimizes the
    inference pass only; ``"training"`` (default — what
    ``ops.deform_conv`` uses, since the same resolved tiles serve the
    custom-VJP backward kernel) minimizes the combined fwd+bwd traffic
    of ``dcl_train_hbm_bytes`` and additionally requires the backward
    working set (``zerocopy_bwd_vmem_bytes``) to fit VMEM.

    ``dtype`` makes both budgets element-width-aware: ``"int8"`` sizes
    the Eq. 6 band and weight blocks at 1 byte/elem (4x the band per
    VMEM byte vs fp32 — the quantized-datapath win the paper's
    fixed-point design banks on), ``"bf16"``/``"fp32"`` at 2/4.
    ``dtype=None`` is the fp32 kernel (4-byte working set and traffic).

    ``cores`` evaluates the training objective with the Megacore
    backward split's traffic (extra partial-d_weights flushes + reduce
    epilogue): the dw terms grow with cores, so the chooser leans
    toward channel tiles that keep the per-core partial cheap.  Each
    core has its own VMEM on Megacore parts, so the VMEM budgets are
    already per-core and need no scaling.

    This replaces the hand-passed tile arguments of ``ops.deform_conv``
    (Sec. 3.2 methodology, evaluated on the zero-copy traffic model).
    The row-tile candidate set extends to 32: per-tile halo re-reads
    amortize with taller tiles, and the PR-2 128-channel regression
    traced to the chooser stopping at tile_h=16 while the bench pinned
    tile_h=8 (see EXPERIMENTS.md §Perf).
    """
    if objective not in ("forward", "training"):
        raise ValueError(f"unknown objective {objective!r}")
    if cores < 1:
        raise ValueError(f"cores={cores} must be >= 1")
    vmem_b = dtype_bytes(dtype) if dtype is not None else 4
    traffic_b = dtype_bytes(dtype) if dtype is not None else 4
    # The int8 kernel keeps offsets/output fp32 (address precision +
    # dequant epilogue) — size those VMEM terms at 4 bytes, not 1.
    aux_b = 4 if dtype == "int8" else None
    ho, wo = out_hw(shape.h, shape.w, kernel_size=shape.kernel_size,
                    stride=shape.stride, dilation=dilation)
    ths = sorted({min(t, max(1, ho)) for t in (1, 2, 4, 8, 16, 32)})
    tws = sorted({min(t, max(1, wo)) for t in (8, 16, 32, 64, 128)})
    tns = _channel_tiles(shape.c_in, (32, 64, 128, 256, 512))
    tms = _channel_tiles(shape.c_out, (32, 64, 128, 256))
    traffic_fn = (functools.partial(dcl_train_hbm_bytes, cores=cores)
                  if objective == "training" else dcl_total_hbm_bytes)
    best: tuple[tuple, TileConfig] | None = None
    for t_h in ths:
        for t_w in tws:
            for t_n in tns:
                for t_m in tms:
                    t = TileConfig(t_h, t_w, t_n, t_m)
                    vmem = zerocopy_vmem_bytes(shape, t, dilation=dilation,
                                               bytes_per_elem=vmem_b,
                                               aux_bytes_per_elem=aux_b)
                    if objective == "training":
                        vmem = max(vmem, zerocopy_bwd_vmem_bytes(
                            shape, t, dilation=dilation,
                            bytes_per_elem=vmem_b))
                    if vmem > vmem_budget:
                        continue
                    traffic = traffic_fn(
                        shape, t, dataflow="zero_copy", batch=batch,
                        dilation=dilation, bytes_per_elem=traffic_b)
                    # Minimize traffic; break ties toward bigger MXU tiles.
                    key = (float(traffic), -t_n * t_m, -t_h * t_w)
                    if best is None or key < best[0]:
                        best = (key, t)
    if best is None:
        raise ValueError(
            f"no zero-copy tile configuration fits VMEM budget "
            f"{vmem_budget} for {shape} at dtype={dtype or 'legacy'}; "
            f"receptive field {shape.rf} too large — train with a larger "
            f"lambda")
    t = best[1]
    return KernelTiles(tile_h=t.t_h, tile_w=t.t_w, tile_c=t.t_n,
                       tile_m=t.t_m)


def neighbor_kernel_tiles(shape: LayerShape, seed: KernelTiles, *,
                          dilation: int = 1,
                          objective: str = "training",
                          dtype: str | None = None,
                          vmem_budget: int = VMEM_LIMIT_BYTES,
                          radius: int = 1) -> list[KernelTiles]:
    """VMEM-feasible tile candidates around ``seed`` — the search space
    of the measured-time autotuner (``repro.tune``).

    Each dimension moves up to ``radius`` positions along the analytic
    chooser's own candidate ladder (spatial tiles clamped to the output
    extent, channel tiles drawn from the same divisor set), and the
    cross product is filtered by exactly the working-set feasibility
    ``choose_kernel_tiles`` enforces (forward VMEM, plus the backward
    working set under the training objective).  The seed is always the
    first candidate, so the analytic pick is measured alongside its
    neighbors and the tuner's win is never an artifact of dropping it.
    """
    if objective not in ("forward", "training"):
        raise ValueError(f"unknown objective {objective!r}")
    vmem_b = dtype_bytes(dtype) if dtype is not None else 4
    aux_b = 4 if dtype == "int8" else None
    ho, wo = out_hw(shape.h, shape.w, kernel_size=shape.kernel_size,
                    stride=shape.stride, dilation=dilation)
    ths = sorted({min(t, max(1, ho)) for t in (1, 2, 4, 8, 16, 32)})
    tws = sorted({min(t, max(1, wo)) for t in (8, 16, 32, 64, 128)})
    tns = _channel_tiles(shape.c_in, (32, 64, 128, 256, 512))
    tms = _channel_tiles(shape.c_out, (32, 64, 128, 256))

    def near(ladder: list[int], v: int) -> list[int]:
        i = min(range(len(ladder)), key=lambda j: abs(ladder[j] - v))
        return ladder[max(0, i - radius):i + radius + 1]

    seed_kt = KernelTiles(seed.tile_h, seed.tile_w, seed.tile_c,
                          seed.tile_m)
    out, seen = [seed_kt], {(seed.tile_h, seed.tile_w, seed.tile_c,
                             seed.tile_m)}
    for t_h in near(ths, seed.tile_h):
        for t_w in near(tws, seed.tile_w):
            for t_n in near(tns, seed.tile_c):
                for t_m in near(tms, seed.tile_m):
                    key = (t_h, t_w, t_n, t_m)
                    if key in seen:
                        continue
                    seen.add(key)
                    t = TileConfig(t_h, t_w, t_n, t_m)
                    vmem = zerocopy_vmem_bytes(shape, t, dilation=dilation,
                                               bytes_per_elem=vmem_b,
                                               aux_bytes_per_elem=aux_b)
                    if objective == "training":
                        vmem = max(vmem, zerocopy_bwd_vmem_bytes(
                            shape, t, dilation=dilation,
                            bytes_per_elem=vmem_b))
                    if vmem > vmem_budget:
                        continue
                    out.append(KernelTiles(t_h, t_w, t_n, t_m))
    return out


def max_offset_bound_fitting(kernel_size: int, stride: int, t_w: int,
                             t_n: int, vmem_budget: int = VMEM_LIMIT_BYTES,
                             *, bytes_per_elem: int = 2) -> float:
    """Inverse of Eq. 6: largest offset bound B whose input tile still
    fits the budget.  This is what couples the Eq. 5 regularizer strength
    to the hardware — the co-design knob of the paper."""
    b = 0
    while True:
        rf = receptive_field(kernel_size, b + 1)
        if input_buffer_size(rf, stride, t_w, t_n,
                             bytes_per_elem=bytes_per_elem) > vmem_budget:
            return float(b)
        b += 1
        if b > 4096:
            return float(b)
