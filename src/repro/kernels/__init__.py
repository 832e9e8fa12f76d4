"""Pallas TPU kernels for the compute hot spots (validated against the
``ref.py`` oracles in interpret mode; TPU is the lowering target).

Every bounded DCL kernel is emitted from the unified band-pipeline
emitter (``band_pipeline.py`` — ``BandSpec``/``DCLPlan`` + the shared
double-buffered ``make_async_copy`` band stager; see
``docs/kernels.md``):

* ``deform_sample``     — stage-1 bounded-halo bilinear sampling
  (Eq. 6; a contraction-free plan)
* ``deform_conv_fused`` — stage 1+2 fused in VMEM (fp32 plan)
* ``deform_conv_q``     — the int8 plans: fused dequant inference and
  the int8→int8 *chained* kernel (fused in-kernel offset-conv stage +
  per-channel requant emission — back-to-back DCLs never round-trip
  fp32 through HBM)
* ``deform_conv_bwd``   — fused backward (d_input / d_offsets /
  d_weights) over the same Eq. 6 bands via the shared stager, with the
  Megacore ``cores`` grid axis; wired as a ``jax.custom_vjp`` on
  ``ops.deform_conv`` so bounded training never leaves the zero-copy
  dataflow

The zero-copy dataflow is the default: the padded input stays whole in
ANY/HBM and each (row-tile, width-tile) Eq. 6 band is DMA'd into
double-buffered VMEM scratch by the kernel itself, overlapping the next
band's fetch with the current tile's sampling + MXU work.  The legacy
HBM-materialized banded dataflow is kept behind ``dataflow="banded"``
as the parity baseline.

* ``flash_attention``   — blockwise online-softmax attention
* ``matmul``            — tiled MXU matmul (the systolic-array analogue)

Public entry points live in ``ops``; plan building and the runner
bodies in ``plan``.
"""
