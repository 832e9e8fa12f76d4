"""Modulated deformable convolution (DCNv2): every kernel datapath and
the served detector against the plain modulated reference
(``bench/reference/resnet_dcnv2.py``, which imports nothing of the
program) on seeded weights, a served fault the comparison must catch,
and the combinations a modulated layer refuses."""
import pathlib
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]
for p in (ROOT, ROOT / "bench" / "tests"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

from bench import run  # noqa: E402
from bench.families import resnet_dcnv2 as family  # noqa: E402
from bench.reference import resnet_dcn as ref_v1  # noqa: E402
from bench.reference import resnet_dcnv2 as ref  # noqa: E402
from _benchtest import CPU, TINY_LIMITS, tiny  # noqa: E402

from repro.kernels import ops  # noqa: E402
from repro.models import resnet_dcn as R  # noqa: E402
from repro.models.layers import dcl_apply  # noqa: E402
from repro.quant.qtypes import compute_scale  # noqa: E402

C = M = 8
BOUND = 2.0
# (name, stride, offset standard deviation in pixels): the last drives
# about half the offsets past the bound, so the Eq. 4 clamp is hit.
CASES = [("stride1", 1, 1.0), ("stride2", 2, 1.0), ("clamp_hit", 1, 3.0)]
DATAPATHS = ("fp32", "int8", "int8_chain")


def _layer(seed, offset_px):
    """One modulated DCL, seeded: offsets of about ``offset_px`` pixels
    and mask logits of about N(0, 1), so the masks span about
    [0.1, 0.9]."""
    k = jax.random.split(jax.random.PRNGKey(seed), 4)
    fan = 9 * C
    w_off = jax.random.normal(k[0], (3, 3, C, 27)) / np.sqrt(fan)
    w_off = w_off.at[..., :18].multiply(offset_px)
    b_off = jax.random.normal(k[1], (27,)) * 0.5
    return {"w_offset": w_off.at[..., 18:].multiply(0.6),
            "b_offset": b_off.at[18:].multiply(0.6),
            "w_deform": jax.random.normal(k[2], (3, 3, C, M))
            * np.sqrt(2 / fan),
            "b_deform": jax.random.normal(k[3], (M,)) * 0.1}


def _rel_l2(got, want):
    return float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))


@pytest.mark.parametrize("datapath", DATAPATHS)
@pytest.mark.parametrize("case", CASES, ids=lambda c: c[0])
def test_kernel_datapath_matches_modulated_reference(case, datapath):
    name, stride, offset_px = case
    p = _layer(CASES.index(case), offset_px)
    x = jax.random.normal(jax.random.PRNGKey(7), (2, 12, 12, C))
    with jax.default_matmul_precision("highest"):
        want = ref.dcl_fp32(p, x, stride, BOUND)
        o = ref._conv(x, p["w_offset"], stride, pad=1) + p["b_offset"]
        dcnv1 = ref_v1.dcl_fp32(
            dict(p, w_offset=p["w_offset"][..., :18],
                 b_offset=p["b_offset"][:18]), x, stride, BOUND)
    mask = jax.nn.sigmoid(o[..., 18:])
    # The case exercises what it names: masks spread over (0, 1), the
    # clamp hit where asked, and an answer far from DCNv1's.
    assert float(mask.min()) < 0.15 and float(mask.max()) > 0.85
    clamped = float(jnp.mean(jnp.abs(o[..., :18]) > BOUND))
    assert (clamped > 0.3) == (name == "clamp_hit"), clamped
    assert _rel_l2(dcnv1, want) > 0.5

    sx = float(compute_scale(x))
    sw = np.asarray(compute_scale(p["w_deform"], axis=-1)).reshape(-1)
    kw = dict(stride=stride, offset_bound=BOUND, use_kernel=True)
    if datapath == "fp32":
        got, _ = dcl_apply(p, x, **kw)
        # The same float32 arithmetic, summed in another order.
        assert float(jnp.abs(got - want).max()
                     / jnp.abs(want).max()) <= 1e-5
    elif datapath == "int8":
        got, _ = dcl_apply(p, x, quant="int8",
                           quant_scales={"x_scale": sx, "w_scale": sw},
                           **kw)
        # Input, weights and masked taps on int8 grids: 0.020-0.023 from
        # float32 on these cases; dropping the mask costs 0.70-0.90.
        assert _rel_l2(got, want) <= 0.06
    else:
        swo = np.asarray(compute_scale(p["w_offset"], axis=-1)).reshape(-1)
        table = {"x_scale": sx, "w_scale": sw, "w_offset_scale": swo,
                 "y_scale": float(jnp.abs(want).max()) / 127}
        got, _ = dcl_apply(p, x, quant="int8_chain", quant_scales=table,
                           **kw)
        q, _ = ref.dcl_int(p, x, {k: jnp.asarray(v, jnp.float32)
                                  for k, v in table.items()},
                           stride, BOUND)
        delta = np.abs(np.asarray(got.values, np.float32) - np.asarray(q))
        # Exact-integer contractions on both sides; a masked tap that a
        # differently contracted fp32 sum puts within an ulp of a .5
        # rounding boundary may round the other way (none on these
        # cases), so a few outputs may differ, never most.
        assert np.count_nonzero(delta) <= 0.005 * delta.size
        # Against float32: the int8 grids' error, 0.032-0.053 here.
        assert _rel_l2(got.dequantize(jnp.float32), want) <= 0.1


# -- the served detector ---------------------------------------------------

def _cell(rung):
    """The new cell's configuration at the benchmark tests' tiny size
    (``_benchtest.tiny``: three modulated DCLs, 64x64 images, the tiny
    size's own float32 limits) on ``rung``."""
    spec, cell, cfg = run.load_spec(run.ROOT, "r101dcnv2_b2.offline")
    cfg = dict(tiny(cfg), rung=rung)
    if rung == "fp32_ref":
        # Float32 at highest against the reference: summation order.
        cfg["limits"] = {"fp32_max": TINY_LIMITS["fp32_max"]}
    with jax.default_matmul_precision(cfg["matmul_precision"]):
        system = run.build(family, cfg, 2**33 + 16)
    return spec, cell, cfg, system


@pytest.fixture(scope="module")
def chain_cell():
    return _cell("int8_chain")


def _serve(cell_setup, seconds=0.5):
    spec, cell, cfg, system = cell_setup
    return run.run_cell(run.ROOT, spec, cell, cfg, 0, seconds, False,
                        device=CPU, system=system)


def test_served_int8_chain_matches_reference(chain_cell):
    spec, cell, cfg, system = chain_cell
    assert system.engine._cfgs["int8_chain"].modulated
    assert all(len(s["w_offset_scale"]) == 27
               for s in system.table.values())
    result, numbers, _ = _serve(chain_cell)
    assert result["correct"], result["checks"]
    assert result["failed"] == 0 and numbers["off_rung"] == 0


def test_served_fp32_ref_matches_reference():
    result, numbers, _ = _serve(_cell("fp32_ref"))
    assert result["correct"], result["checks"]
    assert numbers["off_rung"] == 0


def test_served_mask_forced_to_one_fails(chain_cell):
    """The engine serves with every mask 1 (mask logits forced to +40,
    sigmoid 1.0 in float32) while the reference keeps the seeded masks:
    the comparison must refuse it."""
    spec, cell, cfg, system = chain_cell
    served = system.engine.params
    forced = jax.tree.map(lambda a: a, served)
    for name, *_, is_dcn in ref.blocks(cfg):
        if is_dcn:
            d = dict(forced[name]["dcl"])
            d["w_offset"] = d["w_offset"].at[..., 18:].set(0.0)
            d["b_offset"] = d["b_offset"].at[18:].set(40.0)
            forced[name] = dict(forced[name], dcl=d)
    system.engine.params = forced
    try:
        result, numbers, _ = _serve(chain_cell)
    finally:
        system.engine.params = served
    assert not result["correct"]
    assert numbers["chain_l2"] > cfg["limits"]["chain_l2"]
    assert numbers["dcl_flip_share"] > cfg["limits"]["dcl_flip_share"]


def test_model_dcl_span_carries_modulation():
    from repro.obs import Tracer, tracer_scope
    from repro.quant.calibrate import calibrate_resnet_dcn
    cfg = R.ResNetDCNConfig(
        stage_sizes=(1, 1, 1, 1), widths=(16, 32, 64, 128), stem_width=8,
        num_dcn=2, num_classes=4, img_size=32, offset_bound=BOUND,
        modulated=True)
    params = R.init_params(jax.random.PRNGKey(0), cfg)
    tracer = Tracer(enabled=True)
    with tracer_scope(tracer):
        table = calibrate_resnet_dcn(params, cfg,
                                     [np.zeros((1, 32, 32, 3), np.float32)])
    spans = [s for s in tracer.spans if s.name == "model/dcl"]
    assert len(spans) == cfg.num_dcn
    assert all(s.attrs["modulated"] and s.attrs["offset_channels"] == 27
               for s in spans)
    assert all(len(table[n]["w_offset_scale"]) == 27
               for n in ("s2b0", "s3b0"))


# -- refusals ----------------------------------------------------------------

def test_modulated_refuses_shard_spatial():
    p = _layer(0, 1.0)
    x = jnp.zeros((1, 8, 8, C))
    with pytest.raises(ValueError, match=r"shard_spatial=False"):
        dcl_apply(p, x, offset_bound=BOUND, use_kernel=True, quant="int8",
                  shard_spatial=True)
    offsets = jnp.zeros((1, 8, 8, 27))
    w = jnp.zeros((9, C, M))
    with pytest.raises(ValueError, match=r"shard_spatial=False"):
        ops.deform_conv(x, offsets, w, offset_bound=BOUND,
                        shard_spatial=True)


def test_modulated_kernel_gradient_refused():
    p = _layer(0, 1.0)
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 8, 8, C))

    def loss(p, use_kernel):
        y, _ = dcl_apply(p, x, offset_bound=BOUND, use_kernel=use_kernel)
        return jnp.sum(y ** 2)
    with pytest.raises(NotImplementedError, match=r"use_kernel=False"):
        jax.grad(loss)(p, True)
    g = jax.grad(loss)(p, False)           # the named alternative trains
    assert float(jnp.abs(g["w_offset"][..., 18:]).sum()) > 0


def test_modulated_engine_refuses_spatial_bucket():
    from repro.serve import DCLServeConfig, DCLServingEngine
    cfg = R.ResNetDCNConfig(
        stage_sizes=(1, 1, 1, 1), widths=(16, 32, 64, 128), stem_width=8,
        num_dcn=2, num_classes=4, img_size=32, offset_bound=BOUND,
        modulated=True, use_kernel=True)
    params = R.init_params(jax.random.PRNGKey(0), cfg)
    with pytest.raises(ValueError, match=r"no spatial_shards entry"):
        DCLServingEngine(params, cfg, DCLServeConfig(
            buckets=(32,), quant="fp32_kernel", spatial_shards=((32, 2),)))
