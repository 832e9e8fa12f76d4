"""The yardstick's arithmetic: work from layer shapes, peaks, the
percentile, the generator."""
import json

import numpy as np
import pytest

from bench import checks, peaks, traffic, work
from bench.families import resnet_dcn
from bench.run import ROOT


def _cfg(name):
    return json.loads((ROOT / "bench/configs" / f"{name}.json").read_text())


def test_detector_work_per_512_image():
    """46.28 GFLOP per 512x512 image, as 2 x (15.319 GMAC of XLA convs +
    7.248 GMAC of DCL contraction + 0.573 GMAC of offset convs)."""
    layers = resnet_dcn.layer_work(_cfg("resnet50_dcn_bounded"), 512)
    conv = sum(w["ops"] for w in layers if w["kind"] == "conv")
    dcl = sum(w["ops"] for w in layers if w["kind"] == "dcl")
    # DCL outputs: 3 at 64x64x128, 6 at 32x32x256, 3 at 16x16x512.
    contraction = 2 * 9 * (3 * 64 * 64 * 128 * 128 + 6 * 32 * 32 * 256 * 256
                           + 3 * 16 * 16 * 512 * 512)
    offsets = 2 * 9 * 18 * (3 * 64 * 64 * 128 + 6 * 32 * 32 * 256
                            + 3 * 16 * 16 * 512)
    assert dcl == contraction + offsets
    assert contraction == 2 * 7_247_757_312
    assert offsets == 2 * 573_308_928
    assert conv == 2 * 15_318_974_464
    assert conv + dcl == 46_280_081_408
    assert len([w for w in layers if w["kind"] == "dcl"]) == 12


def test_one_dcl():
    w = work.dcl("s1b1", 1, 64, 64, 64, 64, 128, 128)
    assert w["ops"] == 2 * 64 * 64 * 9 * 128 * (128 + 18)
    assert w["elems"] == 64 * 64 * 128 + 9 * 128 * 146 + 64 * 64 * 128


def test_least_seconds_takes_the_larger_bound():
    p = {"int8_ops": 100.0, "hbm_bytes_per_s": 10.0}
    layers = [{"ops": 1000, "bytes": 50, "datapath": "int8_ops"},
              {"ops": 100, "bytes": 50, "datapath": "int8_ops"}]
    assert work.least_seconds(layers, p) == 10.0 + 5.0
    assert work.compute_seconds(layers, p) == 11.0


def test_peaks_refuse_an_unknown_kind():
    assert peaks.lookup("TPU v5 lite")["int8_ops"] == 393e12
    with pytest.raises(KeyError, match="no peaks"):
        peaks.lookup("cpu")


def test_percentile_is_nearest_rank():
    vals = list(range(1, 21))
    assert checks.percentile(vals, 0.5) == 10
    assert checks.percentile(vals, 0.95) == 19
    assert checks.percentile([1.0, float("inf")], 0.95) == float("inf")


@pytest.mark.parametrize("arrivals", ["poisson", "bursty"])
def test_every_seed_sends_the_same_gaps(arrivals):
    t = {"loop": "open", "arrivals": arrivals, "rate_per_s": 11.0,
         "on_s": 1.0, "off_s": 2.0}
    a = traffic.gaps(t, 30.0, 1)
    b = traffic.gaps(t, 30.0, 2**33 + 7)
    assert len(a) == len(b) == 330
    assert np.all(np.diff(a) >= 0) and a[-1] <= 30.0 + 1e-9
    if arrivals == "poisson":
        assert np.isclose(a[-1], 30.0)
        assert np.allclose(np.sort(np.diff(a, prepend=0)),
                           np.sort(np.diff(b, prepend=0)))
    assert not np.allclose(a, b)


def test_images_are_seeded_and_distinct():
    a, b = traffic.Images(3, [16]), traffic.Images(3, [16])
    assert np.array_equal(a(5), b(5))
    seen = {a(i).tobytes() for i in range(3 * traffic.POOL)}
    assert len(seen) == 3 * traffic.POOL


def test_worst_relative_gaps():
    want = [{"cls": np.array([1.0, -2.0]), "box": np.array([4.0])}]
    got = [{"cls": np.array([1.0, -2.5]), "box": np.array([4.0])}]
    assert checks.worst_max_rel(got, want) == 0.25
    assert np.isclose(checks.worst_l2_rel(got, want), 0.5 / 21 ** 0.5)
    q = np.array([[1.0, 2.0], [3.0, -4.0]])
    assert checks.flip_share({"a": (q + [[0, 1], [0, 0]]) * 0.5},
                             {"a": (q, 0.5)}) == 0.25
