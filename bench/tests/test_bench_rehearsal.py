"""One short window of every traffic mix under bench/traffic, through
the serving engine, at a tiny size on the CPU."""
import pytest

from bench import run, traffic
from _benchtest import CPU

MIXES = sorted(p.stem for p in (run.ROOT / "bench" / "traffic").glob("*.json"))


@pytest.mark.parametrize("mix", MIXES)
def test_one_window_per_traffic_mix(bounded, mix):
    spec, cfg, system = bounded
    _, cell, _ = run.load_spec(run.ROOT, "r50dcn_b2.offline")
    result, numbers, _ = run.run_cell(
        run.ROOT, spec, cell, cfg, 0, 1.0, False, device=CPU, system=system,
        traffic=traffic.load(run.ROOT, mix))
    assert result["correct"], result["checks"]
    assert result["attempted"] > 0 and result["failed"] == 0
    want = {m["name"] for m in run.metrics_for(spec, cell, False)}
    assert set(result["metrics"]) == want
    assert all(m["value"] > 0 for m in result["metrics"].values())
    # The compared numbers come last, each beside its limit.
    assert list(result)[-1] == "checks"
    assert set(result["checks"]) == set(cfg["limits"]) | {"off_rung"}
    assert numbers["dcl_flip_share"] <= cfg["limits"]["dcl_flip_share"]


def test_the_cell_refuses_a_cpu():
    with pytest.raises(run.Refused, match="no TPU"):
        run.check_device(1)
