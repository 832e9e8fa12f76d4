"""The comparisons fail what they must: the control (the reference one
precision down in the program's place) and an answer altered where the
engine produces it, at a tiny size on the CPU."""
import numpy as np

from bench import run
from _benchtest import CPU


def _run(spec, cfg, system, **kw):
    _, cell, _ = run.load_spec(run.ROOT, "r50dcn_b2.offline")
    return run.run_cell(run.ROOT, spec, cell, cfg, 0, 0.5, False,
                        device=CPU, system=system, **kw)


def test_int4_control_fails(bounded):
    spec, cfg, system = bounded
    result, numbers, control = _run(spec, cfg, system, with_control=True)
    assert result["correct"], result["checks"]
    failed = [k for k, lim in cfg["limits"].items() if control[k] > lim]
    assert failed, (control, cfg["limits"])


def test_altered_answer_fails(bounded, monkeypatch):
    from repro.models import resnet_dcn
    forward = resnet_dcn.forward

    def misrouted(*a, **kw):
        # Slot 0 is handed slot 1's answer.
        out, o = forward(*a, **kw)
        out = dict(out)
        for k in ("cls", "box"):
            out[k] = out[k].at[0].set(out[k][1])
        return out, o
    monkeypatch.setattr(resnet_dcn, "forward", misrouted)
    spec, cfg, system = bounded
    result, numbers, _ = _run(spec, cfg, system)
    assert not result["correct"]
    assert numbers["chain_l2"] > cfg["limits"]["chain_l2"]
    assert np.isfinite(numbers["chain_l2"])
