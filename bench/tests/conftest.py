import pathlib
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
for p in (ROOT / "src", ROOT):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))


@pytest.fixture(scope="module")
def bounded():
    """The bounded configuration at the tiny size, built once."""
    import jax
    from _benchtest import tiny
    from bench import run
    from bench.families import resnet_dcn
    spec, _, cfg = run.load_spec(run.ROOT, "r50dcn_b2.offline")
    cfg = tiny(cfg)
    with jax.default_matmul_precision(cfg["matmul_precision"]):
        system = run.build(resnet_dcn, cfg, 20261018)
    return spec, cfg, system
