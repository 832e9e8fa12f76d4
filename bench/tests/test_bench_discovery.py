"""A configuration, a traffic mix and a metric added only as new files
(and entries of BENCHMARK.json) are found by name, with no edit of an
existing file; and the float32 cell's control fails its comparison."""
import json
import shutil

import pytest

from _benchtest import CPU, tiny
from bench import run

NEW_METRIC = '''"""Requests the window served ok."""


def read(rec):
    return float(rec.images_ok)
'''


@pytest.fixture(scope="module")
def added(tmp_path_factory):
    root = tmp_path_factory.mktemp("checkout")
    shutil.copytree(run.ROOT / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    spec = json.loads((run.ROOT / "BENCHMARK.json").read_text())
    cfg = tiny(json.loads(
        (run.ROOT / "bench/configs/resnet50_dcn.json").read_text()))
    (root / "bench/configs/r50dcn_tiny.json").write_text(json.dumps(cfg))
    (root / "bench/traffic/trickle.json").write_text(json.dumps(
        {"loop": "open", "arrivals": "bursty", "rate_per_s": 6.0,
         "on_s": 0.5, "off_s": 0.5}))
    (root / "bench/metrics/served_ok.py").write_text(NEW_METRIC)
    spec["configs"].append({"name": "r50dcn_tiny", "source": "test",
                            "file": "bench/configs/r50dcn_tiny.json",
                            "reduced": ["stage_sizes"], "why": "test"})
    spec["workloads"].append({"name": "tiny.trickle", "config": "r50dcn_tiny",
                              "traffic": "trickle", "chips": 1, "why": "test"})
    spec["end_to_end"].append({"name": "served_ok", "unit": "requests",
                               "better": "higher", "bound": 0.01,
                               "source": "host_clock",
                               "workloads": ["tiny.trickle"]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    spec, cell, cfg = run.load_spec(root, "tiny.trickle")
    result, numbers, control = run.run_cell(
        root, spec, cell, cfg, 5, 2.0, False, device=CPU,
        with_control=True)
    return cfg, result, numbers, control


def test_new_files_are_found_by_name(added):
    cfg, result, numbers, _ = added
    assert result["correct"], result["checks"]
    # 6 requests a second for 2 s, all of them due in the window.
    assert result["attempted"] == 12
    assert result["metrics"]["served_ok"]["value"] == 12.0
    assert set(result["metrics"]) == {"setup_s", "served_ok"}


def test_fp32_control_fails(added):
    cfg, _, numbers, control = added
    failed = [k for k, lim in cfg["limits"].items() if control[k] > lim]
    assert failed, (numbers, control, cfg["limits"])
