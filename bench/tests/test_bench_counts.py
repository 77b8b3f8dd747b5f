"""Counts kept with the benchmark: model FLOPs, kernel bytes, peaks, and
that every name in BENCHMARK.json has the files the harness looks for."""
import json

import jax
import pytest

from bench import harness, peaks
from bench.configs import resnet_tiny
from bench.tests.tiny import ROOT

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())


def _config(name):
    return json.loads((ROOT / "bench" / "configs" / f"{name}.json").read_text())


@pytest.mark.parametrize("name, forward, params", [
    ("resnet_tiny-cifar10", 1_597_379_584, 4_696_394),
    ("resnet_tiny-mnist", 1_221_188_608, 4_695_242),
])
def test_resnet_tiny_counts(name, forward, params):
    cfg = _config(name)
    assert resnet_tiny.forward_flops(cfg["model"], cfg["dataset"]["shape"]) == forward
    assert resnet_tiny.train_flops_per_sample(cfg["model"], cfg["dataset"]) == 3 * forward
    assert resnet_tiny.param_count(cfg["model"]) == params == cfg["param_count"]
    shapes = jax.eval_shape(lambda: resnet_tiny.init_params(cfg["model"], 0))
    assert sum(x.size for x in jax.tree.leaves(shapes)) == params


def test_forward_flops_match_a_direct_count():
    """One stem convolution and one dense head, counted by hand."""
    model = {"widths": [4], "depths": [0], "in_channels": 2, "num_classes": 3}
    assert resnet_tiny.forward_flops(model, (5, 6, 2)) == 2 * 5 * 6 * 9 * 2 * 4 + 2 * 4 * 3


def test_masked_agg_least_bytes():
    reader = harness.load_module(ROOT / "bench" / "metrics" / "masked_agg_roofline.py")
    assert reader.least_bytes(10, 4_696_394) == 21 * 4_696_394 * 4 == 394_497_096


def test_peaks_by_device_kind():
    v5e = peaks.peaks("TPU v5 lite")
    assert v5e["bf16_flops_per_s"] == 197e12 and v5e["hbm_bytes_per_s"] == 819e9
    assert "TPU v5e" in v5e["source"]
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peaks("cpu")


@pytest.mark.parametrize("cell", [w["name"] for w in SPEC["workloads"]])
def test_every_name_has_its_files(cell):
    loaded = harness.load_cell(ROOT, cell)
    assert callable(loaded["generator"].make)
    assert loaded["traffic"]["local_steps"] > 0
    assert loaded["limits"] and set(loaded["limits"]) <= set(loaded["check"].NUMBERS)
    assert loaded["end_to_end"] and loaded["per_layer"]
    for m in loaded["per_layer"]:
        assert callable(harness.load_module(loaded["metrics_dir"] / f"{m['name']}.py").read)
