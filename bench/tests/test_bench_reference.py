"""The plain float32 reference agrees with Federation rounds, and its
bfloat16 control does not (CPU, reduced width)."""
import jax.numpy as jnp
import pytest

from bench import harness
from bench.configs import resnet_tiny
from bench.tests import tiny


@pytest.fixture(scope="module", params=[("cifar10-secagg-l8", 2 ** 33 + 17),
                                        ("mnist-secagg-l2", 2 ** 33 + 19)], ids=lambda p: p[0])
def sound(request):
    cell, seed = request.param
    result, run = harness.run_cell(tiny.ROOT, cell, seed, 0.2, False, require_tpu=False,
                                   overrides=tiny.overrides(resnet_tiny, tiny.CELLS[cell]))
    return result, run


def test_reference_follows_the_federation(sound):
    result, run = sound
    assert result["correct"] is True, result["checks"]
    channels = run["cell"]["config"]["model"]["in_channels"]
    assert set(run["data"]["train"]) == {"image", "label"}
    assert run["data"]["train"]["image"].shape[1:] == (16, 16, channels)
    # on the CPU both sides are float32; what is left is the secure
    # aggregation's fixed-point rounding, far under every limit
    for name, c in result["checks"].items():
        assert c["value"] <= c["limit"] / 3, name
    assert len(run["program"]) == run["cell"]["traffic"]["warmup_rounds"]
    assert round(run["program"][0]["loss"], 3) == round(run["reference"][0]["loss"], 3)


def test_bfloat16_control_is_rejected(sound):
    _, run = sound
    cell, check = run["cell"], run["cell"]["check"]
    control = check.reference_rounds(cell["reference"], cell, run["data"], run["parts"],
                                     run["seeds"]["clients"], run["params0"],
                                     [run["program"][0]["selected"]], dtype=jnp.bfloat16)
    readings = check.readings(control, run["reference"], run["params0"])
    assert any(readings[n] > limit for n, limit in cell["limits"].items()), readings


def test_result_line_keys(sound):
    result, _ = sound
    assert list(result) == ["correct", "attempted", "failed", "metrics", "device", "checks"]
    assert set(result["metrics"]) == {"samples_per_s", "round_s_p90", "setup_s"}
    assert result["attempted"] >= 1 and result["failed"] == 0
    assert all(m["value"] > 0 for m in result["metrics"].values())
