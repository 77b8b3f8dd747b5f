"""The plain float32 reference agrees with Federation rounds, and its
bfloat16 control does not; the check's copies of the weights wait on the
host, and the reference holds a fixed set of arrays on the device (CPU,
reduced width)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness
from bench.configs import resnet_tiny
from bench.tests import tiny


def _live_bytes() -> int:
    return sum(a.nbytes for a in jax.live_arrays())


def _nbytes(tree) -> int:
    return sum(x.nbytes for x in jax.tree.leaves(tree))


@pytest.fixture(scope="module", params=[("cifar10-secagg-l8", 2 ** 33 + 17),
                                        ("mnist-secagg-l2", 2 ** 33 + 19)], ids=lambda p: p[0])
def sound(request):
    """A run, with the device's live bytes read when the check starts (those
    of arrays the run made, and all) and as each client's local round
    returns in the reference."""
    cell, seed = request.param
    check = harness.load_cell(tiny.ROOT, cell)["check"]
    reference_rounds, local_round_fn = check.reference_rounds, check._local_round_fn
    before = jax.live_arrays()  # held, so that no id is reused
    ids = {id(a) for a in before}
    probes = {"loop": []}

    def probed_rounds(*args, **kwargs):
        live = jax.live_arrays()
        probes["start"] = sum(a.nbytes for a in live)
        probes["made"] = sum(a.nbytes for a in live if id(a) not in ids)
        del live
        return reference_rounds(*args, **kwargs)

    def probed_fn(*args):
        local_round = local_round_fn(*args)

        def probed(params, batches):
            out = local_round(params, batches)
            probes["loop"].append(_live_bytes() - probes["start"] - _nbytes(batches) - _nbytes(out[1]))
            return out
        return probed

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(check, "reference_rounds", probed_rounds)
        mp.setattr(check, "_local_round_fn", probed_fn)
        result, run = harness.run_cell(tiny.ROOT, cell, seed, 0.2, False, require_tpu=False,
                                       overrides=tiny.overrides(resnet_tiny, tiny.CELLS[cell]))
    return result, run, probes


def test_reference_follows_the_federation(sound):
    result, run, _ = sound
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
    _, run, _ = sound
    cell, check = run["cell"], run["cell"]["check"]
    control = check.reference_rounds(cell["reference"], cell, run["data"], run["parts"],
                                     run["seeds"]["clients"], run["params0"],
                                     [run["program"][0]["selected"]], dtype=jnp.bfloat16)
    readings = check.readings(control, run["reference"], run["params0"])
    assert any(readings[n] > limit for n, limit in cell["limits"].items()), readings


def test_result_line_keys(sound):
    result, _, _ = sound
    assert list(result) == ["correct", "attempted", "failed", "metrics", "device", "checks"]
    assert set(result["metrics"]) == {"samples_per_s", "round_s_p90", "setup_s"}
    assert result["attempted"] >= 1 and result["failed"] == 0
    assert all(m["value"] > 0 for m in result["metrics"].values())


def test_check_copies_of_the_weights_are_host_arrays(sound):
    _, run, _ = sound
    trees = [run["params0"]] + [r["params"] for r in run["program"] + run["reference"]]
    for tree in trees:
        for leaf in jax.tree.leaves(tree):
            assert isinstance(leaf, np.ndarray) and not isinstance(leaf, jax.Array), type(leaf)


def test_reference_holds_its_budget_on_the_device(sound):
    _, run, probes = sound
    weights = _nbytes(run["params0"])
    # the program is freed and the check's copies are on the host
    assert probes["made"] < weights, probes
    # besides the client's batches and losses: the round's weights, the
    # client's update and the running mean (its momentum and gradients live
    # only inside the compiled local round)
    n_clients = run["cell"]["config"]["protocol"]["clients_per_round"]
    assert len(probes["loop"]) == n_clients
    assert max(probes["loop"]) <= 3 * weights, (probes, weights)


def test_worst_leaf_gap_reads_alike_on_host_and_device(sound):
    _, run, _ = sound
    check = run["cell"]["check"]
    trees = (run["program"][0]["params"], run["reference"][0]["params"], run["params0"])
    on_device = [jax.tree.map(jnp.asarray, t) for t in trees]
    host = check.worst_leaf_gap(*trees)
    assert host == check.worst_leaf_gap(*on_device) == run["readings"]["first_update_gap"]
