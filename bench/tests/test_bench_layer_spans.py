"""The readers of the program's own spans and program names
(``batch_assembly_ms``, ``trainer_device_ms``, ``loop_idle_ms``) on
hand-made rounds and trace summaries, the program names they look for in
compiled programs, and a traced run of the cell at reduced width on the CPU."""
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness
from bench.configs import resnet_tiny
from bench.spans import Span
from bench.tests import tiny
from bench.trace_reduce import Summary

CELL = "cifar10-secagg-l8"
METRICS = tiny.ROOT / "bench" / "metrics"


def _reader(name):
    return harness.load_module(METRICS / f"{name}.py").read


def _round(t0, *children):
    """A ``round`` span of 1 s at ``t0`` and its (name, start, end, depth) children."""
    inner = [Span(n, t0 + s, t0 + e, d, {}) for n, s, e, d in children]
    return Span("round", t0, t0 + 1.0, 1, {}), inner


ROUNDS = [
    _round(0.0, ("select", 0.0, 0.05, 2), ("train", 0.05, 0.8, 2),
           ("cohort_inputs", 0.05, 0.14, 3), ("aggregate", 0.8, 0.85, 2),
           ("server_update", 0.83, 0.84, 3), ("accounting", 0.85, 0.95, 2),
           ("policy_update", 0.95, 1.0, 2)),
    _round(1.0, ("select", 0.0, 0.05, 2), ("train", 0.05, 0.8, 2),
           ("cohort_inputs", 0.05, 0.12, 3), ("accounting", 0.85, 0.95, 2)),
]


def _summary(module_ns=None, idle_ns_by_span=None):
    return Summary(window_ns=2e9, busy_ns={"/device:TPU:0": 1.5e9}, op_ns={},
                   module_ns=module_ns or {}, kernel_ns={}, kernel_runs={},
                   idle_ns_by_span=idle_ns_by_span or {}, gaps=[])


def _run(rounds=ROUNDS, summary=None, chips=1):
    return types.SimpleNamespace(rounds=rounds, summary=summary, chips=chips)


def test_batch_assembly_is_the_mean_cohort_inputs_span():
    assert _reader("batch_assembly_ms")(_run()) == pytest.approx(1e3 * (0.09 + 0.07) / 2)


@pytest.mark.parametrize("chips", [1, 4])
def test_trainer_device_time_per_round_and_chip(chips):
    s = _summary(module_ns={"jit_cohort_train": 1.4e9 * chips, "jit_evaluate": 2e8})
    assert _reader("trainer_device_ms")(_run(summary=s, chips=chips)) == pytest.approx(700.0)


def test_loop_idle_counts_the_round_loops_own_spans():
    idle = {"round": 40e6, "select": 20e6, "aggregate": 30e6, "server_update": 2e6,
            "accounting": 10e6, "policy_update": 4e6,
            # not the round loop's: batch assembly, training, evaluation, between rounds
            "cohort_inputs": 90e6, "train": 7e6, "eval": 8e6, "run": 1e6, "outside spans": 5e6}
    got = _reader("loop_idle_ms")(_run(summary=_summary(idle_ns_by_span=idle)))
    assert got == pytest.approx((40 + 20 + 30 + 2 + 10 + 4) / 2)


@pytest.mark.parametrize("name, run", [
    # a program without the span, as before it was added
    ("batch_assembly_ms", _run(rounds=[_round(0.0, ("train", 0.05, 0.8, 2))])),
    ("batch_assembly_ms", _run(rounds=[])),
    # no trace, a trace without the program (as under its former name), no rounds
    ("trainer_device_ms", _run(summary=None)),
    ("trainer_device_ms", _run(summary=_summary(module_ns={"jit_run": 1e9}))),
    ("trainer_device_ms", _run(rounds=[], summary=_summary(module_ns={"jit_cohort_train": 1e9}))),
    ("loop_idle_ms", _run(summary=None)),
    ("loop_idle_ms", _run(rounds=[], summary=_summary(idle_ns_by_span={"round": 1e6}))),
])
def test_nothing_to_read_gives_nothing(name, run):
    assert _reader(name)(run) is None


# ---------------------------------------------------------------------------
# the program names the readers and the trace's breakdown rely on
# ---------------------------------------------------------------------------


def _federation():
    from repro.api import ExperimentConfig, Federation, FederatedTask, TrainingConfig
    from repro.data.partition import dirichlet_partition
    from repro.data.pipeline import build_clients
    from repro.data.synthetic import MNIST_LIKE, make_image_dataset
    from repro.models.resnet import ResNetConfig, init_resnet, resnet_loss

    data = make_image_dataset(MNIST_LIKE, seed=1, n_train=128, n_test=32)
    parts = dirichlet_partition(data["train"]["label"], 4, 0.5, seed=1)
    rcfg = ResNetConfig(name="t", widths=(4,), depths=(1,), in_channels=1, num_classes=10)
    task = FederatedTask(
        loss_fn=lambda p, b: resnet_loss(p, rcfg, b),
        eval_fn=lambda p, b: resnet_loss(p, rcfg, b)[1],
        params0=init_resnet(jax.random.PRNGKey(0), rcfg),
        clients=build_clients(data["train"], parts), test_data=data["test"],
    )
    cfg = ExperimentConfig(training=TrainingConfig(
        n_clients=4, clients_per_round=2, rounds=1, local_steps=1, batch_size=8))
    return Federation(cfg, task)


@pytest.fixture(scope="module")
def fed():
    return _federation()


def _lowered(fed, program):
    from repro.fl import client as client_mod

    ctx = fed.ctx
    params = ctx.server_state.params
    batches, mus, corrections = ctx._cohort_inputs([0, 1], 0)
    if program == "cohort_train":
        return ctx.cohort_trainer.lower(params, batches, mus, corrections)
    if program == "cohort_train_rows":
        rows = jnp.stack([ctx.pspace.ravel(params)] * 2)
        trainer = client_mod.make_gossip_cohort_trainer(ctx.loss_fn, ctx.local_opt, ctx.pspace)
        return trainer.lower(rows, batches, mus, corrections)
    if program == "server_update":
        return ctx.server_apply.lower(ctx.server_state, params)
    assert program == "evaluate"
    batch = {k: jnp.asarray(np.asarray(v)[:8]) for k, v in ctx.test_data.items()}
    return ctx.eval_fn.lower(params, batch)


@pytest.mark.parametrize("program", ["cohort_train", "cohort_train_rows", "server_update",
                                     "evaluate"])
def test_program_names(fed, program):
    text = _lowered(fed, program).compile().as_text()
    assert f"jit_{program}" in text.splitlines()[0], text.splitlines()[0]


def test_flops_probe_is_named_in_the_compile_log(caplog):
    """The FLOP-count compile at set-up is told apart from the trainer."""
    with caplog.at_level("WARNING"), jax.log_compiles(True):
        _federation()
    compiled = [r.getMessage() for r in caplog.records if "Compiling jit(" in r.getMessage()]
    assert any("jit(flops_probe)" in m for m in compiled), compiled


# ---------------------------------------------------------------------------
# a traced run of the cell at reduced width: the program's spans reach the
# readers through the benchmark's tracer
# ---------------------------------------------------------------------------


def test_traced_run_reads_batch_assembly(monkeypatch):
    # the CPU has no published peaks; no reader that uses them finds a
    # device plane to read here
    monkeypatch.setattr(harness, "peaks", lambda kind: {})
    result, _ = harness.run_cell(tiny.ROOT, CELL, 2 ** 33 + 29, 0.2, True, require_tpu=False,
                                 overrides=tiny.overrides(resnet_tiny))
    metrics = result["metrics"]
    # the CPU trace has no TPU plane, so the device metrics read nothing
    assert set(metrics) == {"round_self_ms", "train_ms", "eval_ms", "batch_assembly_ms"}
    assert 0 < metrics["batch_assembly_ms"]["value"] < metrics["train_ms"]["value"]
    assert result["correct"] is True
