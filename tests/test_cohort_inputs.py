"""Device-resident cohort inputs: one batch schedule as indices, the
training data uploaded once, each round's batches gathered on the device."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import api, obs
from repro.api.runtime import RuntimeContext
from repro.data.partition import dirichlet_partition
from repro.data.pipeline import ClientDataset, build_clients
from repro.data.synthetic import MNIST_LIKE, make_image_dataset
from repro.models.resnet import ResNetConfig, init_resnet, resnet_loss

N_CLIENTS, K, STEPS, BATCH = 6, 3, 2, 16


def _reference_stacked_steps(client: ClientDataset, batch_size, n_steps, round_idx):
    """The host batch schedule as it was written before the index split:
    shuffled epochs, cycled, with replacement for a shard under one batch."""
    out = []
    epoch = 0
    while len(out) < n_steps:
        rng = np.random.default_rng(
            (client.seed * 1_000_003 + round_idx * 131 + epoch) & 0x7FFFFFFF)
        order = rng.permutation(client.indices)
        n = len(order) - (len(order) % batch_size)
        if n == 0:
            order = rng.choice(client.indices, batch_size, replace=True)
            n = batch_size
        for i in range(0, n, batch_size):
            out.append({k: v[order[i:i + batch_size]] for k, v in client.data.items()})
            if len(out) >= n_steps:
                break
        epoch += 1
    return {k: np.stack([b[k] for b in out]) for k in out[0]}


def _data(seed=1, n_train=256):
    return make_image_dataset(MNIST_LIKE, seed=seed, n_train=n_train, n_test=64)


def _task(clients, test):
    rcfg = ResNetConfig(name="t", widths=(4,), depths=(1,), in_channels=1, num_classes=10)
    return api.FederatedTask(
        loss_fn=lambda p, b: resnet_loss(p, rcfg, b),
        eval_fn=lambda p, b: resnet_loss(p, rcfg, b)[1],
        params0=init_resnet(jax.random.PRNGKey(0), rcfg),
        clients=clients, test_data=test,
    )


def _cfg(**topology):
    return api.ExperimentConfig(
        training=api.TrainingConfig(n_clients=N_CLIENTS, clients_per_round=K, rounds=2,
                                    local_steps=STEPS, batch_size=BATCH, eval_every=1,
                                    seed=3),
        topology=api.TopologyConfig(**topology))


@pytest.fixture(scope="module")
def shared():
    data = _data()
    parts = dirichlet_partition(data["train"]["label"], N_CLIENTS, 0.5, seed=1)
    clients = build_clients(data["train"], parts)
    return clients, RuntimeContext(_cfg(), _task(clients, data["test"]))


def _host_stack(clients, sel, step):
    per = [_reference_stacked_steps(clients[ci], BATCH, STEPS, step) for ci in sel]
    return {k: np.stack([b[k] for b in per]) for k in per[0]}


def _assert_bitwise(got, want):
    assert set(got) == set(want)
    for k in want:
        expect = jnp.asarray(want[k])  # the dtype the host path put on the device
        assert got[k].shape == expect.shape and got[k].dtype == expect.dtype, k
        np.testing.assert_array_equal(np.asarray(got[k]), np.asarray(expect))


# ---------------------------------------------------------------------------
# one schedule: step_indices gathered on the host is the old stacked_steps
# ---------------------------------------------------------------------------

_SCHEDULES = {
    # shard size, batch, steps: several batches an epoch
    "many_batches": (200, 16, 4),
    # 3 batches an epoch, 8 steps: cycles into further epochs
    "cycles_epochs": (50, 16, 8),
    # fewer samples than one batch: sampled with replacement
    "tiny_with_replacement": (5, 16, 3),
}


@pytest.mark.parametrize("case", sorted(_SCHEDULES))
@pytest.mark.parametrize("round_idx", [0, 1, 7])
def test_step_indices_match_the_host_schedule(case, round_idx):
    n, batch, steps = _SCHEDULES[case]
    data = {"x": np.random.default_rng(0).standard_normal((300, 4, 3)).astype(np.float32),
            "y": np.arange(300, dtype=np.int64)}
    client = ClientDataset(data, np.arange(40, 40 + n), seed=11)
    idx = client.step_indices(batch, steps, round_idx)
    assert idx.shape == (steps, batch) and idx.dtype == np.int32
    want = _reference_stacked_steps(client, batch, steps, round_idx)
    got = client.stacked_steps(batch, steps, round_idx)
    for k in want:
        np.testing.assert_array_equal(got[k], want[k])
        np.testing.assert_array_equal(data[k][idx], want[k])


# ---------------------------------------------------------------------------
# the device gather gives the host stack's arrays, bitwise
# ---------------------------------------------------------------------------


class _Recorder:
    """Stands in for a trainer and keeps the inputs it was handed."""

    def __call__(self, first, batches, mus, corrections):
        self.inputs = (first, batches, mus, corrections)
        return "trained"


@pytest.mark.parametrize("step", [0, 1, 5])
def test_cohort_inputs_match_the_host_stack(shared, step):
    clients, ctx = shared
    sel = np.array([4, 0, 2])
    batches, mus, corrections = ctx._cohort_inputs(sel, step)
    _assert_bitwise(batches, _host_stack(clients, sel, step))
    np.testing.assert_array_equal(np.asarray(mus), np.zeros(K, np.float32))
    for z in jax.tree.leaves(corrections):
        assert z.shape[0] == K and not np.any(np.asarray(z))


def test_sync_trainer_receives_the_host_stack(shared, monkeypatch):
    clients, ctx = shared
    rec = _Recorder()
    monkeypatch.setattr(ctx, "cohort_trainer", rec)
    sel = np.array([1, 3, 5])
    params = ctx.server_state.params
    assert ctx.train_cohort(params, sel, 2) == "trained"
    assert rec.inputs[0] is params
    _assert_bitwise(rec.inputs[1], _host_stack(clients, sel, 2))


def test_row_trainer_receives_the_host_stack(shared, monkeypatch):
    clients, ctx = shared
    rec = _Recorder()
    monkeypatch.setattr(ctx, "_row_trainer", rec)
    sel = np.array([5, 2, 0])
    rows = jnp.zeros((K, ctx.pspace.dim), jnp.float32)
    assert ctx.train_cohort_rows(rows, sel, 3) == "trained"
    _assert_bitwise(rec.inputs[1], _host_stack(clients, sel, 3))


def test_zero_corrections_are_built_once_per_cohort_size(shared):
    _, ctx = shared
    first = ctx._cohort_inputs([0, 1, 2], 0)[2]
    again = ctx._cohort_inputs([3, 4, 5], 1)[2]
    assert first is again
    pair = ctx._cohort_inputs([0, 1], 0)[2]
    assert pair is not first
    assert all(z.shape[0] == 2 for z in jax.tree.leaves(pair))


def test_clients_on_different_data_gather_their_own_rows():
    a, b = _data(seed=1, n_train=128), _data(seed=2, n_train=96)
    clients = (build_clients(a["train"], [np.arange(0, 60), np.arange(60, 128),
                                          np.arange(10, 40)], seed=0)
               + build_clients(b["train"], [np.arange(0, 50), np.arange(50, 96),
                                            np.arange(5, 9)], seed=3))
    ctx = RuntimeContext(_cfg(), _task(clients, a["test"]))
    assert list(ctx._offsets) == [0, 0, 0, 128, 128, 128]
    assert len(ctx._store["image"]) == 128 + 96
    sel = np.array([3, 0, 5, 4, 1])
    batches, _, _ = ctx._cohort_inputs(sel, 1)
    _assert_bitwise(batches, _host_stack(clients, sel, 1))


def test_the_store_is_uploaded_once():
    data = _data()
    parts = dirichlet_partition(data["train"]["label"], N_CLIENTS, 0.5, seed=1)
    fed = api.Federation(_cfg(), _task(build_clients(data["train"], parts), data["test"]))
    ctx = fed.ctx
    store = dict(ctx._store)
    pointers = {k: v.unsafe_buffer_pointer() for k, v in store.items()}
    fed.run()
    assert all(ctx._store[k] is v for k, v in store.items())
    assert {k: v.unsafe_buffer_pointer() for k, v in ctx._store.items()} == pointers
    # one gather program serves every round
    assert ctx._gather._cache_size() == 1


@pytest.mark.parametrize("mode", ["sync", "gossip"])
def test_cohort_inputs_span_counts_the_index_upload(mode):
    data = _data()
    parts = dirichlet_partition(data["train"]["label"], N_CLIENTS, 0.5, seed=1)
    topology = {"sync": {}, "gossip": dict(mode="gossip", graph="ring", mixing_steps=1)}[mode]
    tracer = obs.Tracer()
    api.Federation(_cfg(**topology), _task(build_clients(data["train"], parts), data["test"]),
                   tracer=tracer).run()
    spans = [s for s in tracer.spans if s.name == "cohort_inputs"]
    assert len(spans) == 2
    # k x local steps x batch int32 indices, and nothing else
    assert all(s.attrs["h2d_bytes"] == K * STEPS * BATCH * 4 for s in spans)


def test_gather_is_named_in_the_trace(shared):
    _, ctx = shared
    idx = np.zeros((K, STEPS, BATCH), np.int32)
    text = ctx._gather.lower(ctx._store, idx).compile().as_text()
    assert "jit_cohort_batches" in text.splitlines()[0]
