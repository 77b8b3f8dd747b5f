"""Async + hierarchical runtime: sync-equivalence anchor, staleness weights,
hierarchy round-trip, and the fused Pallas staleness_agg kernel."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import api
from repro.core import scheduler
from repro.data.partition import dirichlet_partition
from repro.data.pipeline import build_clients
from repro.data.synthetic import MNIST_LIKE, make_image_dataset
from repro.fl.hierarchy import assign_regions, staleness_weight, subfleet
from repro.kernels import ops, ref
from repro.models.resnet import ResNetConfig, init_resnet, resnet_loss


def _setup(n_clients=6, n_train=800, n_test=512):
    data = make_image_dataset(MNIST_LIKE, seed=1, n_train=n_train, n_test=n_test)
    parts = dirichlet_partition(data["train"]["label"], n_clients, 0.5, seed=1)
    clients = build_clients(data["train"], parts)
    rcfg = ResNetConfig(name="t", widths=(8, 16), depths=(1, 1), in_channels=1, num_classes=10)
    params = init_resnet(jax.random.PRNGKey(0), rcfg)
    loss_fn = lambda p, b: resnet_loss(p, rcfg, b)
    eval_fn = lambda p, b: resnet_loss(p, rcfg, b)[1]
    return api.FederatedTask(loss_fn, eval_fn, params, clients, data["test"])


def _config(mode="sync", selection="random", privacy=None, topology=None, **training):
    return api.ExperimentConfig(
        training=api.TrainingConfig(**training),
        privacy=privacy or api.PrivacyConfig(),
        topology=api.TopologyConfig(mode=mode, **(topology or {})),
        orchestrator=api.OrchestratorConfig(selection=selection),
    )


# ---------------------------------------------------------------------------
# Pallas staleness_agg kernel vs jnp.einsum reference
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("k,P", [(4, 1000), (8, 5000), (16, 2048), (3, 7777), (1, 129)])
def test_staleness_agg_kernel_matches_einsum(k, P):
    rng = np.random.default_rng(k * 31 + P)
    deltas = jnp.asarray(rng.normal(0, 0.1, (k, P)).astype(np.float32))
    taus = rng.integers(0, 8, k)
    weights = jnp.asarray(staleness_weight(taus).astype(np.float32))
    out = ops.staleness_aggregate(deltas, weights, interpret=True)
    expect = ref.staleness_aggregate_ref(deltas, weights)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect), atol=1e-5, rtol=1e-5)


def test_staleness_agg_kernel_block_sizes():
    rng = np.random.default_rng(0)
    deltas = jnp.asarray(rng.normal(0, 0.1, (5, 3001)).astype(np.float32))
    weights = jnp.asarray(rng.uniform(0.1, 1.0, 5).astype(np.float32))
    expect = ref.staleness_aggregate_ref(deltas, weights)
    for bp in (256, 1024, 4096):
        out = ops.staleness_aggregate(deltas, weights, block_p=bp, interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(expect), atol=1e-5)


# ---------------------------------------------------------------------------
# Staleness weights
# ---------------------------------------------------------------------------


def test_staleness_weight_shape_and_monotone():
    taus = np.arange(0, 12)
    w = staleness_weight(taus, cap=10)
    assert w[0] == 1.0  # fresh delta keeps full weight
    assert np.all(np.diff(w) <= 0)  # never up-weights staler deltas
    np.testing.assert_allclose(w, 1.0 / np.sqrt(1.0 + np.minimum(taus, 10)))
    # cap clamps: tau=11 weighs the same as tau=10
    assert w[-1] == w[-2]


def test_observe_staleness_ema_and_straggler_demotion():
    """The straggler EMA only touches flushed providers, and a high EMA
    demotes a provider out of the greedy selection."""
    from repro.core import orchestrator as orch
    from repro.core.carbon import make_fleet

    st = orch.init_state(4)
    np.testing.assert_array_equal(np.asarray(st.stale_ema), 0.0)
    mask = np.array([True, False, True, False])
    st = orch.observe_staleness(st, mask, np.array([5.0, 9.0, 0.0, 9.0]))
    np.testing.assert_allclose(
        np.asarray(st.stale_ema), [(1 - orch.STALE_EMA_BETA) * 5.0, 0.0, 0.0, 0.0]
    )
    # chronic straggler: EMA so high the demotion dominates the 0.15 jitter
    st = st._replace(
        stale_ema=jnp.asarray([10.0, 0.0, 0.0, 0.0]), eps=jnp.float32(0.0)
    )
    fleet = make_fleet(jax.random.PRNGKey(0), 4)
    inten = jnp.ones(4, jnp.float32) * 100.0
    sel, _ = orch.select(jax.random.PRNGKey(1), st, fleet, inten, 2,
                         use_green=False, use_priority=False)
    assert not bool(sel[0])  # straggler not selected
    assert int(jnp.sum(sel)) == 2
    # zero EMA is a bitwise no-op on the scores (sync-equivalence anchor)
    st0 = orch.init_state(4)._replace(eps=jnp.float32(0.0))
    sel_a, _ = orch.select(jax.random.PRNGKey(2), st0, fleet, inten, 2,
                           use_green=False, use_priority=False)
    sel_b, _ = orch.select(jax.random.PRNGKey(2), st0, fleet, inten, 2,
                           use_green=False, use_priority=False)
    np.testing.assert_array_equal(np.asarray(sel_a), np.asarray(sel_b))


# ---------------------------------------------------------------------------
# Hierarchy: region assignment + sub-fleet views
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n_regions", [1, 2, 3, 5])
def test_assign_regions_partitions_fleet(n_regions):
    from repro.core.carbon import make_fleet

    fleet = make_fleet(jax.random.PRNGKey(0), 17)
    regions = assign_regions(fleet, n_regions)
    allids = np.concatenate(regions)
    assert len(allids) == 17 and len(np.unique(allids)) == 17
    assert all(len(r) > 0 for r in regions)
    sub = subfleet(fleet, regions[0])
    np.testing.assert_array_equal(
        np.asarray(sub.capability), np.asarray(fleet.capability)[regions[0]]
    )


def test_topk_mask_exact_k_on_ties():
    # tied scores used to inflate the cohort beyond k (scores >= kth)
    mask = scheduler.topk_mask(jnp.ones(10), 3)
    assert int(jnp.sum(mask)) == 3
    mask = scheduler.topk_mask(jnp.asarray([1.0, 2.0, 2.0, 2.0, 0.0]), 2)
    assert int(jnp.sum(mask)) == 2


# ---------------------------------------------------------------------------
# Sync-equivalence anchor: zero latency spread, K = clients_per_round,
# one region, edge_sync_every=1 reproduces the synchronous engine.
# ---------------------------------------------------------------------------


def _equiv_runs(**variant):
    base = dict(n_clients=6, clients_per_round=3, rounds=4, local_steps=2,
                batch_size=16, eval_every=2, seed=3, **variant)
    task = _setup()
    h_s = api.Federation(_config(**base), task).run()
    h_a = api.Federation(_config(mode="async_hier", topology=dict(latency_spread=0.0), **base),
                         task).run()
    return h_s, h_a


@pytest.mark.parametrize("variant", [
    dict(algorithm="fedavg", selection="random"),
    dict(algorithm="fedadam", selection="rl_green", server_lr=0.02),
])
def test_sync_equivalence(variant):
    h_s, h_a = _equiv_runs(**variant)
    assert abs(h_s["final_acc"] - h_a["final_acc"]) < 1e-3
    np.testing.assert_allclose(h_s["acc"], h_a["acc"], atol=1e-3)
    np.testing.assert_allclose(h_s["loss"], h_a["loss"], atol=1e-5)
    assert h_s["selected"] == h_a["selected"]
    assert all(s == 0.0 for s in h_a["staleness"])  # nothing is ever stale


def test_sync_equivalence_secure_agg():
    """1 region + masked-ring aggregation == the flat secure-agg engine."""
    h_s, h_a = _equiv_runs(algorithm="fedavg", selection="random",
                           privacy=api.PrivacyConfig(secure_agg=True, sa_bits=24))
    assert abs(h_s["final_acc"] - h_a["final_acc"]) < 1e-3
    np.testing.assert_allclose(h_s["loss"], h_a["loss"], atol=1e-5)


# ---------------------------------------------------------------------------
# General async behavior
# ---------------------------------------------------------------------------


def test_async_staleness_emerges_with_overlap():
    """Double concurrency + small buffer: some deltas must arrive stale."""
    cfg = _config(mode="async_hier", selection="random", algorithm="fedavg", n_clients=6,
                  clients_per_round=3, rounds=6, local_steps=2, batch_size=16,
                  eval_every=3, seed=3,
                  topology=dict(latency_spread=1.0, buffer_k=2, concurrency=6, n_regions=2,
                                edge_sync_every=2))
    task = _setup()
    h = api.Federation(cfg, task).run()
    assert len(h["acc"]) == 6
    assert max(h["staleness"]) > 0.0
    assert sorted(h["buffer_flushes"]) == [0, 1]
    assert sum(h["buffer_flushes"].values()) == 6
    assert set(h["region"]) == {0, 1}
    # regional CO2 decomposes the total
    assert sum(h["co2_by_region_g"].values()) == pytest.approx(h["cum_co2_total_g"])
    assert np.isfinite(h["final_acc"])
    # every flush stays inside its region's client set
    regions = assign_regions(
        api.Federation(cfg, task).ctx.fleet, cfg.topology.n_regions,
    )
    for rid, sel in zip(h["region"], h["selected"]):
        assert set(sel) <= set(regions[rid].tolist())


def test_global_staleness_version_accounting():
    """Multi-region runs interleave edge→global syncs: the server's round
    counter is exactly the global version, every region's last-sync marker
    trails it, and the straggler EMA picked up the emergent staleness."""
    cfg = _config(mode="async_hier", selection="rl_green", algorithm="fedavg", n_clients=6,
                  clients_per_round=3, rounds=6, local_steps=2, batch_size=16,
                  eval_every=3, seed=3,
                  topology=dict(latency_spread=1.0, buffer_k=2, concurrency=6, n_regions=2,
                                edge_sync_every=2))
    fed = api.Federation(cfg, _setup())
    h = fed.run()
    strategy = fed.strategy
    assert strategy.global_version == int(fed.ctx.server_state.round)  # one bump per apply
    assert strategy.global_version >= 2  # both regions synced at least once
    for reg in strategy.regions:
        assert 0 < reg.synced_version <= strategy.global_version
    # overlap produced staleness, so some straggler EMA must be non-zero
    assert max(h["staleness"]) > 0.0
    assert any(float(jnp.max(reg.orch_state.stale_ema)) > 0.0 for reg in strategy.regions)


def test_async_multi_flush_per_wave_derives_fresh_keys():
    """buffer_k < wave size: one wave triggers several flushes, which must
    consume distinct mask/noise keys (fold_in per trigger) — the first flush
    keeps the wave key verbatim so the sync-equivalence anchor stays exact."""
    cfg = _config(mode="async_hier", selection="random", algorithm="fedavg", n_clients=6,
                  clients_per_round=4, rounds=4, local_steps=2, batch_size=16,
                  eval_every=4, seed=5,
                  privacy=api.PrivacyConfig(secure_agg=True, sa_bits=24),
                  topology=dict(latency_spread=0.0, buffer_k=2, concurrency=4))
    fed = api.Federation(cfg, _setup(n_train=400, n_test=64))
    h = fed.run()
    assert len(h["acc"]) == 4
    # every wave of 4 produced exactly 2 flushes through the K=2 buffer
    counts = list(fed.strategy.regions[0].wave_flushes.values())
    assert counts and all(c == 2 for c in counts)
    assert np.isfinite(h["final_acc"])


def test_async_rejects_sync_only_algorithms():
    cfg = _config(mode="async_hier", algorithm="scaffold", n_clients=6, clients_per_round=2,
                  rounds=1)
    with pytest.raises(ValueError, match="scaffold"):
        api.Federation(cfg, _setup(n_train=200, n_test=64))
