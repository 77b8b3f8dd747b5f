"""repro.topo + the "gossip" strategy: graph/mixing invariants, the fused
gossip_mix kernel (allclose vs oracle), carbon reweighting, MixEvent
telemetry, and the FedAvg golden-equivalence anchor."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro import api
from repro.data.pipeline import build_clients
from repro.data.synthetic import MNIST_LIKE, make_image_dataset
from repro.fl.paramspace import ParamSpace
from repro.kernels import ops, ref
from repro.models.resnet import ResNetConfig, init_resnet, resnet_loss
from repro.topo import gossip as gossip_mod
from repro.topo import graph as graph_mod


# ---------------------------------------------------------------------------
# Graphs + Metropolis mixing
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("name", sorted(graph_mod.GRAPHS))
@pytest.mark.parametrize("n", [1, 2, 5, 8, 12])
def test_metropolis_is_symmetric_doubly_stochastic(name, n):
    plan = graph_mod.plan(name, n, rnd=2, seed=7, p=0.5)
    W = np.asarray(plan.mixing, np.float64)
    assert W.shape == (n, n)
    np.testing.assert_allclose(W, W.T, atol=1e-7)
    np.testing.assert_allclose(W.sum(axis=0), 1.0, atol=1e-6)
    np.testing.assert_allclose(W.sum(axis=1), 1.0, atol=1e-6)
    assert (W >= -1e-9).all()
    adj = plan.adjacency
    assert not adj.diagonal().any() and (adj == adj.T).all()
    # zero pattern of W off-diagonal == the communication graph
    off = W.copy()
    np.fill_diagonal(off, 0.0)
    assert ((off > 0) == adj).all()


def test_full_graph_mixing_is_uniform_with_unit_gap():
    plan = graph_mod.plan("full", 8)
    np.testing.assert_allclose(np.asarray(plan.mixing), 1.0 / 8, atol=1e-7)
    assert plan.spectral_gap == pytest.approx(1.0, abs=1e-6)
    assert plan.consensus_rounds() <= 1.0  # one step lands exactly


def test_spectral_gap_orders_topologies_and_counts_edges():
    n = 16
    ring = graph_mod.plan("ring", n)
    torus = graph_mod.plan("torus", n)
    full = graph_mod.plan("full", n)
    # denser graphs mix faster: ring < torus < full
    assert ring.spectral_gap < torus.spectral_gap < full.spectral_gap
    assert ring.n_edges == n and torus.n_edges == 2 * n
    assert full.n_edges == n * (n - 1) // 2
    assert ring.consensus_rounds() > torus.consensus_rounds()
    # every node of the 4x4 torus has 4 neighbors
    assert all(len(nb) == 4 for nb in torus.neighbors)


def test_one_peer_schedule_is_time_varying_and_cycles():
    n = 8  # tau = 3 offsets: 1, 2, 4
    plans = [graph_mod.plan("one_peer", n, rnd=t) for t in range(4)]
    assert not (plans[0].adjacency == plans[1].adjacency).all()
    assert (plans[0].adjacency == plans[3].adjacency).all()  # period tau=3
    for p in plans:
        assert all(len(nb) <= 2 for nb in p.neighbors)  # one peer each way
        assert p.spectral_gap < 1.0  # sparse round: no single-step consensus
    # the union over one full cycle connects the fleet
    union = np.logical_or.reduce([p.adjacency for p in plans[:3]])
    assert graph_mod.is_connected(union)


def test_erdos_is_deterministic_connected_and_round_varying():
    a = graph_mod.erdos_adjacency(12, p=0.3, seed=5, rnd=1)
    b = graph_mod.erdos_adjacency(12, p=0.3, seed=5, rnd=1)
    assert (a == b).all()
    assert graph_mod.is_connected(a)
    # p far below the connectivity threshold still yields a usable graph
    # (ring-union fallback), deterministically
    c = graph_mod.erdos_adjacency(12, p=0.001, seed=5, rnd=0)
    assert graph_mod.is_connected(c)


def test_disconnected_graph_has_zero_gap_and_infinite_consensus():
    adj = np.zeros((4, 4), bool)  # no edges: W = I
    W = graph_mod.metropolis_weights(adj)
    np.testing.assert_allclose(W, np.eye(4), atol=1e-7)
    assert graph_mod.spectral_gap(W) == pytest.approx(0.0, abs=1e-9)
    assert graph_mod.consensus_rounds(W) == float("inf")
    assert not graph_mod.is_connected(adj)


def test_plan_rejects_unknown_graph_and_bad_n():
    with pytest.raises(ValueError, match="unknown graph"):
        graph_mod.plan("smallworld", 8)
    with pytest.raises(ValueError, match="at least one node"):
        graph_mod.plan("ring", 0)


# ---------------------------------------------------------------------------
# gossip_mix kernel vs oracle
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("k,P", [(4, 1000), (6, 2048), (8, 5000)])
def test_gossip_mix_kernel_matches_ref_bitwise(k, P):
    rng = np.random.default_rng(k)
    rows = jnp.asarray(rng.normal(0, 0.5, (k, P)).astype(np.float32))
    W = jnp.asarray(graph_mod.plan("ring", k).mixing)
    out = ops.gossip_mix(rows, W, interpret=True)
    expect = ref.gossip_mix_ref(rows, W)
    assert out.shape == (k, P) and out.dtype == jnp.float32
    # the tiled kernel and the whole-row matmul are different programs that
    # accumulate the k terms in a different order: a few float32 ulps apart
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect), rtol=1e-6, atol=1e-6)


def test_gossip_mix_preserves_average_and_contracts_disagreement():
    """W doubly stochastic -> the fleet average is invariant and the
    consensus distance contracts at >= the spectral gap's rate."""
    rng = np.random.default_rng(0)
    k, P = 8, 4096
    rows = jnp.asarray(rng.normal(0, 1.0, (k, P)).astype(np.float32))
    plan = graph_mod.plan("torus", k)
    pspace = ParamSpace.build({"a": jnp.zeros((P,))})
    mixed = gossip_mod.mix_rows(pspace, rows, jnp.asarray(plan.mixing))
    np.testing.assert_allclose(
        np.asarray(jnp.mean(mixed, 0)), np.asarray(jnp.mean(rows, 0)), atol=1e-5
    )
    pre = gossip_mod.consensus_distance(rows)
    post = gossip_mod.consensus_distance(mixed)
    assert post <= pre * plan.slem * 1.05 + 1e-6


def test_mix_rows_pads_to_blocks_on_kernel_path():
    """``mix_rows`` pads to whole blocks; the kernel's output, sliced back to
    dim columns, is the unpadded reference's."""
    rng = np.random.default_rng(1)
    k, P = 4, 3000  # not a block multiple
    pspace = ParamSpace.build({"a": jnp.zeros((P,))})
    rows = jnp.asarray(rng.normal(0, 1, (k, P)).astype(np.float32))
    W = jnp.asarray(graph_mod.plan("full", k).mixing)
    # force the kernel, which the TPU runs by default
    out = ops.gossip_mix(pspace.pad_rows(rows), W, interpret=True)[:, : pspace.dim]
    np.testing.assert_array_equal(
        np.asarray(out), np.asarray(ref.gossip_mix_ref(rows, W))
    )


# ---------------------------------------------------------------------------
# Carbon-aware reweighting
# ---------------------------------------------------------------------------


def test_carbon_reweight_invariants_and_green_tilt():
    W = graph_mod.plan("full", 5).mixing
    inten = np.asarray([300.0, 120.0, 180.0, 90.0, 240.0])
    Wc = gossip_mod.carbon_reweight(W, inten, beta=0.8)
    assert (Wc >= -1e-7).all()
    np.testing.assert_allclose(Wc.sum(axis=1), 1.0, atol=1e-6)  # row-stochastic
    # greener peers (lower intensity) receive more incoming mass
    col_mass = Wc.sum(axis=0)
    assert col_mass[np.argmin(inten)] > col_mass[np.argmax(inten)]
    # beta=0 is the identity transformation (the equivalence-anchor regime)
    np.testing.assert_array_equal(
        gossip_mod.carbon_reweight(W, inten, beta=0.0), np.asarray(W, np.float32)
    )
    # reweighted matrices lose symmetry; slem still well-defined
    assert 0.0 <= graph_mod.slem(Wc) <= 1.0 + 1e-9


# ---------------------------------------------------------------------------
# GossipStrategy through the Federation API
# ---------------------------------------------------------------------------


def _setup(n_clients=6, equal_shards=False, n_train=360, n_test=128):
    data = make_image_dataset(MNIST_LIKE, seed=1, n_train=n_train, n_test=n_test)
    if equal_shards:
        # equal-size shards make FedAvg's data-size weights uniform — the
        # regime where uniform gossip mixing and Eq. 6 coincide
        parts = [np.arange(i, n_train, n_clients) for i in range(n_clients)]
    else:
        from repro.data.partition import dirichlet_partition

        parts = dirichlet_partition(data["train"]["label"], n_clients, 0.5, seed=1)
    clients = build_clients(data["train"], parts)
    rcfg = ResNetConfig(name="t", widths=(8, 16), depths=(1, 1), in_channels=1,
                        num_classes=10)
    params = init_resnet(jax.random.PRNGKey(0), rcfg)
    task = api.FederatedTask(
        loss_fn=lambda p, b: resnet_loss(p, rcfg, b),
        eval_fn=lambda p, b: resnet_loss(p, rcfg, b)[1],
        params0=params, clients=clients, test_data=data["test"],
    )
    return task


_BASE = dict(n_clients=6, clients_per_round=6, rounds=2, local_steps=2,
             batch_size=16, eval_every=1, seed=3)


def test_gossip_full_uniform_reproduces_sync_fedavg():
    """The golden-equivalence anchor: complete graph (uniform Metropolis
    weights), one mixing step, full participation, equal shards — every
    round ends in consensus at exactly the FedAvg iterate."""
    cfg_g = api.ExperimentConfig(
        training=api.TrainingConfig(**_BASE),
        topology=api.TopologyConfig(mode="gossip", graph="full", mixing_steps=1),
    )
    fed_g = api.Federation(cfg_g, _setup(equal_shards=True))
    h_g = fed_g.run()
    cfg_s = api.ExperimentConfig(training=api.TrainingConfig(**_BASE))
    fed_s = api.Federation(cfg_s, _setup(equal_shards=True))
    h_s = fed_s.run()
    # same selection PRNG schedule -> bitwise-equal cohorts
    assert h_g["selected"] == h_s["selected"]
    np.testing.assert_allclose(h_g["loss"], h_s["loss"], rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(h_g["co2_g"], h_s["co2_g"], rtol=1e-6)
    # consensus-mean rounding is ulp-scale; accuracy is quantized in steps of
    # 1/(eval samples), so a loose atol only tolerates boundary-sample flips
    np.testing.assert_allclose(h_g["acc"], h_s["acc"], atol=2e-3)
    # the decentralized average model IS the FedAvg server model
    pspace = fed_g.ctx.pspace
    mean_row = np.asarray(jnp.mean(fed_g.strategy.node_rows, axis=0))
    server_row = np.asarray(pspace.ravel(fed_s.ctx.server_state.params))
    np.testing.assert_allclose(mean_row, server_row, rtol=1e-4, atol=1e-5)
    # and the fleet is in (float-exact-ish) consensus after every round
    assert all(c < 1e-4 for c in h_g["consensus"])
    assert all(g == pytest.approx(1.0, abs=1e-6) for g in h_g["spectral_gap"])


def test_gossip_ring_runs_with_partial_participation_and_telemetry():
    events = []
    cfg = api.ExperimentConfig(
        training=api.TrainingConfig(**dict(_BASE, clients_per_round=4, rounds=3)),
        topology=api.TopologyConfig(mode="gossip", graph="ring", mixing_steps=2,
                                    carbon_beta=0.5),
        orchestrator=api.OrchestratorConfig(selection="rl_green"),
    )
    h = api.Federation(cfg, _setup(), telemetry=[api.CallbackSink(
        events.append, fields=("round", "consensus", "spectral_gap", "mix_bytes"),
    )]).run()
    assert len(h["round"]) == 3 and len(events) == 3
    # partial participation: non-selected nodes lag -> fleet disagreement > 0
    assert h["final_consensus"] > 0.0
    assert all(b > 0 for b in h["mix_bytes"]) and h["mix_bytes_total"] > 0
    assert all(s == 2 for s in h["mix_steps"])
    # ring on a 4-cohort: gap strictly inside (0, 1)
    assert all(0.0 < g < 1.0 for g in h["spectral_gap"])
    assert np.isfinite(h["reward"]).all()
    assert sorted(h) == sorted(
        list(api.GossipStrategy.history_keys)
        + ["final_acc", "mean_co2_g", "mean_duration_s", "cum_co2_total_g",
           "final_consensus", "mean_spectral_gap", "mix_bytes_total"]
    )


def test_more_mixing_steps_tighten_cohort_consensus():
    def run(steps):
        cfg = api.ExperimentConfig(
            training=api.TrainingConfig(**dict(_BASE, rounds=1)),
            topology=api.TopologyConfig(mode="gossip", graph="ring",
                                        mixing_steps=steps),
        )
        return api.Federation(cfg, _setup()).run()["final_consensus"]

    # full participation + ring: every node mixed, more passes -> tighter
    assert run(4) < run(1)


def test_gossip_config_round_trips_and_builds_from_dict():
    cfg = api.ExperimentConfig(
        training=api.TrainingConfig(**dict(_BASE, rounds=1)),
        topology=api.TopologyConfig(mode="gossip", graph="torus", mixing_steps=3,
                                    gossip_p=0.6, carbon_beta=0.2),
    )
    import json

    restored = api.ExperimentConfig.from_dict(json.loads(json.dumps(cfg.to_dict())))
    assert restored == cfg
    fed = api.build(restored.to_dict(), _setup())
    assert fed.strategy.name == "gossip"
    h = fed.run()
    assert len(h["round"]) == 1


def test_gossip_validate_rejects_incompatible_configs():
    task = _setup()

    def build(**kw):
        topo = dict(mode="gossip")
        topo.update(kw.pop("topo", {}))
        cfg = api.ExperimentConfig(
            training=api.TrainingConfig(**dict(_BASE, **kw.pop("train", {}))),
            topology=api.TopologyConfig(**topo), **kw,
        )
        return api.Federation(cfg, task)

    with pytest.raises(ValueError, match="needs a server"):
        build(train=dict(algorithm="scaffold"))
    with pytest.raises(ValueError, match="needs a server"):
        build(train=dict(algorithm="fedadam"))
    from repro.privacy.dp import DPConfig

    with pytest.raises(ValueError, match="no aggregation site"):
        build(privacy=api.PrivacyConfig(secure_agg=True))
    with pytest.raises(ValueError, match="no aggregation site"):
        build(privacy=api.PrivacyConfig(dp=DPConfig(clip=1.0, sigma=1.0)))
    with pytest.raises(ValueError, match="unsharded"):
        build(train=dict(sharded=True))
    with pytest.raises(ValueError, match="unknown graph"):
        build(topo=dict(graph="hypercube"))
    with pytest.raises(ValueError, match="mixing_steps"):
        build(topo=dict(mixing_steps=0))
    with pytest.raises(ValueError, match="gossip_p"):
        build(topo=dict(graph="erdos", gossip_p=0.0))
    with pytest.raises(ValueError, match="carbon_beta"):
        build(topo=dict(carbon_beta=-0.1))


def test_gossip_rejects_hand_composed_privacy_pipeline():
    """validate() rejects the privacy flags; a pipeline passed explicitly
    via Federation(privacy=...) must not be silently skipped either."""
    cfg = api.ExperimentConfig(
        training=api.TrainingConfig(**dict(_BASE, rounds=1)),
        topology=api.TopologyConfig(mode="gossip"),
    )
    pipe = api.PrivacyPipeline(stages=(api.ClipStage(1.0),), weighting="uniform")
    with pytest.raises(ValueError, match="would not run"):
        api.Federation(cfg, _setup(), privacy=pipe)


def test_unknown_strategy_error_lists_registry():
    task = _setup()
    cfg = api.ExperimentConfig(training=api.TrainingConfig(**dict(_BASE, rounds=1)))
    with pytest.raises(ValueError) as ei:
        api.Federation(cfg, task, strategy="nope")
    msg = str(ei.value)
    for name in api.strategy_names():
        assert name in msg
    assert "register_strategy" in msg
    assert "gossip" in api.strategy_names()


def test_mix_event_history_row_and_recorder():
    ev = api.MixEvent(round=0, acc=0.4, loss=1.2, co2_g=9.0, cum_co2_g=9.0,
                      duration_s=2.0, reward=0.0, eps_spent=0.0, selected=(0, 2),
                      consensus=0.5, spectral_gap=0.25, mix_steps=3,
                      mix_bytes=1024.0)
    row = ev.history_row()
    assert row["consensus"] == 0.5 and row["spectral_gap"] == 0.25
    assert row["mix_steps"] == 3 and row["mix_bytes"] == 1024.0
    rec = api.HistoryRecorder(api.GossipStrategy.history_keys)
    rec.emit(ev)
    assert rec.history["consensus"] == [0.5] and rec.history["round"] == [0]
