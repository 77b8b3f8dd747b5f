"""FL engine: algorithms, aggregation equivalences, end-to-end mini-simulation."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.data.partition import dirichlet_partition, label_histogram
from repro.data.pipeline import build_clients
from repro.data.synthetic import MNIST_LIKE, make_image_dataset
from repro.fl import client as client_mod
from repro.fl import server as server_mod
from repro.fl.paramspace import ParamSpace
from repro.fl.simulation import FLConfig, Simulation
from repro.launch import cohort as cohort_mod
from repro.models.resnet import ResNetConfig, init_resnet, resnet_loss
from repro.optim import optimizers as opt_mod
from repro.utils import tree_ravel


def _setup(n_clients=6, n_train=600):
    data = make_image_dataset(MNIST_LIKE, seed=1, n_train=n_train, n_test=200)
    parts = dirichlet_partition(data["train"]["label"], n_clients, 0.5, seed=1)
    clients = build_clients(data["train"], parts)
    rcfg = ResNetConfig(name="t", widths=(8, 16), depths=(1, 1), in_channels=1, num_classes=10)
    params = init_resnet(jax.random.PRNGKey(0), rcfg)
    loss_fn = lambda p, b: resnet_loss(p, rcfg, b)
    eval_fn = lambda p, b: resnet_loss(p, rcfg, b)[1]
    return data, clients, params, loss_fn, eval_fn


def test_dirichlet_partition_covers_everything():
    labels = np.random.default_rng(0).integers(0, 10, 2000)
    parts = dirichlet_partition(labels, 8, 0.5, seed=0)
    allidx = np.concatenate(parts)
    assert len(allidx) == 2000 and len(np.unique(allidx)) == 2000
    hist = label_histogram(labels, parts, 10)
    assert hist.sum() == 2000
    # non-IID: per-client label distributions differ substantially
    p = hist / hist.sum(1, keepdims=True)
    assert np.mean(np.std(p, axis=0)) > 0.02


def test_local_trainer_reduces_loss():
    _, clients, params, loss_fn, _ = _setup()
    opt = opt_mod.momentum(0.05, beta=0.9)
    tr = client_mod.make_local_trainer(loss_fn, opt)
    batches = clients[0].stacked_steps(16, 6, 0)
    batches = {k: jnp.asarray(v) for k, v in batches.items()}
    res = tr(params, batches, jnp.float32(0.0), client_mod.zero_correction(params))
    assert float(res.loss_last) < float(res.loss_first)
    flat, _ = tree_ravel(res.delta)
    assert float(jnp.linalg.norm(flat)) > 0


def test_fedprox_mu_shrinks_delta():
    _, clients, params, loss_fn, _ = _setup()
    opt = opt_mod.momentum(0.05, beta=0.9)
    tr = client_mod.make_local_trainer(loss_fn, opt)
    batches = {k: jnp.asarray(v) for k, v in clients[0].stacked_steps(16, 6, 0).items()}
    zc = client_mod.zero_correction(params)
    d0 = tr(params, batches, jnp.float32(0.0), zc).delta
    d1 = tr(params, batches, jnp.float32(10.0), zc).delta
    n0 = float(jnp.linalg.norm(tree_ravel(d0)[0]))
    n1 = float(jnp.linalg.norm(tree_ravel(d1)[0]))
    assert n1 < n0  # strong proximal pull keeps w near w_t (Eq. 7)


def _cohort_inputs(clients, params, k=3, steps=2, batch=8):
    """k clients' stacked batches, distinct FedProx mus and small distinct
    SCAFFOLD-style corrections, so every per-client input matters."""
    batches = [clients[i].stacked_steps(batch, steps, 0) for i in range(k)]
    batches = {key: jnp.stack([jnp.asarray(b[key]) for b in batches]) for key in batches[0]}
    mus = jnp.asarray([0.0, 0.01, 0.1][:k], jnp.float32)
    keys = jax.random.split(jax.random.PRNGKey(5), len(jax.tree.leaves(params)))
    corrections = jax.tree.unflatten(jax.tree.structure(params), [
        1e-3 * jax.random.normal(kk, (k,) + x.shape) for kk, x in zip(keys, jax.tree.leaves(params))])
    return batches, mus, corrections


@pytest.mark.parametrize("kind", ["sync", "gossip_rows", "sharded_one_device"])
def test_cohort_trainer_is_dense_and_matches_a_client_loop(kind):
    """Each cohort trainer trains its clients one after another, so the
    program holds dense convolutions only (a vmap over k weight copies
    makes grouped ones), and each client's row and losses are those of the
    single-client trainer run on its own: the same float32 ops, so only
    the summation order may differ (atol 1e-6 on rows of at most 0.11;
    the gaps read 3e-8)."""
    _, clients, params, loss_fn, _ = _setup()
    opt = opt_mod.momentum(0.05, beta=0.9)
    pspace = ParamSpace.build(params)
    batches, mus, corrections = _cohort_inputs(clients, params)
    k = mus.shape[0]
    if kind == "gossip_rows":
        # every node starts from a model of its own
        base = pspace.ravel(params)
        first = base + 1e-3 * jax.random.normal(jax.random.PRNGKey(9), (k, pspace.dim))
        trainer = client_mod.make_gossip_cohort_trainer(loss_fn, opt, pspace)
        starts = [pspace.unravel(r) for r in first]
    else:
        first = params
        trainer = (client_mod.make_cohort_trainer(loss_fn, opt, pspace) if kind == "sync" else
                   cohort_mod.make_sharded_cohort_trainer(loss_fn, opt, pspace,
                                                          mesh=cohort_mod.cohort_mesh(1)))
        starts = [params] * k
    inputs = (first, batches, mus, corrections)

    text = jax.jit(trainer).lower(*inputs).as_text()
    assert text.count("stablehlo.convolution") > 0
    groups = re.findall(r"(?:feature|batch)_group_count = (\d+)", text)
    assert groups and all(int(g) == 1 for g in groups)

    res = trainer(*inputs)
    assert res.rows.shape == (k, pspace.dim) and res.loss_last.shape == (k,)
    single = client_mod.make_local_trainer(loss_fn, opt)
    for i in range(k):
        one = single(starts[i], jax.tree.map(lambda x: x[i], batches), mus[i],
                     jax.tree.map(lambda x: x[i], corrections))
        np.testing.assert_allclose(np.asarray(res.rows[i]), np.asarray(pspace.ravel(one.delta)),
                                   rtol=0, atol=1e-6)
        np.testing.assert_allclose(float(res.loss_first[i]), float(one.loss_first), rtol=0, atol=1e-6)
        np.testing.assert_allclose(float(res.loss_last[i]), float(one.loss_last), rtol=0, atol=1e-6)
        assert int(res.n_steps[i]) == int(one.n_steps)


def test_weighted_mean_delta_weights():
    d1 = {"w": jnp.ones(4)}
    d2 = {"w": jnp.zeros(4)}
    out = server_mod.weighted_mean_delta([d1, d2], [3, 1])
    np.testing.assert_allclose(np.asarray(out["w"]), 0.75)


def test_adaptive_mu():
    mus = client_mod.adaptive_mu(0.01, jnp.array([0.5, 1.0, 1.5]))
    assert mus[0] > mus[1] > mus[2] > 0  # weak devices pull harder


@pytest.mark.parametrize("alg", ["fedavg", "fedprox", "fedadam", "fedyogi", "fednova", "scaffold"])
def test_all_algorithms_run_one_round(alg):
    data, clients, params, loss_fn, eval_fn = _setup()
    cfg = FLConfig(algorithm=alg, selection="random", n_clients=6, clients_per_round=2,
                   rounds=1, local_steps=2, batch_size=16, eval_every=1,
                   server_lr=0.02 if alg in ("fedadam", "fedyogi") else 1.0)
    sim = Simulation(cfg, loss_fn, eval_fn, params, clients, data["test"])
    h = sim.run()
    assert len(h["acc"]) == 1 and np.isfinite(h["acc"][0])
    assert h["co2_g"][0] > 0 and h["duration_s"][0] > 0


def test_secure_agg_matches_plain_aggregation():
    """The masked-ring path must reproduce plain FedAvg to quantizer precision."""
    data, clients, params, loss_fn, eval_fn = _setup()
    base = dict(algorithm="fedavg", selection="random", n_clients=6, clients_per_round=3,
                rounds=2, local_steps=2, batch_size=16, eval_every=1, seed=7)
    h_plain = Simulation(FLConfig(**base), loss_fn, eval_fn, params, clients, data["test"]).run()
    h_sa = Simulation(FLConfig(secure_agg=True, sa_bits=24, **base), loss_fn, eval_fn,
                      params, clients, data["test"]).run()
    assert abs(h_plain["final_acc"] - h_sa["final_acc"]) < 0.02


def test_rl_green_smoke_with_emissions_accounting():
    data, clients, params, loss_fn, eval_fn = _setup()
    cfg = FLConfig(algorithm="fedavg", selection="rl_green", n_clients=6, clients_per_round=2,
                   rounds=3, local_steps=2, batch_size=16, eval_every=1)
    sim = Simulation(cfg, loss_fn, eval_fn, params, clients, data["test"])
    h = sim.run()
    assert len(h["co2_g"]) == 3
    assert h["cum_co2_g"][-1] == pytest.approx(sum(h["co2_g"]))
    assert all(len(s) == 2 for s in h["selected"])
