"""Sharding rules + HLO parsing (AbstractMesh) + the sharded cohort engine.

The rule/parse tests need no multi-device runtime (AbstractMesh); the
cohort-engine anchors run the real shard_map path on the 1-device fallback
mesh and pin it to the single-device cohort trainer (allclose, rtol=1e-5).
"""
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import AbstractMesh, PartitionSpec as P

from repro.configs import base as cfg_base
from repro.distributed import specs as dspec
from repro.roofline import hlo_parse


def _mesh(multi=False):
    shape = (2, 16, 16) if multi else (16, 16)
    axes = ("pod", "data", "model") if multi else ("data", "model")
    return AbstractMesh(shape, axes)


def _sds(shape):
    return jax.ShapeDtypeStruct(shape, np.dtype("float32"))


def test_attention_weights_shard_only_when_heads_divide():
    mesh = _mesh()
    # gemma: 16 heads % 16 == 0 -> sharded
    g = cfg_base.get("gemma-7b")
    spec = dspec.param_spec((jax.tree_util.DictKey("wq"),), _sds((28, 3072, 4096)), g, 16)
    assert spec == P(None, None, "model")
    # qwen2: 14 heads -> replicated (mid-head sharding forbidden)
    q = cfg_base.get("qwen2-0.5b")
    spec = dspec.param_spec((jax.tree_util.DictKey("wq"),), _sds((24, 896, 896)), q, 16)
    assert spec == P()
    # mixtral: q heads 48 shard, kv heads 8 replicate
    m = cfg_base.get("mixtral-8x22b")
    assert dspec.param_spec((jax.tree_util.DictKey("wq"),), _sds((56, 6144, 6144)), m, 16) == P(None, None, "model")
    assert dspec.param_spec((jax.tree_util.DictKey("wk"),), _sds((56, 6144, 1024)), m, 16) == P()


def test_ffn_and_embed_rules():
    q = cfg_base.get("qwen2-0.5b")
    assert dspec.param_spec((jax.tree_util.DictKey("w1"),), _sds((24, 896, 4864)), q, 16) == P(None, None, "model")
    assert dspec.param_spec((jax.tree_util.DictKey("w2"),), _sds((24, 4864, 896)), q, 16) == P(None, "model", None)
    assert dspec.param_spec((jax.tree_util.DictKey("embed"),), _sds((151936, 896)), q, 16) == P("model", None)
    # norms replicate
    assert dspec.param_spec((jax.tree_util.DictKey("ln1"),), _sds((24, 896)), q, 16) == P()


def test_mlstm_projections_always_replicate():
    x = cfg_base.get("xlstm-125m")
    path = (jax.tree_util.DictKey("mlstm"), jax.tree_util.DictKey("wq"))
    assert dspec.param_spec(path, _sds((6, 1536, 1536)), x, 16) == P()


def test_batch_spec_divisibility():
    mesh = _mesh()
    assert dspec.batch_spec(mesh, 256, 1) == P(("data",), None)
    assert dspec.batch_spec(mesh, 1, 1) == P(None, None)  # long_500k: replicate
    multi = _mesh(multi=True)
    assert dspec.batch_spec(multi, 256, 1) == P(("pod", "data"), None)


def test_hlo_collective_parsing_iota_and_braces():
    txt = """
  %all-reduce.1 = f32[16,4096,896]{2,1,0} all-reduce(%x), channel_id=1, replica_groups=[16,16]<=[256], use_global_device_ids=true, to_apply=%add
  %all-gather.2 = bf16[4,1024]{1,0} all-gather(%y), channel_id=2, replica_groups={{0,1,2,3}}, dimensions={0}
  %cp = u32[128]{0} collective-permute(%z), source_target_pairs={{0,1}}
"""
    colls = hlo_parse.parse_collectives(txt)
    kinds = {c.kind: c for c in colls}
    ar = kinds["all-reduce"]
    assert ar.group_size == 16
    assert ar.out_bytes == 16 * 4096 * 896 * 4
    assert ar.traffic_bytes == int(2 * ar.out_bytes * 15 / 16)
    ag = kinds["all-gather"]
    assert ag.group_size == 4 and ag.out_bytes == 4 * 1024 * 2
    assert kinds["collective-permute"].traffic_bytes == 128 * 4


def test_shape_bytes_tuple():
    assert hlo_parse.shape_bytes("(f32[2,3], bf16[4])") == 2 * 3 * 4 + 4 * 2


def test_mesh_factory_shapes():
    # only the geometry (can't instantiate 512 devices here — that is dryrun's job)
    from repro.launch.mesh import data_axes
    m = _mesh(multi=True)
    assert tuple(m.shape[a] for a in ("pod", "data", "model")) == (2, 16, 16)
    assert data_axes(m) == ("pod", "data")


# ---------------------------------------------------------------------------
# Sharded cohort engine: shard_map over the data axis == single-device path
# ---------------------------------------------------------------------------


def _cohort_setup(k=3, n_steps=2, batch=16):
    from repro.data.partition import dirichlet_partition
    from repro.data.pipeline import build_clients
    from repro.data.synthetic import MNIST_LIKE, make_image_dataset
    from repro.fl import client as client_mod
    from repro.fl.paramspace import ParamSpace
    from repro.models.resnet import ResNetConfig, init_resnet, resnet_loss
    from repro.optim import optimizers as opt_mod

    data = make_image_dataset(MNIST_LIKE, seed=1, n_train=500, n_test=64)
    parts = dirichlet_partition(data["train"]["label"], k + 1, 0.5, seed=1)
    clients = build_clients(data["train"], parts)
    rcfg = ResNetConfig(name="t", widths=(8, 16), depths=(1, 1), in_channels=1, num_classes=10)
    params = init_resnet(jax.random.PRNGKey(0), rcfg)
    loss_fn = lambda p, b: resnet_loss(p, rcfg, b)
    pspace = ParamSpace.build(params)
    opt = opt_mod.momentum(0.05, beta=0.9)

    batch_l = [clients[i].stacked_steps(batch, n_steps, 0) for i in range(k)]
    batches = {kk: jnp.asarray(np.stack([b[kk] for b in batch_l])) for kk in batch_l[0]}
    mus = jnp.zeros(k, jnp.float32)
    corrs = jax.tree.map(
        lambda z: jnp.broadcast_to(z, (k,) + z.shape), client_mod.zero_correction(params)
    )
    return params, pspace, opt, loss_fn, batches, mus, corrs


def test_sharded_cohort_trainer_matches_single_device():
    """The shard_map trainer (1-device fallback mesh) reproduces the
    single-device cohort trainer — the smoke-protocol equivalence anchor."""
    from repro.fl import client as client_mod
    from repro.launch import cohort as cohort_mod

    params, pspace, opt, loss_fn, batches, mus, corrs = _cohort_setup()
    single = client_mod.make_cohort_trainer(loss_fn, opt, pspace)
    sharded = cohort_mod.make_sharded_cohort_trainer(loss_fn, opt, pspace)
    r1 = single(params, batches, mus, corrs)
    r2 = sharded(params, batches, mus, corrs)
    assert r1.rows.shape == r2.rows.shape == (3, pspace.dim)
    # rows come back on one device, where the round's Mosaic kernels run
    assert r2.rows.sharding.device_set == {sharded.mesh.devices.flat[0]}
    np.testing.assert_allclose(np.asarray(r1.rows), np.asarray(r2.rows), rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(r1.loss_last), np.asarray(r2.loss_last), rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(r1.n_steps), np.asarray(r2.n_steps))


def test_sharded_cohort_step_fused_reduce():
    """Fused train+psum dispatch == einsum over the gathered rows."""
    from repro.fl import client as client_mod
    from repro.launch import cohort as cohort_mod

    params, pspace, opt, loss_fn, batches, mus, corrs = _cohort_setup()
    single = client_mod.make_cohort_trainer(loss_fn, opt, pspace)
    step = cohort_mod.make_sharded_cohort_step(loss_fn, opt, pspace)
    w = jnp.asarray([0.5, 0.3, 0.2], jnp.float32)
    ref_rows = single(params, batches, mus, corrs).rows
    row, loss_last = step(params, batches, mus, corrs, w)
    np.testing.assert_allclose(
        np.asarray(row), np.asarray(jnp.einsum("kp,k->p", ref_rows, w)),
        rtol=1e-5, atol=1e-6,
    )
    assert loss_last.shape == (3,)


def test_cohort_mesh_fallback_and_padding_indices():
    from repro.launch import cohort as cohort_mod

    mesh = cohort_mod.cohort_mesh()
    assert "data" in mesh.axis_names and mesh.shape["data"] >= 1
    idx, pad = cohort_mod._pad_cohort(5, 4)
    assert pad == 3 and list(np.asarray(idx)) == [0, 1, 2, 3, 4, 0, 1, 2]
    idx, pad = cohort_mod._pad_cohort(4, 4)
    assert pad == 0


def test_sharded_simulation_matches_flat_engine():
    """FLConfig(sharded=True) runs the whole engine through the shard_map
    cohort path and reproduces the flat engine's trajectory."""
    from repro.data.partition import dirichlet_partition
    from repro.data.pipeline import build_clients
    from repro.data.synthetic import MNIST_LIKE, make_image_dataset
    from repro.fl.simulation import FLConfig, Simulation
    from repro.models.resnet import ResNetConfig, init_resnet, resnet_loss

    data = make_image_dataset(MNIST_LIKE, seed=1, n_train=400, n_test=128)
    parts = dirichlet_partition(data["train"]["label"], 4, 0.5, seed=1)
    clients = build_clients(data["train"], parts)
    rcfg = ResNetConfig(name="t", widths=(8, 16), depths=(1, 1), in_channels=1, num_classes=10)
    params = init_resnet(jax.random.PRNGKey(0), rcfg)
    loss_fn = lambda p, b: resnet_loss(p, rcfg, b)
    eval_fn = lambda p, b: resnet_loss(p, rcfg, b)[1]
    base = dict(algorithm="fedavg", selection="random", n_clients=4, clients_per_round=2,
                rounds=2, local_steps=2, batch_size=16, eval_every=1, seed=3)
    h_flat = Simulation(FLConfig(**base), loss_fn, eval_fn, params, clients,
                        data["test"]).run()
    h_shard = Simulation(FLConfig(sharded=True, **base), loss_fn, eval_fn, params,
                         clients, data["test"]).run()
    np.testing.assert_allclose(h_flat["acc"], h_shard["acc"], atol=1e-4)
    np.testing.assert_allclose(h_flat["loss"], h_shard["loss"], rtol=1e-5)
    assert h_flat["selected"] == h_shard["selected"]
