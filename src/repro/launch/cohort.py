"""Sharded cohort engine: a region's cohort trains across the mesh ``data``
axis in ONE dispatch.

Every strategy drives local training through a cohort trainer that returns
``(k, P)`` ParamSpace rows; ``TrainingConfig(sharded=True)`` makes
``RuntimeContext`` build it here.  On one device that trainer maps over
the k clients, one after another (``client.map_cohort``); this module wraps
the *same mapped body* in ``jax.shard_map`` over the ``data`` axis of the
mesh (``cohort_mesh``) so each device trains its k/d clients in turn and
the cohort's rows are reduced across devices in-graph:

  * :func:`make_sharded_cohort_trainer` — drop-in replacement for
    ``client.make_cohort_trainer``: all-gathers the per-device row shards so
    the full ``(k, P)`` buffer is replicated for the privacy/kernels
    pipeline (clip -> quantize -> mask -> fused aggregation);
  * :func:`make_sharded_cohort_step` — the fully-fused plain-FedAvg path:
    each device reduces its local rows with the weight slice and a single
    ``psum`` over ``data`` yields the weighted delta row — train + reduce in
    one dispatch, no (k, P) buffer ever replicated.

Cohorts that do not divide the data axis are padded by cycling clients
modulo k; padded outputs are sliced off (and padded weights zeroed in the
fused step), so results are independent of the padding.

The mesh spans every visible device: one on a CPU test run (the
sharded-vs-single-device anchor in ``tests/test_sharding.py``, allclose
rtol=1e-5), four on a 2x2 TPU v5e host (``chip_smoke.py --chips 4``).
Replication of the gathered outputs is not type-checked
(``check_vma=False``): the all-gather makes them replicated by
construction.
"""
from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.fl import client as client_mod
from repro.fl.paramspace import ParamSpace
from repro.launch import mesh as mesh_mod
from repro.optim.optimizers import Optimizer


def cohort_mesh(n_devices: Optional[int] = None) -> Mesh:
    """Mesh whose ``data`` axis carries the cohort.

    On a pod-scale host this is the production mesh; anywhere smaller
    (CPU container, tests) it falls back to a 1-D ``data`` mesh over the
    locally visible devices — 1 device on CPU — so the shard_map path is
    always exercised.
    """
    devs = jax.devices()
    if n_devices is None and len(devs) >= 256:
        return mesh_mod.make_production_mesh()
    n = n_devices or len(devs)
    return Mesh(np.asarray(devs[:n]), ("data",))


def _pad_cohort(k: int, d: int):
    """Indices that cycle the cohort up to a multiple of d (and the pad count)."""
    pad = (-k) % d
    idx = np.arange(k + pad) % k
    return jnp.asarray(idx), pad


def make_sharded_cohort_trainer(
    loss_fn: Callable, opt: Optimizer, pspace: ParamSpace, mesh: Optional[Mesh] = None
) -> Callable:
    """Cohort trainer sharded over the mesh ``data`` axis.

    Drop-in for ``client.make_cohort_trainer``: same signature, same
    :class:`~repro.fl.client.CohortResult`, handed back on the mesh's first
    device after the in-graph all-gather, so every aggregation path —
    plain, masked-ring, DP — runs unchanged on the output.  The mesh the
    cohort trained on is the returned function's ``mesh`` attribute.
    """
    mesh = mesh or cohort_mesh()
    home = mesh.devices.flat[0]
    replicated = NamedSharding(mesh, P())
    d = mesh.shape["data"]
    single = client_mod.make_local_trainer(loss_fn, opt)

    def shard_body(params_global, batches, mus, corrections) -> client_mod.CohortResult:
        res = client_mod.map_cohort(lambda b, m, c: single(params_global, b, m, c), pspace,
                                    batches, mus, corrections)  # (k_local, P) rows
        gather = lambda x: jax.lax.all_gather(x, "data", axis=0, tiled=True)
        return client_mod.CohortResult(*map(gather, res))

    sharded = jax.shard_map(
        shard_body, mesh=mesh,
        in_specs=(P(), P("data"), P("data"), P("data")),
        out_specs=P(),
        check_vma=False,
    )

    # named as the unsharded trainer, so one profiler reader finds either
    @jax.jit
    def cohort_train(params_global, batches, mus, corrections) -> client_mod.CohortResult:
        k = jax.tree.leaves(batches)[0].shape[0]
        idx, pad = _pad_cohort(k, d)
        if pad:
            take = lambda x: jnp.take(x, idx, axis=0)
            batches = jax.tree.map(take, batches)
            mus = take(mus)
            corrections = jax.tree.map(take, corrections)
        res = sharded(params_global, batches, mus, corrections)
        if pad:
            res = client_mod.CohortResult(
                res.rows[:k], res.n_steps[:k], res.loss_first[:k], res.loss_last[:k]
            )
        return res

    def run(*inputs) -> client_mod.CohortResult:
        # the rest of the round (privacy stages, Pallas kernels, server
        # update) runs on one device, as after the unsharded trainer: XLA
        # cannot partition a Mosaic kernel over the mesh the rows come from.
        # So the inputs, committed to that device, move onto the mesh first.
        res = cohort_train(*jax.device_put(inputs, replicated))
        return jax.device_put(res, home)

    run.mesh = mesh
    return run


def make_sharded_cohort_step(
    loss_fn: Callable, opt: Optimizer, pspace: ParamSpace, mesh: Optional[Mesh] = None
) -> Callable:
    """Fused train+reduce: one dispatch returns the weighted delta row.

    run(params_global, batches, mus, corrections, weights) -> (row, loss_last)
    where ``row = Σ_i weights_i · delta_i`` (pass normalized weights for a
    mean) and ``loss_last`` is the (k,) per-client final loss.  Each device
    reduces its local row shard and a single ``psum`` over ``data``
    completes the reduction — the replicated (k, P) buffer of the gathering
    trainer never exists, which is the pod-scale plain-FedAvg path.
    """
    mesh = mesh or cohort_mesh()
    d = mesh.shape["data"]
    single = client_mod.make_local_trainer(loss_fn, opt)

    def shard_body(params_global, batches, mus, corrections, weights):
        res = client_mod.map_cohort(lambda b, m, c: single(params_global, b, m, c), pspace,
                                    batches, mus, corrections)
        part = jnp.einsum("kp,k->p", res.rows, weights)  # local partial reduce
        row = jax.lax.psum(part, "data")                 # cross-device reduce
        loss_last = jax.lax.all_gather(res.loss_last, "data", axis=0, tiled=True)
        return row, loss_last

    sharded = jax.shard_map(
        shard_body, mesh=mesh,
        in_specs=(P(), P("data"), P("data"), P("data"), P("data")),
        out_specs=(P(), P()),
        check_vma=False,
    )

    @jax.jit
    def run(params_global, batches, mus, corrections, weights):
        k = jax.tree.leaves(batches)[0].shape[0]
        idx, pad = _pad_cohort(k, d)
        if pad:
            take = lambda x: jnp.take(x, idx, axis=0)
            batches = jax.tree.map(take, batches)
            mus, corrections = take(mus), jax.tree.map(take, corrections)
            # zero the padded weights: cycled clients must not double-count
            weights = jnp.concatenate([weights, jnp.zeros(pad, weights.dtype)])
        row, loss_last = sharded(params_global, batches, mus, corrections, weights)
        return row, loss_last[:k]

    return run
