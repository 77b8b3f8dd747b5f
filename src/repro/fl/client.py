"""Client-side local training (paper §III-C).

One jitted function runs a client's whole local round — ``lax.scan`` over the
stacked local batches — and returns the *model delta* (w_local - w_global).
The cohort trainer flattens the k-stacked deltas into ``(k, P)`` float32
rows via the experiment's :class:`repro.fl.paramspace.ParamSpace` before
they leave the jitted call, which is what every aggregation path (plain,
masked-ring, Paillier, the fused Pallas kernels) consumes — deltas never
materialize host-side as pytrees.

Supports the paper's client rules:
  * FedAvg        — plain local SGD/momentum
  * FedProx       — proximal term mu/2 ||w - w_t||^2 with MetaFed's adaptive
                    mu_i = mu_base * (2.0 - C_i)  (Eq. 7)
  * SCAFFOLD      — control-variate corrected gradients g + c - c_i
"""
from __future__ import annotations

import functools
from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.fl.paramspace import ParamSpace
from repro.optim.optimizers import Optimizer
from repro.utils import PyTree, tree_scale, tree_sub, tree_zeros_like


class LocalResult(NamedTuple):
    delta: PyTree        # w_local - w_global
    n_steps: jax.Array   # local step count (FedNova normalization)
    loss_first: jax.Array
    loss_last: jax.Array


class CohortResult(NamedTuple):
    """k-stacked cohort output in the flat-row representation."""

    rows: jax.Array      # (k, P) float32 deltas in ParamSpace ravel order
    n_steps: jax.Array   # (k,) local step counts (FedNova normalization)
    loss_first: jax.Array  # (k,)
    loss_last: jax.Array   # (k,)


def make_local_trainer(loss_fn: Callable, opt: Optimizer) -> Callable:
    """Build the jitted local-round function.

    loss_fn(params, batch) -> (scalar, metrics dict).
    Returned fn signature:
        run(params_global, batches, mu, correction) -> LocalResult
    ``batches``: dict of (n_steps, batch, ...) stacked arrays.
    ``mu``: FedProx proximal coefficient (0 disables).
    ``correction``: SCAFFOLD c - c_i pytree (zeros disable).
    """

    @functools.partial(jax.jit, static_argnames=())
    def run(params_global, batches, mu, correction) -> LocalResult:
        opt_state = opt.init(params_global)
        n_steps = jax.tree.leaves(batches)[0].shape[0]

        def step(carry, batch):
            params, opt_state = carry
            (loss, _), grads = jax.value_and_grad(loss_fn, has_aux=True)(params, batch)
            grads = jax.tree.map(
                lambda g, p, p0, c: g + mu * (p - p0) + c,
                grads, params, params_global, correction,
            )
            params, opt_state = opt.update(params, grads, opt_state)
            return (params, opt_state), loss

        # NOTE: unrolled rather than lax.scan — XLA:CPU executes conv bodies
        # ~13x slower inside while-loops (measured; see EXPERIMENTS.md §Notes).
        # n_steps is static (fixed local step count), so the unroll is exact.
        carry = (params_global, opt_state)
        losses = []
        for i in range(n_steps):
            carry, loss = step(carry, jax.tree.map(lambda x: x[i], batches))
            losses.append(loss)
        params = carry[0]
        delta = tree_sub(params, params_global)
        return LocalResult(delta, jnp.int32(n_steps), losses[0], losses[-1])

    return run


def make_cohort_trainer(loss_fn: Callable, opt: Optimizer, pspace: ParamSpace) -> Callable:
    """Vectorized local training: the whole selected cohort in ONE jitted call.

    This is both the CPU-simulation fast path (one dispatch per round, XLA
    batches the per-client work) and the semantic template for the sharded
    cohort engine (the same vmapped body shard_mapped over the mesh data
    axis — see repro/launch/cohort.py) and the pod-scale ``fl_train_step``
    (repro/launch/train.py).

    run(params_global, batches, mus, corrections) with a leading cohort axis
    on ``batches`` (k, n_steps, batch, ...), ``mus`` (k,), ``corrections``
    (k-stacked pytree).  Returns a :class:`CohortResult` whose deltas are
    ``(k, P)`` rows in ``pspace`` — flattened inside the jitted call, so the
    pytree form of a cohort delta never exists outside the trace.
    """
    single = make_local_trainer(loss_fn, opt)

    # the function's name is the program's in a profiler trace
    # (``jit_cohort_train``), which the benchmark reads by name
    @jax.jit
    def cohort_train(params_global, batches, mus, corrections) -> CohortResult:
        res = jax.vmap(lambda b, m, c: single(params_global, b, m, c))(
            batches, mus, corrections
        )
        return CohortResult(pspace.stack(res.delta), res.n_steps,
                            res.loss_first, res.loss_last)

    return cohort_train


def make_gossip_cohort_trainer(loss_fn: Callable, opt: Optimizer, pspace: ParamSpace) -> Callable:
    """Cohort trainer for decentralized strategies: per-node start params.

    Identical contract to :func:`make_cohort_trainer` except the cohort does
    NOT share one global model — each node trains from its own model, handed
    in as a ``(k, P)`` ParamSpace rows matrix (the representation the gossip
    mixing passes operate on).  The rows are folded back to pytrees inside
    the vmapped trace, so per-node param pytrees never exist outside jit.

    When every row is identical this reduces to :func:`make_cohort_trainer`
    on that model — the training half of the gossip↔FedAvg equivalence
    anchor.
    """
    single = make_local_trainer(loss_fn, opt)

    @jax.jit
    def cohort_train_rows(param_rows, batches, mus, corrections) -> CohortResult:
        res = jax.vmap(lambda r, b, m, c: single(pspace.unravel(r), b, m, c))(
            param_rows, batches, mus, corrections
        )
        return CohortResult(pspace.stack(res.delta), res.n_steps,
                            res.loss_first, res.loss_last)

    return cohort_train_rows


def zero_correction(params: PyTree) -> PyTree:
    return tree_zeros_like(params, jnp.float32)


def adaptive_mu(mu_base: float, capability) -> jax.Array:
    """MetaFed Eq. 7: mu_i = mu_base * (2.0 - C_i) — weaker devices get a
    stronger proximal pull (they run fewer/slower local steps)."""
    return mu_base * (2.0 - capability)


def scaffold_new_control(
    c_i: PyTree, c: PyTree, delta: PyTree, n_steps, lr: float
) -> PyTree:
    """SCAFFOLD option II: c_i+ = c_i - c - delta / (K * lr)."""
    scale = 1.0 / (jnp.maximum(n_steps.astype(jnp.float32), 1.0) * lr)
    return jax.tree.map(lambda ci, cc, d: ci - cc - scale * d, c_i, c, delta)
