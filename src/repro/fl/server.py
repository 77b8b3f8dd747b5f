"""Server-side aggregation and optimizers.

Aggregation (Eq. 6, "Enhanced FedAvg"): data-size-weighted mean of client
deltas.  The server optimizer then treats the negated mean delta as a
pseudo-gradient (Reddi et al., "Adaptive Federated Optimization"):

    FedAvg  : w += mean_delta                    (SGD, lr=1)
    FedAdam : Adam(pseudo_grad)
    FedYogi : Yogi(pseudo_grad)
    FedNova : deltas normalized by local step counts before averaging
    SCAFFOLD: FedAvg + control-variate state on the side
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.optim import optimizers as opt_mod
from repro.utils import PyTree, tree_scale, tree_zeros_like


class ServerState(NamedTuple):
    params: PyTree
    opt_state: object
    c: Optional[PyTree]  # SCAFFOLD global control variate (None otherwise)
    round: jax.Array


def make_server(name: str, params: PyTree, server_lr: float = 1.0):
    """Returns (ServerState, apply_fn(state, mean_delta, extra) -> ServerState)."""
    name = name.lower()
    if name in ("fedavg", "fedprox", "fednova", "scaffold"):
        opt = opt_mod.sgd(server_lr)
    elif name == "fedadam":
        opt = opt_mod.adam(server_lr, b1=0.9, b2=0.99, eps=1e-3)
    elif name == "fedyogi":
        opt = opt_mod.yogi(server_lr, b1=0.9, b2=0.99, eps=1e-3)
    else:
        raise ValueError(f"unknown server algorithm {name!r}")

    c = tree_zeros_like(params, jnp.float32) if name == "scaffold" else None
    state = ServerState(params, opt.init(params), c, jnp.int32(0))

    @jax.jit
    def server_update(state: ServerState, mean_delta: PyTree) -> ServerState:
        # pseudo-gradient = -mean_delta
        grads = tree_scale(mean_delta, -1.0)
        params, opt_state = opt.update(state.params, grads, state.opt_state)
        return ServerState(params, opt_state, state.c, state.round + 1)

    return state, server_update


def weighted_mean_delta(deltas: list[PyTree], weights) -> PyTree:
    """Eq. 6: sum_i (n_i / sum_j n_j) * delta_i."""
    w = jnp.asarray(weights, jnp.float32)
    w = w / jnp.sum(w)
    out = tree_scale(deltas[0], w[0])
    for i in range(1, len(deltas)):
        out = jax.tree.map(lambda o, d: o + w[i] * d, out, deltas[i])
    return out


def fednova_mean_delta(deltas: list[PyTree], weights, n_steps: list) -> PyTree:
    """FedNova: normalize each delta by its local step count, rescale by the
    effective tau so the update magnitude matches FedAvg's."""
    w = jnp.asarray(weights, jnp.float32)
    w = w / jnp.sum(w)
    taus = jnp.asarray([jnp.maximum(t, 1) for t in n_steps], jnp.float32)
    tau_eff = jnp.sum(w * taus)
    out = None
    for i, d in enumerate(deltas):
        scaled = tree_scale(d, w[i] * tau_eff / taus[i])
        out = scaled if out is None else jax.tree.map(jnp.add, out, scaled)
    return out


def scaffold_update_c(state: ServerState, c_deltas: list[PyTree], n_total_clients: int) -> ServerState:
    """c += (|S|/N) * mean_i (c_i+ - c_i)."""
    mean_cd = weighted_mean_delta(c_deltas, [1.0] * len(c_deltas))
    frac = len(c_deltas) / n_total_clients
    new_c = jax.tree.map(lambda c, d: c + frac * d, state.c, mean_cd)
    return state._replace(c=new_c)
