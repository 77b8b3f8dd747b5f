"""Plain float32 reference of synchronous FedAvg rounds, and the comparison
that decides ``correct``.

The reference follows the program's first round: the same initial weights
(made by the benchmark), the same data and partition, and the cohort that
the program's selector picked (an input, like the data).  For each selected
client it draws the batches by its own copy of the batch schedule and runs
local momentum SGD; the server takes the data-size weighted mean of the
deltas (Eq. 6) and adds it (FedAvg, server rate 1).  Secure aggregation
changes none of this: its masks cancel and its fixed-point rounding is the
program's error to carry.

Numbers read (a cell's file in ``bench/cells/`` gives the limit of each
that it compares):

- ``first_loss_gap``: each client's loss at its first local step (a
  forward pass at the initial weights), the largest relative gap.
- ``first_update_gap``: the first round's server update (the parameters
  after round 1 less the initial ones: the mean of the cohort's trained
  deltas, through the privacy pipeline and the ``masked_agg`` kernel), by
  the worst leaf: the gap between the program's leaf norm and the
  reference's, over the reference's norm of that leaf or of the median
  leaf, whichever is larger.

Only round 1 is compared.  Later rounds diverge: the program's default
precision convolutions round differently from the float32 reference, and
the first rounds' unstable training (losses of 13 to 18) amplifies that
into gaps as large as the bfloat16 control's (PERF.md, "How correct is
decided").  Leaves whose reference update is under a thousandth of the
median leaf's are left out (none are, in ResNet-Tiny).

Weights come and go as host trees.  On the device the reference holds the
round's weights, one client's local state and the running mean, and
nothing more: the client's update is scaled and added into the mean by
steps that donate their first argument, each round's weights go to the
host as soon as they are made, and the norms move one leaf at a time.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench import generate

NUMBERS = ("first_loss_gap", "first_update_gap")
TINY_LEAF = 1e-3


@functools.lru_cache(maxsize=None)
def _local_round_fn(model_module, model_key, lr: float, beta: float, dtype):
    model = {k: (list(v) if isinstance(v, tuple) else v) for k, v in model_key}

    @jax.jit
    def local_round(params, batches):
        p0 = jax.tree.map(lambda x: x.astype(dtype), params)
        mu0 = jax.tree.map(jnp.zeros_like, p0)

        def step(carry, batch):
            p, mu = carry
            value, grads = jax.value_and_grad(model_module.loss)(p, model, batch, dtype)
            mu = jax.tree.map(lambda m, g: (beta * m + g).astype(dtype), mu, grads)
            p = jax.tree.map(lambda w, m: (w - lr * m).astype(dtype), p, mu)
            return (p, mu), value

        (p, _), losses = jax.lax.scan(step, (p0, mu0), batches)
        delta = jax.tree.map(lambda a, b: (a - b).astype(jnp.float32), p, p0)
        return delta, losses.astype(jnp.float32)

    return local_round


# the product and the sum are kept in programs of their own, so no
# compiler contracts them into one rounding (a fused multiply-add)
@functools.partial(jax.jit, donate_argnums=0)
def _scaled(delta, w):
    return jax.tree.map(lambda d: w * d, delta)


@functools.partial(jax.jit, donate_argnums=0)
def _add_into(a, b):
    """``a + b`` in float32, in ``a``'s type and buffer."""
    return jax.tree.map(lambda x, y: (x.astype(jnp.float32) + y).astype(x.dtype), a, b)


def reference_rounds(model_module, cell, data, parts, client_seed: int, params0,
                     selections, dtype=jnp.float32) -> list[dict]:
    """The reference's trajectory over the rounds in ``selections`` (the
    cohort of each): per round the clients' first-step losses, the round's
    loss (the clients' mean last-step loss) and the server parameters
    (float32, on the host).  ``params0`` is a host tree."""
    proto = cell["config"]["protocol"]
    model_key = tuple((k, tuple(v) if isinstance(v, list) else v)
                      for k, v in sorted(cell["config"]["model"].items()))
    local_round = _local_round_fn(model_module, model_key, float(proto["client_lr"]),
                                  float(proto["client_momentum"]), dtype)
    train = data["train"]
    batch, steps = proto["batch_size"], cell["traffic"]["local_steps"]
    # a copy of its own, as the round's update donates it
    params = jax.tree.map(lambda x: jnp.array(x).astype(dtype), params0)
    out = []
    with jax.default_matmul_precision("highest"):
        for rnd, sel in enumerate(selections):
            sizes = np.array([len(parts[c]) for c in sel], np.float64)
            mean, first, last = None, [], []
            for c, w in zip(sel, sizes / sizes.sum()):
                idx = generate.local_batches(parts[c], client_seed + int(c), batch, steps, rnd)
                delta, losses = local_round(params, {k: jnp.asarray(v[idx]) for k, v in train.items()})
                losses = jax.device_get(losses)
                first.append(float(losses[0]))
                last.append(float(losses[-1]))
                scaled = _scaled(delta, jnp.float32(w))
                mean = scaled if mean is None else _add_into(mean, scaled)
                del delta, scaled
            params = _add_into(params, mean)
            out.append({"first_losses": np.array(first), "loss": float(np.mean(last)),
                        "params": jax.tree.map(lambda x: np.asarray(x, np.float32),
                                               jax.device_get(params))})
    return out


def _leaf_norms(tree, base) -> np.ndarray:
    """Float32 norm of each leaf's change, of host or device trees: each
    leaf goes to the device on its own and comes back as one number."""
    norms = jax.tree.map(lambda a, b: float(jnp.sqrt(jnp.sum(jnp.square(
        jnp.asarray(a).astype(jnp.float32) - jnp.asarray(b).astype(jnp.float32))))), tree, base)
    return np.array(jax.tree.leaves(norms), np.float64)


def worst_leaf_gap(prog_tree, ref_tree, params0) -> float:
    """Largest gap of per-leaf change norms, over the reference's norm of
    that leaf or of the median leaf, whichever is larger."""
    a, b = _leaf_norms(prog_tree, params0), _leaf_norms(ref_tree, params0)
    keep = b >= TINY_LEAF * np.median(b)
    scale = np.maximum(b, np.median(b[keep]))
    return float(np.max(np.abs(a - b)[keep] / scale[keep]))


def readings(program: list[dict], reference: list[dict], params0) -> dict[str, float]:
    """The numbers compared, from round 1 of each trajectory."""
    p, r = program[0], reference[0]
    loss_gap = np.max(np.abs(np.asarray(p["first_losses"]) - r["first_losses"]) / np.abs(r["first_losses"]))
    return {"first_loss_gap": float(loss_gap),
            "first_update_gap": worst_leaf_gap(p["params"], r["params"], params0)}
