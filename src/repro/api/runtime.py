"""Shared experiment runtime for every aggregation strategy.

``RuntimeContext`` wires the subsystem stack once — data-size weights, the
flat-row ``ParamSpace``, the (optionally sharded) cohort trainer, the server
optimizer, the provider fleet + carbon model, the selection policy/MARL
state, and the privacy pipeline — and every strategy (the synchronous round
loop, the event-driven async hierarchy, gossip) composes it rather than
subclassing another strategy.

Dataflow is flat-row end to end (``repro.fl.paramspace``): the cohort
trainer returns (k, P) float32 delta rows, the privacy pipeline
clips/quantizes/masks rows, the Pallas kernels reduce rows, and the pytree
form of an update is materialized exactly once — at the server-optimizer
boundary.

Energy/emissions: per-round client FLOPs are measured from the *compiled*
local step (``cost_analysis``), fed through the §III-D device/carbon model.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Optional, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.api.config import ExperimentConfig
from repro.api.pipeline import (AggregationContext, PrivacyPipeline, StageRecord,
                                build_pipeline)
from repro.core import carbon as carbon_mod
from repro.core import orchestrator as orch
from repro.core.selection import POLICIES, policy_uses_rl
from repro.data.pipeline import ClientDataset, eval_batches
from repro.fl import client as client_mod
from repro.fl import server as server_mod
from repro.fl.paramspace import ParamSpace
from repro.kernels import ops as kernel_ops
from repro.obs.trace import NULL_TRACER
from repro.optim import optimizers as opt_mod
from repro.utils import PyTree, tree_zeros_like


@dataclasses.dataclass
class FederatedTask:
    """The learning problem a federation runs: model, loss, and data."""

    loss_fn: Callable              # (params, batch) -> (scalar, metrics)
    eval_fn: Callable              # (params, batch) -> metrics dict with "acc"
    params0: PyTree
    clients: list[ClientDataset]
    test_data: dict[str, np.ndarray]


class RuntimeContext:
    """Everything a strategy needs to run rounds, built once per experiment.

    The clients' training data lives on the device for the whole run: the
    distinct ``data`` dicts the clients hold (by identity; ``build_clients``
    shares one) are concatenated into one store and uploaded once, each
    client keeping a row offset into it.  A round then sends only its
    (k, n_steps, batch) sample indices and gathers the batches on the chip
    (:meth:`_cohort_inputs`).  The store costs the training set's bytes in
    device memory (614 MB for 50,000 CIFAR-10-shaped float32 images) and
    has no host fallback: a training set larger than the device's memory
    is not supported.
    """

    def __init__(
        self,
        cfg: ExperimentConfig,
        task: FederatedTask,
        *,
        pipeline: Optional[PrivacyPipeline] = None,
        selector: Union[None, str, Callable] = None,
        tracer=None,
    ):
        train, priv = cfg.training, cfg.privacy
        assert len(task.clients) == train.n_clients
        self.cfg = cfg
        # span tracer every strategy wraps its phases with; the shared no-op
        # singleton by default, so untraced hot paths cost nothing
        self.tracer = tracer if tracer is not None else NULL_TRACER
        self.train = train
        self.privacy = priv
        self.topology = cfg.topology
        self.carbon = cfg.carbon
        self.clients = task.clients
        self.test_data = task.test_data
        task_eval = task.eval_fn

        def evaluate(params, batch):
            return task_eval(params, batch)

        # named, so the profiler shows the program as ``jit_evaluate``
        # whatever the caller's function is called
        self.eval_fn = jax.jit(evaluate)
        self.pipeline = pipeline if pipeline is not None else build_pipeline(priv)

        # SCAFFOLD's control-variate correction assumes plain SGD clients
        # (Karimireddy et al. Alg. 1); momentum double-applies the correction.
        if train.algorithm == "scaffold":
            local_opt = opt_mod.sgd(train.client_lr)
        else:
            local_opt = opt_mod.momentum(train.client_lr, beta=train.client_momentum)
        # the canonical pytree<->rows mapping every downstream layer shares
        self.pspace = ParamSpace.build(task.params0)
        self.loss_fn = task.loss_fn
        self.local_opt = local_opt
        self.trainer = client_mod.make_local_trainer(task.loss_fn, local_opt)
        self._row_trainer = None  # built lazily by train_cohort_rows
        if train.sharded:
            from repro.launch import cohort as cohort_mod  # lazy: touches devices

            self.cohort_trainer = cohort_mod.make_sharded_cohort_trainer(
                task.loss_fn, local_opt, self.pspace
            )
        else:
            self.cohort_trainer = client_mod.make_cohort_trainer(
                task.loss_fn, local_opt, self.pspace
            )
        self.server_state, self.server_apply = server_mod.make_server(
            train.algorithm, task.params0, train.server_lr
        )
        self.fleet = carbon_mod.make_fleet(
            jax.random.PRNGKey(train.seed + 1), train.n_clients, cfg.carbon.hetero
        )
        self.policy, self.uses_rl = _resolve_selector(selector, cfg)
        self.orch_state = orch.init_state(
            train.n_clients, stale_in_state=cfg.orchestrator.stale_in_state
        )
        # SCAFFOLD per-client control variates
        self.c_locals = (
            [tree_zeros_like(task.params0, jnp.float32) for _ in range(train.n_clients)]
            if train.algorithm == "scaffold"
            else None
        )
        self.zero_corr = client_mod.zero_correction(task.params0)
        # the (k,)-broadcast zero corrections, one per cohort size; no
        # trainer donates its inputs, so one copy serves every round
        self._zero_corrs: dict[int, PyTree] = {}
        self._store, self._offsets, self._gather = _device_store(task.clients)

        # measured FLOPs of one full local round (compute model for emissions)
        sample = task.clients[0].stacked_steps(train.batch_size, train.local_steps, 0)
        sample = {k: jnp.asarray(v) for k, v in sample.items()}

        def flops_probe(p, b):
            return self.trainer(p, b, jnp.float32(0.0), self.zero_corr)

        lowered = jax.jit(flops_probe).lower(task.params0, sample)
        self.round_flops = float(lowered.compile().cost_analysis()["flops"])
        self.model_bytes = float(self.pspace.nbytes)
        self.param_dim = self.pspace.dim
        # EF top-k residual bank: one ParamSpace row per client, fed to and
        # updated by TopKStage through each aggregate call.  Allocated only
        # when the pipeline actually sparsifies; checkpointed with the rest
        # of the run state so crash->resume replays EF bitwise.
        if any(s.name == "topk" for s in self.pipeline.stages):
            self.ef_residuals = jnp.zeros(
                (train.n_clients, self.pspace.dim), jnp.float32
            )
        else:
            self.ef_residuals = None
        # fault tolerance: Federation.run(checkpoint=...) installs a
        # CheckpointManager here; strategies call checkpoint_round per round
        self.ckpt_manager = None
        # continuous-time engine: EngineConfig.trace attaches the simulated
        # clock + recorded latency streams every strategy consults
        self.engine = None
        if cfg.engine.trace:
            from repro.engine import runtime as engine_runtime
            from repro.engine import traces as traces_mod

            trace = traces_mod.load(cfg.engine.trace)
            base_durs = np.asarray(carbon_mod.client_durations_s(
                self.fleet, self.round_flops, self.model_bytes
            ), np.float64)
            self.engine = engine_runtime.EngineRuntime(
                trace, cfg.engine, train.n_clients, base_durs
            )

    # ------------------------------------------------------------------
    def checkpoint_round(self, strategy, rnd: int) -> None:
        """Per-round checkpoint hook — a no-op unless ``Federation.run``
        installed a manager.  Strategies call this *after* emitting the
        round's event, so a checkpoint at round r implies rows 0..r already
        reached every sink."""
        if self.ckpt_manager is not None:
            self.ckpt_manager.on_round(strategy, self, rnd)

    def state_dict(self) -> dict:
        """The context's mutable run state (the rest of the wiring is a pure
        function of config + task and is rebuilt on resume)."""
        from repro.checkpoint.state import pack_tree

        s = {
            "server_state": pack_tree(self.server_state),
            "orch_state": pack_tree(self.orch_state),
        }
        if self.c_locals is not None:  # SCAFFOLD per-client control variates
            s["c_locals"] = pack_tree(self.c_locals)
        if self.ef_residuals is not None:  # EF top-k residual bank
            s["ef_residuals"] = pack_tree(self.ef_residuals)
        if self.engine is not None:  # simulated clock + latency-stream cursors
            s["engine"] = self.engine.state_dict()
        return s

    def load_state_dict(self, s: dict) -> None:
        from repro.checkpoint.state import unpack_tree

        self.server_state = unpack_tree(s["server_state"], self.server_state)
        self.orch_state = unpack_tree(s["orch_state"], self.orch_state)
        if self.c_locals is not None:
            if "c_locals" not in s:
                raise ValueError(
                    "checkpoint has no SCAFFOLD control variates but this run "
                    "needs them — was it written by a different algorithm?"
                )
            self.c_locals = unpack_tree(s["c_locals"], self.c_locals)
        if self.ef_residuals is not None:
            if "ef_residuals" not in s:
                raise ValueError(
                    "checkpoint has no EF residual bank but this run sparsifies "
                    "— was it written without topk_density set?"
                )
            self.ef_residuals = unpack_tree(s["ef_residuals"], self.ef_residuals)
        if self.engine is not None:
            if "engine" not in s:
                raise ValueError(
                    "checkpoint has no engine state but this run is trace-driven "
                    "— was it written without engine.trace set?"
                )
            self.engine.load_state_dict(s["engine"])

    # ------------------------------------------------------------------
    def _cohort_inputs(self, sel, step: int, corrections=None):
        """Shared cohort-dispatch plumbing: per-client step batches,
        FedProx adaptive mu, and the correction broadcast (zero unless the
        caller passes SCAFFOLD control variates).  ``step`` seeds the
        clients' batch schedule (round index / dispatch wave).

        The batches are gathered on the device from the store built at
        set-up: the host uploads only the (k, n_steps, batch) int32 sample
        indices, which the span records as ``h2d_bytes``."""
        train = self.train
        with self.tracer.span("cohort_inputs", cohort=len(sel)) as span:
            idx = np.stack([
                self.clients[ci].step_indices(train.batch_size, train.local_steps, step)
                + self._offsets[ci]
                for ci in sel
            ])
            span.set(h2d_bytes=idx.nbytes)
            batches = self._gather(self._store, idx)
            if train.algorithm == "fedprox":
                mus = client_mod.adaptive_mu(
                    train.prox_mu, self.fleet.capability[jnp.asarray(sel)]
                )
            else:
                mus = jnp.zeros(len(sel), jnp.float32)
            if corrections is None:
                k = len(sel)
                if k not in self._zero_corrs:
                    self._zero_corrs[k] = jax.tree.map(
                        lambda z: jnp.broadcast_to(z, (k,) + z.shape), self.zero_corr
                    )
                corrections = self._zero_corrs[k]
            return batches, mus, corrections

    def train_cohort(self, params, sel, step: int, corrections=None):
        """One local-training dispatch of the selected cohort, its clients
        trained in turn against the shared ``params`` (the sync/async server
        model)."""
        batches, mus, corrections = self._cohort_inputs(sel, step, corrections)
        return self.cohort_trainer(params, batches, mus, corrections)

    def train_cohort_rows(self, param_rows, sel, step: int):
        """Decentralized cohort dispatch: each selected node trains from its
        OWN model, handed in as (k, P) ParamSpace rows — the gossip
        strategy's node states.  Same batch schedule and FedProx rules as
        :meth:`train_cohort`; SCAFFOLD corrections are undefined without a
        server and therefore not accepted here.
        """
        if self._row_trainer is None:
            self._row_trainer = client_mod.make_gossip_cohort_trainer(
                self.loss_fn, self.local_opt, self.pspace
            )
        batches, mus, corrections = self._cohort_inputs(sel, step)
        return self._row_trainer(param_rows, batches, mus, corrections)

    # ------------------------------------------------------------------
    def aggregate(
        self, rows: jax.Array, weights, key, clients=None
    ) -> tuple[jax.Array, list[StageRecord]]:
        """Run the privacy pipeline over (k, P) delta rows -> (MEAN row, records).

        Everything is row-native: clipping, quantization, masking and the
        kernel reductions all act on the ParamSpace representation; the
        pytree form only reappears at the server-update boundary.  The
        records tell the caller exactly which stages ran (the accountant
        reads the ``noise`` record's sigma).

        ``clients``: cohort client ids aligned with ``rows`` — required when
        the pipeline sparsifies, so ``TopKStage`` reads/writes the right rows
        of the EF residual bank; the updated bank is committed back here.
        """
        # independent streams for the one-time-pad masks and the DP noise —
        # reusing one key would correlate the pads with the Gaussian draw
        k_mask, k_noise = jax.random.split(key)
        actx = AggregationContext(
            self.pspace, len(weights), weights, k_mask, k_noise,
            self.weighted_sum, clients=clients, residuals=self.ef_residuals,
        )
        mean_row = self.pipeline.aggregate(rows, actx)
        if self.ef_residuals is not None:
            self.ef_residuals = actx.residuals
        return mean_row, actx.records

    def weighted_sum(self, rows: jax.Array, w) -> jax.Array:
        """Σ_i w_i·row_i — the shared sync/async server reduction.

        The fused buffer-aggregation kernel (one VMEM pass over the (k, P)
        rows, pre-padded to whole blocks by the ParamSpace); off the TPU
        ``kernels.ops`` runs its XLA reference instead.  Both strategies
        route through this method, which is what makes the async
        sync-equivalence anchor bitwise.
        """
        w = jnp.asarray(w, jnp.float32)
        return kernel_ops.staleness_aggregate(self.pspace.pad_rows(rows), w)[: self.pspace.dim]

    # ------------------------------------------------------------------
    def round_accounting(self, sel, t_hours: float):
        """Participation mask + emissions + wall-time of one cohort round —
        the §III-D accounting every lock-step strategy reports identically.

        Returns ``(sel_mask, co2_g, duration_s)``.
        """
        # the first host read here (float(co2)) waits for everything queued
        # before it on the device, the round's aggregation and server
        # update included: this span, not ``aggregate``, holds that drain
        with self.tracer.span("accounting"):
            sel_mask = jnp.zeros(self.train.n_clients, bool).at[jnp.asarray(sel)].set(True)
            co2, _ = carbon_mod.round_emissions_g(
                self.fleet, sel_mask, t_hours, self.round_flops, None
            )
            dur = carbon_mod.round_duration_s(
                self.fleet, sel_mask, self.round_flops, self.model_bytes
            )
            return sel_mask, float(co2), float(dur)

    def policy_update(self, sel_mask, acc: float, dur: float, co2: float, inten) -> float:
        """One MARL reward update of the fleet-level orchestrator state
        (no-op returning 0.0 for non-RL selectors).

        Reward calibration: accuracy enters Eq. 4 as a fraction — with
        alpha=15 a typical +0.05 round gives +0.75 reward, commensurate with
        the CO2 term (co2/1000 ~ 0.25); percent scale would make early jumps
        (+75) lock the Q-table onto the first cohort selected.  The
        efficiency signal is ``-dur/100`` (faster rounds reward).  Strategies
        with per-region orchestrator instances (async) keep their own update
        site; this helper is the single fleet-level one, so the reward terms
        cannot drift between the strategies that share it.
        """
        with self.tracer.span("policy_update"):
            if not self.uses_rl:
                return 0.0
            self.orch_state, r = orch.update(
                self.orch_state, np.asarray(sel_mask), jnp.float32(acc),
                jnp.float32(-dur / 100.0), jnp.float32(co2), jnp.mean(inten),
            )
            return float(r)

    # ------------------------------------------------------------------
    def evaluate(self, params) -> float:
        with self.tracer.span("eval"):
            accs, n = [], 0
            for batch in eval_batches(self.test_data, 256):
                m = self.eval_fn(params, {k: jnp.asarray(v) for k, v in batch.items()})
                accs.append(float(m["acc"]))
                n += 1
                if n >= self.train.max_eval_batches:
                    break
            return float(np.mean(accs)) if accs else 0.0


def _device_store(clients: list[ClientDataset]):
    """Upload the clients' training data once.

    Returns ``(store, offsets, gather)``: ``store`` maps each data key to
    one device array holding the distinct ``data`` dicts back to back,
    with each sample flattened to one row, so small trailing dimensions
    (an image's 3 channels) are not padded to the chip's tile;
    ``offsets[i]`` is client i's first row in it; ``gather(store, idx)``
    turns (k, n_steps, batch) store rows into the cohort's batches, in the
    shapes and dtypes the host stack gave.
    """
    parts = list({id(c.data): c.data for c in clients}.values())
    sizes = [len(next(iter(d.values()))) for d in parts]
    if sum(sizes) >= 2 ** 31:
        raise ValueError(f"the clients hold {sum(sizes)} samples; int32 indices reach 2**31 - 1")
    starts = dict(zip(map(id, parts), np.cumsum([0] + sizes[:-1])))
    shapes = {k: v.shape[1:] for k, v in parts[0].items()}

    def rows(k):
        flat = [np.asarray(d[k]).reshape(len(d[k]), -1) if shapes[k] else np.asarray(d[k])
                for d in parts]
        return flat[0] if len(flat) == 1 else np.concatenate(flat)

    store = {k: jax.device_put(rows(k)) for k in shapes}
    offsets = np.array([starts[id(c.data)] for c in clients], np.int32)

    # named, so the profiler shows the program as ``jit_cohort_batches``
    @jax.jit
    def cohort_batches(store, idx):
        return {k: v[idx].reshape(idx.shape + shapes[k]) for k, v in store.items()}

    return store, offsets, cohort_batches


def _resolve_selector(selector, cfg: ExperimentConfig) -> tuple[Callable, bool]:
    """Selector registry lookup: None -> cfg.orchestrator.selection, a name
    -> POLICIES[name], a callable -> used as-is (``uses_rl`` attribute opts
    into the MARL reward update)."""
    if selector is None:
        selector = cfg.orchestrator.selection
    if isinstance(selector, str):
        return POLICIES[selector], policy_uses_rl(selector)
    return selector, bool(getattr(selector, "uses_rl", False))
