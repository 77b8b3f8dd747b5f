"""Decentralized gossip strategy — peer-to-peer mixing, no server.

MetaFed is pitched as a *decentralized* framework, yet the sync and async
strategies still funnel every update through a central server or an
edge→global tree.  Here there is no aggregation point at all: every client
keeps its OWN model (one ParamSpace row of the fleet-wide ``(n, P)`` state),
and a round is

    1. carbon-aware selection of a cohort (same policy/PRNG schedule as the
       sync strategy — selection stays bitwise comparable),
    2. each selected node trains locally *from its own model*
       (``RuntimeContext.train_cohort_rows``),
    3. ``TopologyConfig.mixing_steps`` gossip passes X ← W X over the
       cohort's rows, where W is the round's Metropolis–Hastings mixing
       matrix on the configured graph (``repro.topo.graph``) — the fused
       Pallas ``gossip_mix`` kernel on TPU, the einsum oracle on CPU,
    4. optionally, carbon-aware reweighting tilts W toward peers sitting on
       a green grid (``TopologyConfig.carbon_beta`` > 0) before mixing —
       the decentralized analogue of carbon-aware selection.

Evaluation reports the *average model* x̄ = mean_i x_i, the standard
decentralized-SGD metric; the per-round :class:`~repro.api.telemetry.MixEvent`
carries the fleet-wide consensus distance, the spectral gap of the mixing
matrix actually applied, and the network bytes the mixing moved.

**FedAvg-equivalence anchor** (``tests/test_topo.py``): with the complete
graph (uniform 1/k Metropolis weights), one mixing step, full participation
and equal client weights, every round leaves the whole fleet in consensus at
exactly the FedAvg iterate — ``"gossip"`` reproduces ``SyncStrategy``
trajectories allclose.  Partial participation, sparse graphs, fewer mixing
steps and carbon tilting then relax that baseline along measurable axes
(consensus distance > 0, spectral gap < 1).

Privacy pipeline stages are rejected up front: they are server-side
(mask/noise *the aggregate*), and gossip has no aggregation site — a
secure-gossip variant needs pairwise masking, a different construction.
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.api.config import ExperimentConfig
from repro.api.runtime import RuntimeContext
from repro.api.telemetry import GOSSIP_HISTORY_KEYS, MixEvent
from repro.core import carbon as carbon_mod
from repro.topo import gossip as gossip_mod
from repro.topo import graph as graph_mod


class GossipStrategy:
    """Serverless aggregation: per-node models, neighbor mixing each round."""

    name = "gossip"
    history_keys = GOSSIP_HISTORY_KEYS

    # ------------------------------------------------------------------
    def validate(self, cfg: ExperimentConfig) -> None:
        train, topo, priv = cfg.training, cfg.topology, cfg.privacy
        if train.algorithm not in ("fedavg", "fedprox"):
            raise ValueError(
                f"{train.algorithm!r} needs a server (adaptive server optimizer "
                "/ control variates / step normalization); gossip supports "
                "'fedavg' and 'fedprox' local rules."
            )
        if priv.secure_agg or priv.dp is not None or priv.topk_density > 0:
            raise ValueError(
                "the privacy pipeline stages are server-side (they "
                "sparsify/mask/noise the aggregate) and gossip has no "
                "aggregation site; run privacy experiments on the 'sync' or "
                "'async_hier' strategies."
            )
        if train.sharded:
            raise ValueError(
                "gossip trains each node from its own model row; the sharded "
                "cohort engine (TrainingConfig.sharded) only covers the "
                "shared-params trainers — run gossip unsharded."
            )
        if topo.graph not in graph_mod.GRAPHS:
            raise ValueError(
                f"unknown graph {topo.graph!r}; registered: {sorted(graph_mod.GRAPHS)}"
            )
        if topo.mixing_steps < 1:
            raise ValueError("mixing_steps must be >= 1")
        if not 0.0 < topo.gossip_p <= 1.0:
            raise ValueError("gossip_p must be in (0, 1]")
        if topo.carbon_beta < 0.0:
            raise ValueError("carbon_beta must be >= 0")

    def setup(self, ctx: RuntimeContext) -> None:
        # validate() rejects the privacy *flags*, but a hand-composed
        # pipeline passed via Federation(privacy=...) reaches the context
        # anyway — and this strategy never calls ctx.aggregate, so silently
        # accepting one would report a privacy run that never executed
        if ctx.pipeline.describe():
            raise ValueError(
                "gossip never aggregates server-side, so the supplied "
                f"privacy pipeline ({' -> '.join(ctx.pipeline.describe())}) "
                "would not run; remove it or use the 'sync'/'async_hier' "
                "strategies."
            )
        self.key = jax.random.PRNGKey(ctx.train.seed)
        # fleet state: one model row per client, all starting at params0
        row0 = ctx.pspace.ravel(ctx.server_state.params)
        self.node_rows = jnp.tile(row0[None, :], (ctx.train.n_clients, 1))
        # run-loop state on the strategy so checkpoints capture it mid-run
        self.start_round = 0
        self.co2_l: list[float] = []
        self.dur_l: list[float] = []
        self.gap_l: list[float] = []
        self.cum_co2 = 0.0
        self.mix_bytes_total = 0.0
        self.acc: float = 0.0
        self.last_acc: float = 0.0
        self.consensus = 0.0

    # ------------------------------------------------------------------
    def state_dict(self, ctx: RuntimeContext) -> dict:
        """Serverless state is the whole fleet: the (n, P) per-node model
        rows plus the PRNG chain, accumulators and the shared runtime's
        orchestrator state (gossip never touches the server optimizer, but
        its selection policy mutates ``orch_state``)."""
        return {
            "rounds_done": self.start_round,
            "key": np.asarray(self.key),
            "node_rows": np.asarray(self.node_rows),
            "co2_l": list(self.co2_l),
            "dur_l": list(self.dur_l),
            "gap_l": list(self.gap_l),
            "cum_co2": self.cum_co2,
            "mix_bytes_total": self.mix_bytes_total,
            "acc": self.acc,
            "last_acc": self.last_acc,
            "consensus": self.consensus,
            "runtime": ctx.state_dict(),
        }

    def load_state_dict(self, ctx: RuntimeContext, s: dict) -> None:
        n, dim = int(ctx.train.n_clients), int(ctx.pspace.dim)
        rows = np.asarray(s["node_rows"])
        if rows.shape != (n, dim):
            raise ValueError(
                f"node_rows shape mismatch: checkpoint has {rows.shape}, "
                f"this run needs {(n, dim)}"
            )
        self.start_round = int(s["rounds_done"])
        self.key = jnp.asarray(np.asarray(s["key"]))
        self.node_rows = jnp.asarray(rows)
        self.co2_l = [float(v) for v in s["co2_l"]]
        self.dur_l = [float(v) for v in s["dur_l"]]
        self.gap_l = [float(v) for v in s["gap_l"]]
        self.cum_co2 = float(s["cum_co2"])
        self.mix_bytes_total = float(s["mix_bytes_total"])
        self.acc = float(s["acc"])
        self.last_acc = float(s["last_acc"])
        self.consensus = float(s["consensus"])
        ctx.load_state_dict(s["runtime"])

    # ------------------------------------------------------------------
    def mean_model(self, ctx: RuntimeContext):
        """The average model x̄ over all node rows (the evaluation target)."""
        return ctx.pspace.unravel(jnp.mean(self.node_rows, axis=0))

    # ------------------------------------------------------------------
    def run(self, ctx: RuntimeContext, emit: Callable) -> dict:
        train, cfg, topo = ctx.train, ctx.cfg, ctx.topology
        if self.start_round == 0:
            self.acc = ctx.evaluate(self.mean_model(ctx))
            self.last_acc = self.acc
        tracer = ctx.tracer
        for rnd in range(self.start_round, train.rounds):
            if ctx.engine is not None and ctx.engine.past_horizon():
                break  # engine.sim_hours horizon reached on the simulated clock
            with tracer.span("round", round=rnd, strategy=self.name) as round_sp:
                # same 5-way split as the sync strategy: k_agg/k_noise are unused
                # (no server aggregation) but keeping the schedule makes the
                # selection stream bitwise comparable across strategies
                self.key, k_sel, k_int, k_agg, k_noise = jax.random.split(self.key, 5)
                t_hours = rnd * cfg.carbon.round_hours
                inten = carbon_mod.intensity(ctx.fleet, t_hours, k_int)

                with tracer.span("select", round=rnd):
                    mask, ctx.orch_state = ctx.policy(
                        k_sel, ctx.orch_state, ctx.fleet, inten, train.clients_per_round
                    )
                    sel = np.flatnonzero(np.asarray(mask))[: train.clients_per_round]
                sel_ix = jnp.asarray(sel)
                k = len(sel)

                # --- local training: each node from its own model row ----------
                with tracer.span("train", round=rnd, cohort=k):
                    res = ctx.train_cohort_rows(self.node_rows[sel_ix], sel, rnd)
                    losses = [float(l) for l in res.loss_last]
                    rows = self.node_rows[sel_ix] + res.rows

                # --- neighbor mixing over the round's cohort graph -------------
                plan = graph_mod.plan(topo.graph, k, rnd, seed=train.seed, p=topo.gossip_p)
                W = plan.mixing
                if topo.carbon_beta > 0.0:
                    W = gossip_mod.carbon_reweight(
                        W, np.asarray(inten)[sel], topo.carbon_beta
                    )
                # time-budgeted waves: engine.wave_budget_s > 0 sizes the
                # round's mixing passes by what the budget pays for at the
                # cohort's transfer rate, instead of the fixed mixing_steps
                steps = topo.mixing_steps
                if ctx.engine is not None and ctx.engine.cfg.wave_budget_s > 0.0:
                    steps = ctx.engine.wave_steps(ctx.fleet, sel, ctx.model_bytes)
                mix_bytes = float(steps * plan.bytes_per_step(ctx.pspace.nbytes))
                with tracer.span("mix", round=rnd, steps=steps,
                                 graph=topo.graph, bytes=mix_bytes):
                    for _ in range(steps):
                        rows = gossip_mod.mix_rows(ctx.pspace, rows, W)
                    self.node_rows = self.node_rows.at[sel_ix].set(rows)
                self.mix_bytes_total += mix_bytes
                gap = graph_mod.spectral_gap(W)  # of the matrix actually applied

                # ---- carbon + time accounting (training cost = sync's) --------
                sel_mask, co2, dur = ctx.round_accounting(sel, t_hours)
                self.cum_co2 += co2
                if ctx.engine is not None:
                    sim_dur = ctx.engine.gossip_wave(
                        ctx.fleet, sel, ctx.model_bytes, steps, dur
                    )
                    round_sp.set(
                        sim_s=sim_dur, sim_time_s=ctx.engine.clock.now_s
                    )
                    if ctx.engine.cfg.wave_budget_s > 0.0:
                        dur = sim_dur

                # ---- evaluation (average model) + MARL update ------------------
                if (rnd + 1) % train.eval_every == 0 or rnd == train.rounds - 1:
                    self.acc = ctx.evaluate(self.mean_model(ctx))
                self.consensus = gossip_mod.consensus_distance(self.node_rows)
                r = ctx.policy_update(sel_mask, self.acc, dur, co2, inten)
                self.co2_l.append(co2)
                self.dur_l.append(dur)
                self.gap_l.append(gap)
                self.last_acc = self.acc
                round_sp.set(co2_g=co2, bytes=mix_bytes)
                emit(MixEvent(
                    round=rnd, acc=self.acc, loss=float(np.mean(losses)) if losses else 0.0,
                    co2_g=co2, cum_co2_g=self.cum_co2, duration_s=dur, reward=r,
                    eps_spent=0.0, selected=tuple(int(c) for c in sel),
                    consensus=self.consensus, spectral_gap=gap,
                    mix_steps=steps, mix_bytes=mix_bytes,
                    sim_time_s=ctx.engine.clock.now_s if ctx.engine is not None else 0.0,
                ))
            self.start_round = rnd + 1
            ctx.checkpoint_round(self, rnd)
        return {
            "final_acc": self.last_acc,
            "mean_co2_g": float(np.mean(self.co2_l)) if self.co2_l else 0.0,
            "mean_duration_s": float(np.mean(self.dur_l)) if self.dur_l else 0.0,
            "cum_co2_total_g": self.cum_co2,
            "final_consensus": self.consensus,
            "mean_spectral_gap": float(np.mean(self.gap_l)) if self.gap_l else 0.0,
            "mix_bytes_total": self.mix_bytes_total,
        }
