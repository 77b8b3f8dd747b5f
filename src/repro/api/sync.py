"""Synchronous round-loop strategy (the paper's §IV protocol).

One ``run`` = ``rounds`` lock-step federated rounds: carbon-aware selection,
one cohort-training dispatch, the privacy pipeline, one server
update, then emissions accounting and the MARL reward — emitting one typed
:class:`~repro.api.telemetry.RoundEvent` per round.

The subsystem wiring lives in :class:`~repro.api.runtime.RuntimeContext`,
which the asynchronous and gossip strategies compose too.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.api.config import ExperimentConfig
from repro.api.pipeline import cohort_wire_bytes
from repro.api.runtime import RuntimeContext
from repro.api.telemetry import SYNC_HISTORY_KEYS, RoundEvent
from repro.core import carbon as carbon_mod
from repro.fl import client as client_mod
from repro.fl import server as server_mod
from repro.privacy import dp as dp_mod
from repro.privacy.accountant import SubsampledAccountant


class SyncStrategy:
    """Flat synchronous aggregation: every round waits for its whole cohort."""

    name = "sync"
    history_keys = SYNC_HISTORY_KEYS

    def validate(self, cfg: ExperimentConfig) -> None:
        pass  # every algorithm/selection combination is defined synchronously

    def setup(self, ctx: RuntimeContext) -> None:
        self.key = jax.random.PRNGKey(ctx.train.seed)
        dp = ctx.privacy.dp
        self.accountant = (
            SubsampledAccountant(dp.delta)
            if dp is not None and ctx.privacy.accounting == "per_region"
            else None
        )
        # run-loop state lives on the strategy so a checkpoint can capture it
        # mid-run; start_round > 0 means "resumed" and skips the initial eval
        self.start_round = 0
        self.co2_l: list[float] = []
        self.dur_l: list[float] = []
        self.cum_co2 = 0.0
        self.acc: float = 0.0
        self.last_acc: float = 0.0

    # ------------------------------------------------------------------
    def state_dict(self, ctx: RuntimeContext) -> dict:
        """Everything the round loop needs to continue bitwise: the PRNG
        chain position, accumulators, cached eval, accountant step log, and
        the shared runtime state (server/orchestrator/control variates)."""
        s = {
            "rounds_done": self.start_round,
            "key": np.asarray(self.key),
            "co2_l": list(self.co2_l),
            "dur_l": list(self.dur_l),
            "cum_co2": self.cum_co2,
            "acc": self.acc,
            "last_acc": self.last_acc,
            "runtime": ctx.state_dict(),
        }
        if self.accountant is not None:
            s["accountant"] = self.accountant.state_dict()
        return s

    def load_state_dict(self, ctx: RuntimeContext, s: dict) -> None:
        self.start_round = int(s["rounds_done"])
        self.key = jnp.asarray(np.asarray(s["key"]))
        self.co2_l = [float(v) for v in s["co2_l"]]
        self.dur_l = [float(v) for v in s["dur_l"]]
        self.cum_co2 = float(s["cum_co2"])
        self.acc = float(s["acc"])
        self.last_acc = float(s["last_acc"])
        if self.accountant is not None:
            self.accountant.load_state_dict(s["accountant"])
        ctx.load_state_dict(s["runtime"])

    # ------------------------------------------------------------------
    def _record_privacy(self, ctx: RuntimeContext, records, n_sel: int) -> None:
        """Compose this round's NoiseStage step into the subsampled
        accountant (``per_region`` accounting — the sync topology is one
        region spanning the whole fleet).  Called once at the aggregate
        site; :meth:`_spent_epsilon` is a pure query."""
        if self.accountant is None:
            return
        noise = [r for r in records if r.stage == "noise"]
        if noise:
            self.accountant.record(
                q=min(1.0, n_sel / ctx.train.n_clients), sigma=noise[-1].info["sigma"]
            )

    def _spent_epsilon(self, ctx: RuntimeContext, rounds_done: int) -> float:
        """Privacy spent so far: the configured global schedule by default,
        or whatever the NoiseStage-driven accountant has composed."""
        dp = ctx.privacy.dp
        if dp is None:
            return 0.0
        if self.accountant is None:
            return dp_mod.spent_epsilon(dp, rounds_done)
        return self.accountant.epsilon()

    # ------------------------------------------------------------------
    def run(self, ctx: RuntimeContext, emit) -> dict:
        train, cfg = ctx.train, ctx.cfg
        if self.start_round == 0:
            # fresh run; a resumed run restored the cached eval instead
            # (evaluate has no PRNG side effects, so skipping it is safe)
            self.acc = ctx.evaluate(ctx.server_state.params)
            self.last_acc = self.acc
        tracer = ctx.tracer
        for rnd in range(self.start_round, train.rounds):
            if ctx.engine is not None and ctx.engine.past_horizon():
                break  # engine.sim_hours horizon reached on the simulated clock
            with tracer.span("round", round=rnd, strategy=self.name) as round_sp:
                self.key, k_sel, k_int, k_agg, k_noise = jax.random.split(self.key, 5)
                t_hours = rnd * cfg.carbon.round_hours
                inten = carbon_mod.intensity(ctx.fleet, t_hours, k_int)

                with tracer.span("select", round=rnd):
                    mask, ctx.orch_state = ctx.policy(
                        k_sel, ctx.orch_state, ctx.fleet, inten, train.clients_per_round
                    )
                    sel = np.flatnonzero(np.asarray(mask))[: train.clients_per_round]

                # --- cohort local training: one jit call per round -------------
                weights = [len(ctx.clients[ci]) for ci in sel]
                if train.algorithm == "scaffold":
                    corrs = jax.tree.map(
                        lambda c, *cis: jnp.stack([c - ci for ci in cis]),
                        ctx.server_state.c, *[ctx.c_locals[ci] for ci in sel],
                    )
                else:
                    corrs = None  # train_cohort broadcasts the zero correction
                with tracer.span("train", round=rnd, cohort=len(sel)):
                    res = ctx.train_cohort(
                        ctx.server_state.params, sel, rnd, corrections=corrs
                    )
                    losses = [float(l) for l in res.loss_last]

                c_deltas = []
                if train.algorithm == "scaffold":
                    # control-variate updates need per-client pytree deltas: fold
                    # the rows back through the single conversion site
                    for j, ci in enumerate(sel):
                        delta_j = ctx.pspace.unravel(res.rows[j])
                        new_ci = client_mod.scaffold_new_control(
                            ctx.c_locals[ci], ctx.server_state.c, delta_j,
                            res.n_steps[j], train.client_lr,
                        )
                        c_deltas.append(jax.tree.map(lambda a, b: a - b, new_ci, ctx.c_locals[ci]))
                        ctx.c_locals[ci] = new_ci

                # a dispatch span: it ends once the privacy pipeline and the
                # server update are queued, not when the device has run them.
                # Device idle under it is host dispatch cost; the wait for the
                # device falls in ``accounting``, whose first host read comes
                # after these programs in the device's queue
                with tracer.span("aggregate", round=rnd, cohort=len(sel)):
                    if train.algorithm == "fednova":
                        deltas = [ctx.pspace.unravel(res.rows[j]) for j in range(len(sel))]
                        mean_delta = server_mod.fednova_mean_delta(deltas, weights, list(res.n_steps))
                        # float32 rows both ways — no pipeline records to price
                        wire = 2 * len(sel) * ctx.model_bytes
                    else:
                        mean_row, records = ctx.aggregate(
                            res.rows, weights, k_agg, clients=sel
                        )
                        mean_delta = ctx.pspace.unravel(mean_row)
                        self._record_privacy(ctx, records, len(sel))
                        wire = cohort_wire_bytes(
                            records, len(sel), ctx.model_bytes, ctx.param_dim
                        )
                    with tracer.span("server_update", round=rnd):
                        ctx.server_state = ctx.server_apply(ctx.server_state, mean_delta)
                        if train.algorithm == "scaffold" and c_deltas:
                            ctx.server_state = server_mod.scaffold_update_c(
                                ctx.server_state, c_deltas, train.n_clients
                            )

                # ---- carbon + time accounting -------------------------------
                sel_mask, co2, dur = ctx.round_accounting(sel, t_hours)
                self.cum_co2 += co2
                if ctx.engine is not None:
                    # barrier event on the simulated clock; with jitter=0 the
                    # engine echoes the analytic duration back bitwise (the
                    # legacy-equivalence anchor), so dur is unchanged there
                    sim_dur = ctx.engine.round_barrier(sel, dur)
                    round_sp.set(
                        sim_s=sim_dur, sim_time_s=ctx.engine.clock.now_s
                    )
                    if ctx.engine.cfg.latency_jitter > 0.0:
                        dur = sim_dur

                # ---- evaluation + MARL update --------------------------------
                if (rnd + 1) % train.eval_every == 0 or rnd == train.rounds - 1:
                    self.acc = ctx.evaluate(ctx.server_state.params)
                r = ctx.policy_update(sel_mask, self.acc, dur, co2, inten)
                eps_spent = self._spent_epsilon(ctx, rnd + 1)
                self.co2_l.append(co2)
                self.dur_l.append(dur)
                self.last_acc = self.acc
                round_sp.set(co2_g=co2, bytes=wire)
                emit(RoundEvent(
                    round=rnd, acc=self.acc, loss=float(np.mean(losses)) if losses else 0.0,
                    co2_g=co2, cum_co2_g=self.cum_co2, duration_s=dur, reward=r,
                    eps_spent=eps_spent, selected=tuple(int(c) for c in sel),
                    wire_bytes=wire,
                    sim_time_s=ctx.engine.clock.now_s if ctx.engine is not None else 0.0,
                ))
            self.start_round = rnd + 1
            ctx.checkpoint_round(self, rnd)
        return {
            "final_acc": self.last_acc,
            "mean_co2_g": float(np.mean(self.co2_l)) if self.co2_l else 0.0,
            "mean_duration_s": float(np.mean(self.dur_l)) if self.dur_l else 0.0,
            "cum_co2_total_g": self.cum_co2,
        }
