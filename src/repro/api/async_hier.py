"""Asynchronous + hierarchical strategy (FedBuff-style buffered aggregation).

An event-driven strategy that composes the shared
:class:`~repro.api.runtime.RuntimeContext` (same cohort trainer, same
privacy pipeline, same server optimizer as the sync strategy) and plugs into
:class:`~repro.api.federation.Federation` through the ``Strategy`` protocol.

Behavior:

  * **Buffered async aggregation** — each region's edge aggregator applies an
    update whenever K client deltas have arrived, each delta down-weighted by
    ``1/sqrt(1 + staleness)``; the buffer reduction streams device-resident
    ``(P,)`` ParamSpace rows through the privacy pipeline into the fused
    Pallas kernels (per-client delta pytrees are never materialized).
  * **Edge→global hierarchy** — phase-coherent regions
    (``repro.fl.hierarchy``), each with its own carbon trace, selector +
    MARL orchestrator instance, syncing its accumulated delta row to the
    global server every ``edge_sync_every`` flushes, down-weighted by the
    global-tier staleness.
  * **Staleness-aware selection** — every flush feeds observed staleness
    into the orchestrator's straggler EMA (``orchestrator.observe_staleness``).
  * **Event-driven clock** — a ``repro.engine`` ``SimClock`` advanced to
    each completion event popped from an ``EventQueue`` (the engine core
    this strategy's hand-rolled heap was factored into).  Completion times
    come from the fleet latency model scaled by ``latency_spread`` — or,
    when ``ExperimentConfig.engine.trace`` is set, from the clients'
    recorded latency streams (``EngineRuntime.completion_latencies``).

**Sync-equivalence anchor**: ``latency_spread=0``, ``buffer_k =
clients_per_round = concurrency``, one region, ``edge_sync_every=1`` makes
every flush exactly one synchronous round — same PRNG schedule, same
kernels, same server update — so this strategy reproduces ``SyncStrategy``
trajectories (see ``tests/test_async.py`` / ``tests/test_api.py``).

**Per-region DP accounting** (``PrivacyConfig.accounting="per_region"``):
each edge region owns a :class:`~repro.privacy.accountant.SubsampledAccountant`
fed by the pipeline's ``NoiseStage`` records — the subsampling rate is the
flushed cohort over the *region's* population, which the global per-flush
schedule (``accounting="global"``, the default) cannot express.  The
reported ``eps_spent`` is the worst region's epsilon (a client participates
in exactly one region, so the worst region bounds every client's loss);
per-region values land in the ``eps_by_region`` summary.
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.api.config import ExperimentConfig
from repro.api.pipeline import cohort_wire_bytes
from repro.api.runtime import RuntimeContext
from repro.api.telemetry import ASYNC_HISTORY_KEYS, FlushEvent
from repro.core import carbon as carbon_mod
from repro.core import orchestrator as orch
from repro.engine.clock import SimClock
from repro.engine.events import EventQueue
from repro.fl import hierarchy
from repro.privacy import dp as dp_mod
from repro.privacy.accountant import SubsampledAccountant


def _pack_entry(e: hierarchy.BufferEntry) -> dict:
    """BufferEntry -> plain container (checkpoint form)."""
    return {
        "client": e.client, "local": e.local, "version": e.version,
        "wave": e.wave, "weight": e.weight, "loss": e.loss,
        "t_hours": e.t_hours, "row": np.asarray(e.row),
        "k_agg": np.asarray(e.k_agg), "inten": np.asarray(e.inten),
    }


def _unpack_entry(d: dict) -> hierarchy.BufferEntry:
    return hierarchy.BufferEntry(
        client=int(d["client"]), local=int(d["local"]),
        version=int(d["version"]), wave=int(d["wave"]),
        weight=float(d["weight"]), row=jnp.asarray(np.asarray(d["row"])),
        loss=float(d["loss"]), t_hours=float(d["t_hours"]),
        k_agg=jnp.asarray(np.asarray(d["k_agg"])),
        inten=jnp.asarray(np.asarray(d["inten"])),
    )


class AsyncHierStrategy:
    """Event-driven buffered aggregation under an edge→global hierarchy."""

    name = "async_hier"
    history_keys = ASYNC_HISTORY_KEYS

    # ------------------------------------------------------------------
    def validate(self, cfg: ExperimentConfig) -> None:
        train, topo = cfg.training, cfg.topology
        if train.algorithm in ("scaffold", "fednova"):
            raise ValueError(
                f"{train.algorithm!r} needs synchronized per-cohort state "
                "(control variates / step normalization) and is not defined "
                "for buffered-async aggregation; use the sync strategy."
            )
        if topo.edge_sync_every < 1:
            raise ValueError("edge_sync_every must be >= 1")
        if topo.staleness_cap < 0:
            raise ValueError("staleness_cap must be >= 0")
        if topo.buffer_k < 0 or topo.concurrency < 0:
            raise ValueError("buffer_k and concurrency must be >= 0 (0 = clients_per_round)")

    def setup(self, ctx: RuntimeContext) -> None:
        train, topo = ctx.train, ctx.topology
        self.buffer_k = topo.buffer_k or train.clients_per_round
        self.concurrency = topo.concurrency or train.clients_per_round
        # constant for the run: per-client latency vector the event clock draws from
        self.client_durs = np.asarray(
            carbon_mod.client_durations_s(ctx.fleet, ctx.round_flops, ctx.model_bytes)
        )
        self.global_version = 0  # bumped per edge->global server update
        dp = ctx.privacy.dp
        per_region = dp is not None and ctx.privacy.accounting == "per_region"
        self.accountants = {}
        self.regions: list[hierarchy.Region] = []
        root = jax.random.PRNGKey(train.seed)
        for ridx, ids in enumerate(hierarchy.assign_regions(ctx.fleet, topo.n_regions)):
            # a single region keeps the root key so its PRNG stream (and
            # therefore selection/masking/noise) is bitwise the sync strategy's
            key = root if topo.n_regions == 1 else jax.random.fold_in(root, ridx)
            self.regions.append(hierarchy.Region(
                idx=ridx,
                clients=ids,
                fleet=hierarchy.subfleet(ctx.fleet, ids),
                policy=ctx.policy,
                orch_state=orch.init_state(
                    len(ids), stale_in_state=ctx.cfg.orchestrator.stale_in_state
                ),
                key=key,
                edge_params=ctx.server_state.params,
                edge_accum=ctx.pspace.zeros_row(),
            ))
            if per_region:
                self.accountants[ridx] = SubsampledAccountant(dp.delta)
        # event-clock state (repro.engine core); reset on the first run()
        # call, or restored by load_state_dict, which flips _started so
        # run() continues mid-queue
        self.clock = SimClock()
        self.events = EventQueue()   # payload: (region idx, BufferEntry)
        self._started = False
        self._active = None  # (ridx, trigger entry) while draining a region

    @property
    def now(self) -> float:
        """Simulated seconds — the event clock's current position."""
        return self.clock.now_s

    # ------------------------------------------------------------------
    def state_dict(self, ctx: RuntimeContext) -> dict:
        """The whole event engine: clock, heap (packed BufferEntries),
        per-region edge state (models, accumulators, buffers, MARL state,
        PRNG streams, wave/flush counters), per-region accountant step
        logs, and the shared runtime — everything the trajectory depends
        on, so a resumed run replays the same event sequence bitwise."""
        from repro.checkpoint.state import pack_tree

        regions = []
        for reg in self.regions:
            regions.append({
                "key": np.asarray(reg.key),
                "orch_state": pack_tree(reg.orch_state),
                "edge_params": pack_tree(reg.edge_params),
                "edge_accum": np.asarray(reg.edge_accum),
                "version": reg.version, "waves": reg.waves,
                "flushes": reg.flushes, "pending": reg.pending,
                "inflight": reg.inflight, "synced_version": reg.synced_version,
                "co2_g": reg.co2_g,
                "buffer": [_pack_entry(e) for e in reg.buffer],
                # msgpack maps need str keys; waves are ints
                "wave_flushes": {str(k): v for k, v in reg.wave_flushes.items()},
            })
        return {
            "flushes": self.flushes,
            "clock": self.clock.state_dict(),
            "global_version": self.global_version,
            "co2_l": list(self.co2_l),
            "dur_l": list(self.dur_l),
            "stale_l": list(self.stale_l),
            "cum_co2": self.cum_co2,
            "acc": self.acc,
            "last_acc": self.last_acc,
            "events": self.events.state_dict(
                pack=lambda p: {"ridx": p[0], "entry": _pack_entry(p[1])}
            ),
            "active": (
                None if self._active is None
                else {"ridx": self._active[0], "entry": _pack_entry(self._active[1])}
            ),
            "regions": regions,
            "accountants": {str(r): a.state_dict() for r, a in self.accountants.items()},
            "runtime": ctx.state_dict(),
        }

    def load_state_dict(self, ctx: RuntimeContext, s: dict) -> None:
        from repro.checkpoint.state import unpack_tree

        if len(s["regions"]) != len(self.regions):
            raise ValueError(
                f"region count mismatch: checkpoint has {len(s['regions'])}, "
                f"this run has {len(self.regions)}"
            )
        self.flushes = int(s["flushes"])
        self.clock.load_state_dict(s["clock"])
        self.global_version = int(s["global_version"])
        self.co2_l = [float(v) for v in s["co2_l"]]
        self.dur_l = [float(v) for v in s["dur_l"]]
        self.stale_l = [float(v) for v in s["stale_l"]]
        self.cum_co2 = float(s["cum_co2"])
        self.acc = float(s["acc"])
        self.last_acc = float(s["last_acc"])
        # restored in saved order: a valid heap restored verbatim pops in
        # the same sequence, which is what keeps the event replay bitwise
        self.events.load_state_dict(
            s["events"],
            unpack=lambda d: (int(d["ridx"]), _unpack_entry(d["entry"])),
        )
        self._active = (
            None if s["active"] is None
            else (int(s["active"]["ridx"]), _unpack_entry(s["active"]["entry"]))
        )
        for reg, rs in zip(self.regions, s["regions"]):
            reg.key = jnp.asarray(np.asarray(rs["key"]))
            reg.orch_state = unpack_tree(rs["orch_state"], reg.orch_state)
            reg.edge_params = unpack_tree(rs["edge_params"], reg.edge_params)
            reg.edge_accum = jnp.asarray(np.asarray(rs["edge_accum"]))
            reg.version = int(rs["version"])
            reg.waves = int(rs["waves"])
            reg.flushes = int(rs["flushes"])
            reg.pending = int(rs["pending"])
            reg.inflight = int(rs["inflight"])
            reg.synced_version = int(rs["synced_version"])
            reg.co2_g = float(rs["co2_g"])
            reg.buffer = [_unpack_entry(d) for d in rs["buffer"]]
            reg.wave_flushes = {int(k): int(v) for k, v in rs["wave_flushes"].items()}
        for r, a in self.accountants.items():
            a.load_state_dict(s["accountants"][str(r)])
        ctx.load_state_dict(s["runtime"])
        self._started = True

    # ------------------------------------------------------------------
    def _dispatch(self, ctx: RuntimeContext, reg: hierarchy.Region) -> None:
        """Select a wave in ``reg``, train it against the current edge model,
        and enqueue per-client completion events."""
        train = ctx.train
        now = self.clock.now_s
        k = min(train.clients_per_round, reg.n)
        reg.key, k_sel, k_int, k_agg, k_noise = jax.random.split(reg.key, 5)
        t_hours = reg.waves * ctx.carbon.round_hours
        inten = carbon_mod.intensity(reg.fleet, t_hours, k_int)
        with ctx.tracer.span("select", region=reg.idx, wave=reg.waves):
            mask, reg.orch_state = reg.policy(k_sel, reg.orch_state, reg.fleet, inten, k)
            sel_local = np.flatnonzero(np.asarray(mask))[:k]
            sel_global = reg.global_ids(sel_local)

        with ctx.tracer.span("train", region=reg.idx, wave=reg.waves,
                             cohort=len(sel_global)):
            res = ctx.train_cohort(reg.edge_params, sel_global, reg.waves)

        if ctx.engine is not None:
            # trace-driven latencies: each client's recorded arrival stream
            # (cycled), blended with the analytic model by latency_jitter —
            # this replaces the latency_spread interpolation entirely
            lat = ctx.engine.completion_latencies(sel_global)
            comp = now + carbon_mod.ROUND_OVERHEAD_S + lat
        else:
            durs = self.client_durs[np.asarray(sel_global)]
            mean_d = float(np.mean(durs))
            # latency_spread interpolates between "wave lands together" (0,
            # the sync-equivalence anchor) and the full heterogeneous fleet
            # model (1)
            spread = ctx.topology.latency_spread
            comp = now + carbon_mod.ROUND_OVERHEAD_S + mean_d + spread * (durs - mean_d)
        for j, (ci, li) in enumerate(zip(sel_global, sel_local)):
            entry = hierarchy.BufferEntry(
                client=int(ci), local=int(li), version=reg.version, wave=reg.waves,
                weight=float(len(ctx.clients[ci])),
                row=res.rows[j],  # device-resident (P,) slice — no host pytree
                loss=float(res.loss_last[j]), t_hours=t_hours, k_agg=k_agg,
                inten=inten,
            )
            self.events.push(float(comp[j]), (reg.idx, entry))
        reg.waves += 1
        reg.inflight += len(sel_global)

    def _maybe_dispatch(self, ctx: RuntimeContext, reg: hierarchy.Region) -> None:
        k = min(ctx.train.clients_per_round, reg.n)
        while reg.inflight + k <= max(self.concurrency, k):
            self._dispatch(ctx, reg)

    # ------------------------------------------------------------------
    def _edge_sync(self, ctx: RuntimeContext, reg: hierarchy.Region) -> None:
        """Push the region's accumulated delta row to the global server.

        The accumulator is tracked additively (never re-derived as
        edge_params - global_params) and the pytree form of the delta is
        produced exactly once, at the server-update boundary, so with one
        region and edge_sync_every=1 the global update is bitwise the sync
        strategy's.  The sync is weighted by the *global-tier* staleness
        ``1/sqrt(1 + tau_g)`` where ``tau_g`` counts global model versions
        applied since this edge last synced — a region that lagged while
        others advanced the global model pushes a discounted delta instead
        of an unweighted one.  tau_g == 0 (single region, or no interleaved
        syncs) keeps the weight exactly 1.
        """
        if reg.pending == 0:
            return
        with ctx.tracer.span("edge_sync", region=reg.idx,
                             bytes=ctx.model_bytes):
            tau_g = self.global_version - reg.synced_version
            w_g = float(hierarchy.staleness_weight(tau_g, ctx.topology.staleness_cap))
            scale = w_g * reg.n / ctx.train.n_clients
            row = reg.edge_accum if scale == 1.0 else reg.edge_accum * scale
            ctx.server_state = ctx.server_apply(ctx.server_state, ctx.pspace.unravel(row))
        self.global_version += 1
        reg.synced_version = self.global_version
        reg.edge_params = ctx.server_state.params
        reg.edge_accum = ctx.pspace.zeros_row()
        reg.pending = 0

    def _emissions_for(self, ctx: RuntimeContext, entries) -> tuple[float, np.ndarray]:
        """gCO2 of the training behind ``entries``, grouped by dispatch phase.

        Returns (total_g, union participation mask over the global fleet).
        """
        co2 = 0.0
        union = np.zeros(ctx.train.n_clients, bool)
        for t in dict.fromkeys(e.t_hours for e in entries):  # stable unique
            ids = np.asarray([e.client for e in entries if e.t_hours == t])
            m = jnp.zeros(ctx.train.n_clients, bool).at[jnp.asarray(ids)].set(True)
            g, _ = carbon_mod.round_emissions_g(ctx.fleet, m, t, ctx.round_flops, None)
            co2 += float(g)
            union[ids] = True
        return co2, union

    def _flush(self, ctx: RuntimeContext, reg: hierarchy.Region, trigger: hierarchy.BufferEntry):
        """Apply one staleness-weighted buffer flush at ``reg``'s edge.

        Returns the per-flush record (co2, duration, staleness, ...) for the
        event stream; the aggregation runs the shared privacy pipeline with
        staleness-adjusted weights, so plain / secure-agg / DP paths behave
        exactly as in the sync strategy.
        """
        topo = ctx.topology
        entries = reg.buffer[: self.buffer_k]
        reg.buffer = reg.buffer[self.buffer_k:]
        taus = np.asarray([reg.version - e.version for e in entries])
        s = hierarchy.staleness_weight(taus, topo.staleness_cap)
        eff_w = [e.weight * float(si) for e, si in zip(entries, s)]
        rows = jnp.stack([e.row for e in entries])  # (k, P) — stays on device
        # one wave can trigger several flushes (buffer_k < wave size): the
        # first reuses the wave's k_agg verbatim (sync-equivalence anchor),
        # later ones fold the count in so no mask/noise stream ever repeats
        n_prior = reg.wave_flushes.get(trigger.wave, 0)
        reg.wave_flushes[trigger.wave] = n_prior + 1
        k_flush = trigger.k_agg if n_prior == 0 else jax.random.fold_in(trigger.k_agg, n_prior)
        with ctx.tracer.span("aggregate", region=reg.idx, cohort=len(entries)):
            mean_row, records = ctx.aggregate(
                rows, eff_w, k_flush, clients=[e.client for e in entries]
            )
        reg.edge_params = ctx.pspace.add_to_tree(reg.edge_params, mean_row)
        reg.edge_accum = reg.edge_accum + mean_row
        reg.version += 1
        reg.flushes += 1
        reg.pending += 1
        if reg.flushes % topo.edge_sync_every == 0:
            self._edge_sync(ctx, reg)

        # per-region subsampled accounting: the NoiseStage record carries the
        # sigma that actually ran; the sampling rate counts *distinct* clients
        # over the region.  A client with m entries in one flush (possible
        # when concurrency > clients_per_round) has sensitivity m·clip, so
        # the step is composed at the effective multiplier sigma/m —
        # conservative: epsilon can only be overestimated, never under.
        if reg.idx in self.accountants:
            noise = [r for r in records if r.stage == "noise"]
            if noise:
                counts: dict[int, int] = {}
                for e in entries:
                    counts[e.client] = counts.get(e.client, 0) + 1
                mult = max(counts.values())
                self.accountants[reg.idx].record(
                    q=min(1.0, len(counts) / reg.n),
                    sigma=noise[-1].info["sigma"] / mult,
                )

        # ---- carbon + modeled-time accounting (per dispatch-phase group) --
        co2, union = self._emissions_for(ctx, entries)
        dur = float(carbon_mod.round_duration_s(
            ctx.fleet, jnp.asarray(union), ctx.round_flops, ctx.model_bytes
        ))
        reg.co2_g += co2
        flush_mask = np.zeros(reg.n, bool)
        flush_mask[[e.local for e in entries]] = True
        wire = cohort_wire_bytes(records, len(entries), ctx.model_bytes, ctx.param_dim)
        return entries, taus, co2, dur, flush_mask, wire

    def _spent_epsilon(self, ctx: RuntimeContext, flushes: int) -> float:
        dp = ctx.privacy.dp
        if dp is None:
            return 0.0
        if self.accountants:
            return max(a.epsilon() for a in self.accountants.values())
        return dp_mod.spent_epsilon(dp, flushes)

    # ------------------------------------------------------------------
    def _drain(self, ctx: RuntimeContext, reg: hierarchy.Region,
               entry: hierarchy.BufferEntry, emit: Callable) -> None:
        """Flush ``reg``'s buffer while it holds >= K deltas, then refill
        the region's dispatch pipeline.  ``entry`` is the completion event
        that triggered the drain (its wave keys derive the flush PRNG).

        This is the inner loop of :meth:`run`, factored out so a checkpoint
        taken between two flushes of the same drain (``self._active``) can
        resume exactly where it stopped.
        """
        train = ctx.train
        while len(reg.buffer) >= self.buffer_k and self.flushes < train.rounds:
            with ctx.tracer.span("flush", region=reg.idx, flush=self.flushes) as fsp:
                entries, taus, co2, dur, flush_mask, wire = self._flush(ctx, reg, entry)
                fsp.set(co2_g=co2, bytes=wire, sim_time_s=self.clock.now_s)
            # straggler EMA: observed staleness per flushed client feeds
            # the MARL state so selection can demote chronic stragglers
            # (zero in the sync-equivalence regime -> no behavior change).
            # maximum.at: a client with two entries in one flush records
            # its worst staleness, not whichever entry came last.
            tau_vec = np.zeros(reg.n, np.float32)
            np.maximum.at(tau_vec, [e.local for e in entries], taus)
            reg.orch_state = orch.observe_staleness(reg.orch_state, flush_mask, tau_vec)
            self.cum_co2 += co2
            self.flushes += 1
            if self.flushes % train.eval_every == 0 or self.flushes == train.rounds:
                self.acc = ctx.evaluate(ctx.server_state.params)
            eff = -dur / 100.0
            if ctx.uses_rl:
                reg.orch_state, r = orch.update(
                    reg.orch_state, flush_mask, jnp.float32(self.acc),
                    jnp.float32(eff), jnp.float32(co2), jnp.mean(entry.inten),
                )
                r = float(r)
            else:
                r = 0.0
            stale = float(np.mean(taus))
            self.co2_l.append(co2)
            self.dur_l.append(dur)
            self.stale_l.append(stale)
            self.last_acc = self.acc
            emit(FlushEvent(
                round=self.flushes - 1, acc=self.acc,
                loss=float(np.mean([e.loss for e in entries])),
                co2_g=co2, cum_co2_g=self.cum_co2, duration_s=dur, reward=r,
                eps_spent=self._spent_epsilon(ctx, self.flushes),
                selected=tuple(e.client for e in entries),
                staleness=stale, region=reg.idx, sim_time_s=self.now,
                wire_bytes=wire,
            ))
            ctx.checkpoint_round(self, self.flushes - 1)
        if self.flushes < train.rounds:
            self._maybe_dispatch(ctx, reg)
        self._active = None

    def run(self, ctx: RuntimeContext, emit: Callable) -> dict:
        train = ctx.train
        if not self._started:
            self.co2_l: list[float] = []
            self.dur_l: list[float] = []
            self.stale_l: list[float] = []
            self.cum_co2 = 0.0
            self.acc = ctx.evaluate(ctx.server_state.params)
            self.last_acc = self.acc
            self.clock = SimClock()
            self.events = EventQueue()
            self.flushes = 0
            self._active = None
            for reg in self.regions:
                self._maybe_dispatch(ctx, reg)
            self._started = True
        elif self._active is not None:
            # resumed from a checkpoint taken between two flushes of one
            # drain: finish that region's drain before popping the heap
            ridx, entry = self._active
            self._drain(ctx, self.regions[ridx], entry, emit)

        while self.flushes < train.rounds and self.events:
            if ctx.engine is not None and ctx.engine.past_horizon(self.events.peek_time()):
                break  # next completion lands past the sim_hours horizon
            t, _, (ridx, entry) = self.events.pop()
            self.clock.advance_to(t)
            reg = self.regions[ridx]
            reg.inflight -= 1
            reg.buffer.append(entry)
            self._active = (ridx, entry)
            self._drain(ctx, reg, entry, emit)

        # drain: push any un-synced edge progress to the global model, and
        # charge emissions for training that was dispatched but never
        # flushed (in-flight at the rounds cap or left in a partial buffer)
        # — the energy was spent whether or not a flush consumed the delta
        unflushed = 0.0
        leftovers: dict[int, list] = {reg.idx: list(reg.buffer) for reg in self.regions}
        for _, _, (ridx, entry) in self.events:
            leftovers[ridx].append(entry)
        for reg in self.regions:
            g, _ = self._emissions_for(ctx, leftovers[reg.idx])
            reg.co2_g += g
            unflushed += g
        self.cum_co2 += unflushed
        pending = any(reg.pending for reg in self.regions)
        for reg in self.regions:
            self._edge_sync(ctx, reg)
        if pending:
            self.last_acc = ctx.evaluate(ctx.server_state.params)
        summary = {
            "final_acc": self.last_acc,
            "mean_co2_g": float(np.mean(self.co2_l)) if self.co2_l else 0.0,
            "mean_duration_s": float(np.mean(self.dur_l)) if self.dur_l else 0.0,
            "cum_co2_total_g": self.cum_co2,
            "unflushed_co2_g": unflushed,
            "mean_staleness": float(np.mean(self.stale_l)) if self.stale_l else 0.0,
            "buffer_flushes": {reg.idx: reg.flushes for reg in self.regions},
            "co2_by_region_g": {reg.idx: reg.co2_g for reg in self.regions},
        }
        if self.accountants:
            summary["eps_by_region"] = {
                ridx: a.epsilon() for ridx, a in self.accountants.items()
            }
        return summary
