"""``Federation`` — the composable public entry point for experiments.

One federated experiment is the composition of five swappable pieces::

    Federation(cfg, task,
               strategy=...,   # how updates reach the server (sync | async_hier)
               selector=...,   # who trains each round (random | green | rl | rl_green)
               privacy=...,    # what the server is allowed to see (PrivacyPipeline)
               telemetry=[...])  # who observes the run (RoundEvent/FlushEvent sinks)

``Federation.run()`` returns the same history-dict schema the legacy engines
did (the schema is rebuilt from the typed event stream by a
:class:`~repro.api.telemetry.HistoryRecorder`), so downstream tooling —
benchmarks, figures, claim checks — is unchanged.

A *strategy* owns the control flow between cohort training and server
updates and implements the :class:`Strategy` protocol; the two built-ins are
registered in :data:`STRATEGIES`:

    sync        lock-step rounds (``repro.api.sync.SyncStrategy``)
    async_hier  event-driven buffered aggregation under an edge→global
                hierarchy (``repro.api.async_hier.AsyncHierStrategy``)
    gossip      decentralized peer-to-peer mixing over graph topologies —
                no server at all (``repro.api.gossip.GossipStrategy``)

Strategies *compose* a shared :class:`~repro.api.runtime.RuntimeContext`
(dataflow, fleet, privacy pipeline, server optimizer) instead of inheriting
from one engine class — a new topology is one class plus one
:func:`register_strategy` call, no engine edits.

``build(cfg, task)`` is the registry constructor: it accepts an
``ExperimentConfig`` or its plain-dict form (experiment grids in JSON) and
resolves every component from the config's registry keys.
"""
from __future__ import annotations

from typing import Callable, Iterable, Optional, Protocol, Union, runtime_checkable

from repro.api.config import ExperimentConfig
from repro.api.pipeline import PrivacyPipeline
from repro.api.runtime import FederatedTask, RuntimeContext
from repro.api.telemetry import (CallbackSink, HistoryRecorder, RoundEvent,
                                 TelemetrySink)


@runtime_checkable
class Strategy(Protocol):
    """The control-flow plug of a federation.

    ``validate`` may reject a config up front (construction-time errors);
    ``setup`` builds any per-run state from the shared context; ``run``
    drives rounds/flushes, emitting one event per server-visible update via
    ``emit`` and returning the summary columns of the history dict.
    ``history_keys`` fixes the per-event history schema.
    """

    name: str
    history_keys: tuple[str, ...]

    def validate(self, cfg: ExperimentConfig) -> None: ...
    def setup(self, ctx: RuntimeContext) -> None: ...
    def run(self, ctx: RuntimeContext, emit: Callable[[RoundEvent], None]) -> dict: ...

    # Fault tolerance is opt-in for third-party strategies: ``state_dict``/
    # ``load_state_dict`` (mirroring the built-ins' signatures
    # ``state_dict(ctx) -> dict`` / ``load_state_dict(ctx, state)``) are only
    # required when ``Federation.run`` is asked to checkpoint or resume —
    # a strategy without them still runs, it just can't be checkpointed.


#: registry mapping ``TopologyConfig.mode`` names to strategy factories; the
#: built-ins land on first use (lazily — sync/async_hier import the runtime
#: stack, and package-init-time imports would cycle)
STRATEGIES: dict[str, Callable[[], Strategy]] = {}
_builtins_loaded = False


def _ensure_registry() -> dict[str, Callable[[], Strategy]]:
    global _builtins_loaded
    if not _builtins_loaded:
        from repro.api.async_hier import AsyncHierStrategy
        from repro.api.gossip import GossipStrategy
        from repro.api.sync import SyncStrategy

        STRATEGIES.setdefault("sync", SyncStrategy)
        STRATEGIES.setdefault("async_hier", AsyncHierStrategy)
        STRATEGIES.setdefault("gossip", GossipStrategy)
        _builtins_loaded = True
    return STRATEGIES


def register_strategy(name: str, factory: Callable[[], Strategy]) -> None:
    """Make ``TopologyConfig(mode=name)`` construct ``factory()`` — the one
    registration point a new aggregation topology needs."""
    _ensure_registry()[name] = factory


def strategy_names() -> tuple[str, ...]:
    return tuple(_ensure_registry())


class Federation:
    """One experiment, built once, run once.

    Every component defaults to what ``cfg`` names (strategy from
    ``cfg.topology.mode``, selector from ``cfg.orchestrator.selection``,
    pipeline from ``cfg.privacy``); pass instances to override — a custom
    ``Strategy`` object, a selector callable, a hand-composed
    :class:`PrivacyPipeline`, extra telemetry sinks, a span ``tracer``
    (``repro.obs.Tracer``; the no-op default makes instrumentation free).
    A ``Tracer``'s spans also land in a running ``jax.profiler`` trace, as
    host events named ``fed.<span>`` beside the device's operations.
    Validation and all subsystem wiring happen at construction, so bad
    configs fail fast.
    """

    def __init__(
        self,
        cfg: ExperimentConfig,
        task: FederatedTask,
        *,
        strategy: Union[None, str, Strategy] = None,
        selector: Union[None, str, Callable] = None,
        privacy: Optional[PrivacyPipeline] = None,
        telemetry: Iterable[TelemetrySink] = (),
        tracer=None,
    ):
        self.cfg = cfg
        self.task = task
        if strategy is None:
            strategy = cfg.topology.mode
        if isinstance(strategy, str):
            registry = _ensure_registry()
            if strategy not in registry:
                raise ValueError(
                    f"unknown strategy {strategy!r}; registered strategies: "
                    f"{', '.join(sorted(strategy_names()))}. Third-party "
                    "topologies join via repro.api.register_strategy(name, factory)."
                )
            strategy = registry[strategy]()
        self.strategy: Strategy = strategy
        self.strategy.validate(cfg)
        self.ctx = RuntimeContext(cfg, task, pipeline=privacy, selector=selector,
                                  tracer=tracer)
        self.strategy.setup(self.ctx)
        self.telemetry: list[TelemetrySink] = list(telemetry)
        self._ran = False

    # ------------------------------------------------------------------
    def _resolve_manager(self, checkpoint):
        """None | directory str | CheckpointManager -> manager (or None).

        With no explicit argument, ``cfg.checkpoint.directory`` decides; a
        bare directory (argument or config) gets a manager with the config
        block's cadence/retention knobs.
        """
        from repro.checkpoint import CheckpointManager, CheckpointPolicy

        ck = self.cfg.checkpoint
        if checkpoint is None and ck.directory:
            checkpoint = ck.directory
        if checkpoint is None or isinstance(checkpoint, CheckpointManager):
            return checkpoint
        policy = CheckpointPolicy(every_k_rounds=ck.every_k_rounds,
                                  keep_last_n=ck.keep_last_n)
        return CheckpointManager(str(checkpoint), policy)

    def _restore(self, resume_from: str) -> None:
        """Load the newest checkpoint under ``resume_from`` into the
        strategy + runtime, validating it belongs to this experiment."""
        from repro.checkpoint import load_checkpoint, resume_key

        if not hasattr(self.strategy, "load_state_dict"):
            raise ValueError(
                f"strategy {self.strategy.name!r} does not implement "
                "state_dict/load_state_dict and cannot resume"
            )
        state, meta = load_checkpoint(resume_from)
        if state.get("strategy") != self.strategy.name:
            raise ValueError(
                f"checkpoint was written by strategy {state.get('strategy')!r}, "
                f"this federation runs {self.strategy.name!r}"
            )
        stored_key = meta.get("resume_key")
        if stored_key is not None and stored_key != resume_key(self.cfg):
            raise ValueError(
                "checkpoint config mismatch: this run's config differs from "
                "the checkpointed one beyond training.rounds / the checkpoint "
                "block — resume requires an otherwise-identical experiment"
            )
        # cut append-mode event logs back to the checkpoint's cursor so the
        # re-run rounds append cleanly (no duplicate rows past the snapshot)
        offsets = (state.get("telemetry") or {}).get("jsonl_offsets") or {}
        for sink in self.telemetry:
            if getattr(sink, "append", False) and callable(getattr(sink, "truncate_to", None)):
                off = offsets.get(str(getattr(sink, "path", None)))
                if off is not None:
                    sink.truncate_to(int(off))
        self.strategy.load_state_dict(self.ctx, state["state"])

    def run(
        self,
        progress: Optional[Callable[[dict], None]] = None,
        *,
        checkpoint=None,
        resume_from: Optional[str] = None,
    ) -> dict:
        """Drive the strategy to completion; returns the history dict.

        ``progress`` is the legacy per-round callback — it is adapted onto
        the event stream via :class:`CallbackSink`.  A ``Federation`` is
        single-shot (its runtime state is consumed by the run), matching the
        legacy engines.

        ``checkpoint`` (a directory or a ``repro.checkpoint.CheckpointManager``;
        defaults to ``cfg.checkpoint.directory``) saves the full federation
        state per the checkpoint policy, atomically and off the round loop.
        ``resume_from`` (a step dir or a manager directory — newest loadable
        step wins) restores strategy + runtime state before running, so the
        remaining rounds replay bitwise what an uninterrupted run would have
        produced.  A resumed run's history dict covers the resumed rounds;
        the pre-crash rounds live in the durable event log / checkpoints.
        """
        if self._ran:
            raise RuntimeError("Federation.run() is single-shot; build a new one")
        self._ran = True
        manager = self._resolve_manager(checkpoint)
        if manager is not None:
            if not hasattr(self.strategy, "state_dict"):
                raise ValueError(
                    f"strategy {self.strategy.name!r} does not implement "
                    "state_dict/load_state_dict and cannot be checkpointed"
                )
            self.ctx.ckpt_manager = manager
            manager.telemetry_probe = self._jsonl_offsets
        if resume_from is not None:
            self._restore(resume_from)
        recorder = HistoryRecorder(self.strategy.history_keys)
        sinks: list[TelemetrySink] = [recorder, *self.telemetry]
        if progress is not None:
            sinks.append(CallbackSink(progress))

        def emit(event: RoundEvent) -> None:
            for sink in sinks:
                sink.emit(event)

        try:
            with self.ctx.tracer.span("run", strategy=self.strategy.name):
                summary = self.strategy.run(self.ctx, emit)
        finally:
            if manager is not None:
                manager.wait()  # drain background writes; surface failures
        history = recorder.history
        history.update(summary)
        return history

    def _jsonl_offsets(self) -> dict:
        """Byte cursors of every appendable event-log sink (folded into each
        checkpoint so a resume can truncate the logs to the snapshot)."""
        offsets = {}
        for sink in self.telemetry:
            path = getattr(sink, "path", None)
            tell = getattr(sink, "tell", None)
            if path is not None and callable(tell):
                offsets[str(path)] = int(tell())
        return {"jsonl_offsets": offsets}


def build(
    cfg: Union[ExperimentConfig, dict],
    task: FederatedTask,
    *,
    telemetry: Iterable[TelemetrySink] = (),
) -> Federation:
    """Registry construction: config (or its plain-dict form) -> Federation.

    Everything is resolved from the config's registry keys — this is the
    one-call path for JSON experiment grids::

        fed = api.build(json.load(f), task)
        history = fed.run()
    """
    if isinstance(cfg, dict):
        cfg = ExperimentConfig.from_dict(cfg)
    return Federation(cfg, task, telemetry=telemetry)
