"""``repro.api`` — the composable public experiment API.

Quick tour::

    from repro import api

    task = api.FederatedTask(loss_fn, eval_fn, params0, clients, test_data)
    cfg = api.ExperimentConfig(
        training=api.TrainingConfig(rounds=30, n_clients=16, clients_per_round=4),
        privacy=api.PrivacyConfig(secure_agg=True),
        topology=api.TopologyConfig(mode="async_hier", n_regions=2),
        orchestrator=api.OrchestratorConfig(selection="rl_green"),
    )
    history = api.Federation(cfg, task, telemetry=[api.ConsoleSink(every=5)]).run()

Components (all swappable at the ``Federation`` call site):

    strategy    ``STRATEGIES`` registry: "sync" | "async_hier" | "gossip",
                or any object implementing the ``Strategy`` protocol
    selector    ``repro.core.selection.POLICIES`` key, or a callable
    privacy     a ``PrivacyPipeline`` of row-native stages
                (``ClipStage → QuantizeStage → MaskStage → NoiseStage``)
    telemetry   sinks consuming the typed ``RoundEvent``/``FlushEvent``/
                ``MixEvent`` stream

Third-party aggregation topologies plug in without touching this package:
implement the three-method ``Strategy`` protocol (``validate``/``setup``/
``run``) against the shared ``RuntimeContext`` and call
``register_strategy("myname", MyStrategy)`` — from then on
``TopologyConfig(mode="myname")`` (and the JSON-grid ``build`` path)
constructs it like a built-in.  ``strategy_names()`` lists what is
registered; the built-in ``gossip`` strategy is itself registered this way.

``build(cfg_or_dict, task)`` is the registry constructor for JSON grids.
"""
from repro.api.config import (CarbonConfig, CheckpointConfig, EngineConfig,
                              ExperimentConfig, OrchestratorConfig,
                              PrivacyConfig, TopologyConfig, TrainingConfig)
from repro.api.federation import (STRATEGIES, Federation, Strategy, build,
                                  register_strategy, strategy_names)
from repro.api.pipeline import (AggregationContext, ClipStage,
                                FusedCompressStage, MaskStage, NoiseStage,
                                PrivacyPipeline, QuantizeStage, ScaleStage,
                                StageRecord, TopKStage, build_pipeline,
                                cohort_wire_bytes, fuse_pipeline,
                                upload_bytes_per_client)
from repro.api.runtime import FederatedTask, RuntimeContext
from repro.api.telemetry import (CallbackSink, ConsoleSink, FlushEvent,
                                 HistoryRecorder, MixEvent, RoundEvent,
                                 TelemetrySink)

# strategy classes are re-exported for subclass-free composition, but the
# registry itself stays lazy inside federation.py (import-cycle hygiene)
from repro.api.async_hier import AsyncHierStrategy  # noqa: E402  isort: skip
from repro.api.gossip import GossipStrategy  # noqa: E402  isort: skip
from repro.api.sync import SyncStrategy  # noqa: E402  isort: skip

__all__ = [
    "AggregationContext", "AsyncHierStrategy", "build", "build_pipeline",
    "CallbackSink", "CarbonConfig", "CheckpointConfig", "ClipStage",
    "cohort_wire_bytes", "ConsoleSink", "EngineConfig", "ExperimentConfig",
    "Federation",
    "FederatedTask", "FlushEvent", "fuse_pipeline", "FusedCompressStage",
    "GossipStrategy", "HistoryRecorder", "MaskStage", "MixEvent",
    "NoiseStage", "OrchestratorConfig", "PrivacyConfig", "PrivacyPipeline",
    "QuantizeStage", "register_strategy", "RoundEvent", "RuntimeContext",
    "ScaleStage", "StageRecord", "STRATEGIES", "Strategy", "strategy_names",
    "SyncStrategy", "TelemetrySink", "TopKStage", "TopologyConfig",
    "TrainingConfig", "upload_bytes_per_client",
]
