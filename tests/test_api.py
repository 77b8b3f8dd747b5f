"""repro.api: the JSON-grid ``build`` path against ``Federation``, config
round-trip, privacy-pipeline stages, per-region accountant, telemetry, and
the stale-in-state MARL encoding."""
import io
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import api
from repro.core import orchestrator as orch
from repro.data.partition import dirichlet_partition
from repro.data.pipeline import build_clients
from repro.data.synthetic import MNIST_LIKE, make_image_dataset
from repro.fl.paramspace import ParamSpace
from repro.models.resnet import ResNetConfig, init_resnet, resnet_loss
from repro.privacy.accountant import SubsampledAccountant, eps_from_rdp
from repro.privacy.dp import DPConfig


def _setup(n_clients=6, n_train=400, n_test=128):
    data = make_image_dataset(MNIST_LIKE, seed=1, n_train=n_train, n_test=n_test)
    parts = dirichlet_partition(data["train"]["label"], n_clients, 0.5, seed=1)
    clients = build_clients(data["train"], parts)
    rcfg = ResNetConfig(name="t", widths=(8, 16), depths=(1, 1), in_channels=1, num_classes=10)
    params = init_resnet(jax.random.PRNGKey(0), rcfg)
    loss_fn = lambda p, b: resnet_loss(p, rcfg, b)
    eval_fn = lambda p, b: resnet_loss(p, rcfg, b)[1]
    task = api.FederatedTask(loss_fn, eval_fn, params, clients, data["test"])
    return data, clients, params, loss_fn, eval_fn, task


_BASE = dict(n_clients=6, clients_per_round=3, rounds=2, local_steps=2,
             batch_size=16, eval_every=1, seed=3)


# ---------------------------------------------------------------------------
# The JSON-grid path: ``build(cfg.to_dict(), task)`` drives the same run as
# ``Federation(cfg, task)`` and gives the same history schema.
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("cfg", [
    api.ExperimentConfig(training=api.TrainingConfig(**_BASE)),
    api.ExperimentConfig(training=api.TrainingConfig(**_BASE),
                         privacy=api.PrivacyConfig(secure_agg=True, sa_bits=24)),
    api.ExperimentConfig(
        training=api.TrainingConfig(**_BASE),
        privacy=api.PrivacyConfig(dp=DPConfig(clip=2.0, sigma=1.1, sample_rate=0.5, rounds=2)),
    ),
    api.ExperimentConfig(
        training=api.TrainingConfig(**dict(_BASE, rounds=4)),
        topology=api.TopologyConfig(mode="async_hier", latency_spread=1.0, buffer_k=2,
                                    concurrency=6, n_regions=2, edge_sync_every=2),
    ),
], ids=["plain", "secure_agg", "dp", "async_hier"])
def test_build_from_dict_matches_federation(cfg):
    _, _, _, _, _, task = _setup()
    fed = api.build(cfg.to_dict(), task)
    h = fed.run()
    h_direct = api.Federation(cfg, task).run()
    assert sorted(h) == sorted(h_direct)
    for key in ("acc", "loss", "cum_co2_g", "eps_spent", "staleness"):
        if key in h_direct:
            np.testing.assert_allclose(h[key], h_direct[key])
    for key in ("selected", "region", "buffer_flushes"):
        if key in h_direct:
            assert h[key] == h_direct[key]
    if cfg.topology.mode == "async_hier":
        assert fed.strategy.buffer_k == 2 and fed.strategy.global_version >= 2
        assert len(fed.strategy.regions) == 2 and fed.ctx.fleet.n == 6


def test_async_strategy_rejects_sync_only_algorithms_via_api():
    _, _, _, _, _, task = _setup()
    cfg = api.ExperimentConfig(
        training=api.TrainingConfig(algorithm="scaffold", **{k: v for k, v in _BASE.items()}),
        topology=api.TopologyConfig(mode="async_hier"),
    )
    with pytest.raises(ValueError, match="scaffold"):
        api.Federation(cfg, task)


def test_federation_is_single_shot_and_rejects_unknown_strategy():
    _, _, _, _, _, task = _setup()
    cfg = api.ExperimentConfig(training=api.TrainingConfig(**dict(_BASE, rounds=1)))
    with pytest.raises(ValueError, match="unknown strategy"):
        api.Federation(cfg, task, strategy="nope")
    fed = api.Federation(cfg, task)
    fed.run()
    with pytest.raises(RuntimeError, match="single-shot"):
        fed.run()


def test_register_strategy_extends_the_registry():
    class NullStrategy:
        name = "null"
        history_keys = ("round",)

        def validate(self, cfg):
            pass

        def setup(self, ctx):
            pass

        def run(self, ctx, emit):
            return {"final_acc": 0.0}

    assert {"sync", "async_hier"} <= set(api.strategy_names())
    api.register_strategy("null", NullStrategy)
    try:
        assert "null" in api.strategy_names()
        _, _, _, _, _, task = _setup()
        cfg = api.ExperimentConfig(training=api.TrainingConfig(**dict(_BASE, rounds=1)))
        h = api.Federation(cfg, task, strategy="null").run()
        assert h == {"round": [], "final_acc": 0.0}
    finally:
        api.STRATEGIES.pop("null", None)


def test_privacy_config_rejects_unknown_accounting():
    with pytest.raises(ValueError, match="accounting"):
        api.PrivacyConfig(accounting="per-region")


# ---------------------------------------------------------------------------
# ExperimentConfig round-trip
# ---------------------------------------------------------------------------


def test_experiment_config_round_trips_through_json():
    cfg = api.ExperimentConfig(
        training=api.TrainingConfig(algorithm="fedprox", rounds=7, seed=11),
        privacy=api.PrivacyConfig(
            dp=DPConfig(clip=2.0, sigma=1.3), accounting="per_region"
        ),
        topology=api.TopologyConfig(mode="async_hier", n_regions=3, buffer_k=2),
        carbon=api.CarbonConfig(round_hours=0.25),
        orchestrator=api.OrchestratorConfig(selection="rl_green", stale_in_state=True),
    )
    restored = api.ExperimentConfig.from_dict(json.loads(json.dumps(cfg.to_dict())))
    assert restored == cfg
    assert isinstance(restored.privacy.dp, DPConfig)
    assert api.ExperimentConfig.from_dict({}) == api.ExperimentConfig()


# ---------------------------------------------------------------------------
# Privacy pipeline: stage composition, records, reductions
# ---------------------------------------------------------------------------


def _row_ctx(pspace, k, weights, seed=0):
    km, kn = jax.random.split(jax.random.PRNGKey(seed))
    weighted_sum = lambda rows, w: jnp.einsum("kp,k->p", rows, jnp.asarray(w, jnp.float32))
    return api.AggregationContext(pspace, k, weights, km, kn, weighted_sum)


def _pspace_and_rows(k=4, seed=0):
    tree = {"a": jnp.zeros((13,)), "b": jnp.zeros((3, 5))}
    pspace = ParamSpace.build(tree)
    rng = np.random.default_rng(seed)
    rows = jnp.asarray(rng.normal(0, 0.5, (k, pspace.dim)).astype(np.float32))
    return pspace, rows


def test_build_pipeline_matches_legacy_compositions():
    assert api.build_pipeline(api.PrivacyConfig()).describe() == []
    assert api.build_pipeline(api.PrivacyConfig(secure_agg=True)).describe() == \
        ["scale", "quantize", "mask"]
    dp = DPConfig(clip=1.0, sigma=1.0)
    assert api.build_pipeline(api.PrivacyConfig(dp=dp)).describe() == \
        ["clip", "quantize", "mask", "noise"]


def test_plain_pipeline_is_weighted_mean():
    pspace, rows = _pspace_and_rows()
    ctx = _row_ctx(pspace, 4, [1.0, 2.0, 3.0, 4.0])
    out = api.PrivacyPipeline().aggregate(rows, ctx)
    w = np.asarray([1, 2, 3, 4], np.float64) / 10.0
    np.testing.assert_allclose(np.asarray(out),
                               np.einsum("kp,k->p", np.asarray(rows), w), rtol=1e-6)
    assert ctx.records == []


def test_masked_pipeline_recovers_mean_and_records_stages():
    pspace, rows = _pspace_and_rows()
    pipe = api.PrivacyPipeline(
        stages=(api.QuantizeStage(clip=10.0, bits=24), api.MaskStage()),
        weighting="uniform",
    )
    ctx = _row_ctx(pspace, 4, [1.0, 1.0, 1.0, 1.0])
    out = pipe.aggregate(rows, ctx)
    np.testing.assert_allclose(np.asarray(out), np.asarray(jnp.mean(rows, 0)), atol=1e-4)
    assert [r.stage for r in ctx.records] == ["quantize", "mask"]
    assert ctx.records[0].info == {"clip": 10.0, "bits": 24}


def test_custom_clip_noise_pipeline_without_masking():
    """Central DP without secure-agg: a composition the legacy flags could
    not express — clip rows, plain uniform sum, Gaussian noise, mean."""
    pspace, rows = _pspace_and_rows()
    dp = DPConfig(clip=0.5, sigma=0.0)  # sigma 0: noise stage records, adds nothing
    pipe = api.PrivacyPipeline(stages=(api.ClipStage(dp.clip), api.NoiseStage(dp)),
                               weighting="uniform")
    ctx = _row_ctx(pspace, 4, [1.0, 1.0, 1.0, 1.0])
    out = pipe.aggregate(rows, ctx)
    clipped = np.stack([r * min(1.0, 0.5 / np.linalg.norm(r)) for r in np.asarray(rows)])
    np.testing.assert_allclose(np.asarray(out), clipped.mean(0), rtol=1e-5)
    assert [r.stage for r in ctx.records] == ["clip", "noise"]
    assert ctx.records[1].info["sigma"] == 0.0


def test_mask_stage_requires_quantize():
    pspace, rows = _pspace_and_rows()
    pipe = api.PrivacyPipeline(stages=(api.MaskStage(),), weighting="uniform")
    with pytest.raises(ValueError, match="QuantizeStage"):
        pipe.aggregate(rows, _row_ctx(pspace, 4, [1.0] * 4))
    with pytest.raises(ValueError, match="weighting"):
        api.PrivacyPipeline(weighting="nope")
    # declared order is execution order: sum-scope before row-scope rejected
    with pytest.raises(ValueError, match="precede"):
        api.PrivacyPipeline(
            stages=(api.NoiseStage(DPConfig(clip=1.0)), api.ClipStage(1.0)),
            weighting="uniform",
        )


# ---------------------------------------------------------------------------
# Fused compression, EF top-k, and wire-byte pricing
# ---------------------------------------------------------------------------


def test_build_pipeline_fuses_dp_composition():
    dp = DPConfig(clip=1.0, sigma=1.0)
    pipe = api.build_pipeline(api.PrivacyConfig(dp=dp))
    assert [s.name for s in pipe.stages] == ["fused_compress", "noise"]
    # fusion is invisible outside: describe() expands to the staged names
    assert pipe.describe() == ["clip", "quantize", "mask", "noise"]
    staged = api.build_pipeline(api.PrivacyConfig(dp=dp, fuse=False))
    assert [s.name for s in staged.stages] == ["clip", "quantize", "mask", "noise"]
    # scale-based secure-agg doesn't match clip->quantize->mask: stays staged
    sa = api.build_pipeline(api.PrivacyConfig(secure_agg=True))
    assert [s.name for s in sa.stages] == ["scale", "quantize", "mask"]


def test_build_pipeline_inserts_ef_topk_ahead_of_compression():
    dp = DPConfig(clip=1.0, sigma=1.0)
    pipe = api.build_pipeline(api.PrivacyConfig(dp=dp, topk_density=0.1))
    assert [s.name for s in pipe.stages] == ["topk", "fused_compress", "noise"]
    assert pipe.describe() == ["topk", "clip", "quantize", "mask", "noise"]
    # plain top-k without DP/masking keeps data weighting (Eq. 6)
    plain = api.build_pipeline(api.PrivacyConfig(topk_density=0.25))
    assert plain.describe() == ["topk"] and plain.weighting == "data"
    with pytest.raises(ValueError, match="topk_density"):
        api.PrivacyConfig(topk_density=1.5)
    with pytest.raises(ValueError, match="density"):
        api.TopKStage(0.0)


def test_fuse_pipeline_leaves_non_matching_compositions_alone():
    clip, q, m = api.ClipStage(1.0), api.QuantizeStage(clip=1.0, bits=16), api.MaskStage()
    fused = api.fuse_pipeline(
        api.PrivacyPipeline(stages=(clip, q, m), weighting="uniform"))
    assert [s.name for s in fused.stages] == ["fused_compress"]
    # clip values disagree -> fusing would change the ring encoding: refuse
    q2 = api.QuantizeStage(clip=2.0, bits=16)
    kept = api.fuse_pipeline(
        api.PrivacyPipeline(stages=(clip, q2, m), weighting="uniform"))
    assert [s.name for s in kept.stages] == ["clip", "quantize", "mask"]
    # no clip ahead of quantize -> no match (and the input object is reused)
    p = api.PrivacyPipeline(stages=(q, m), weighting="uniform")
    assert api.fuse_pipeline(p) is p


def test_wire_byte_pricing_from_stage_records():
    dim = 1000
    # plain run: float32 row up, full model down == legacy 2 transfers/client
    assert api.upload_bytes_per_client([], dim) == dim * 4.0
    assert api.cohort_wire_bytes([], 3, dim * 4.0, dim) == 2 * 3 * dim * 4.0
    # ring quantization prices each value at its bit width, not float32
    quant = [api.StageRecord("quantize", {"clip": 1.0, "bits": 18})]
    assert api.upload_bytes_per_client(quant, dim) == dim * 18 / 8.0
    # top-k shrinks the payload to k_kept (index, value) pairs
    recs = [api.StageRecord("topk", {"density": 0.05, "k_kept": 50, "index_bits": 32}),
            api.StageRecord("clip", {"clip": 1.0}),
            api.StageRecord("quantize", {"clip": 1.0, "bits": 18}),
            api.StageRecord("mask", {"ring_bits": 32})]
    assert api.upload_bytes_per_client(recs, dim) == 50 * 18 / 8.0 + 50 * 4.0


def test_metrics_sink_prefers_record_priced_wire_bytes():
    from repro.obs.metrics import MetricsSink

    ev = dict(round=0, acc=0.5, loss=1.0, co2_g=1.0, cum_co2_g=1.0,
              duration_s=1.0, reward=0.0, eps_spent=0.0, selected=(0, 1, 2))
    priced = MetricsSink(model_bytes=1000.0)
    priced.emit(api.RoundEvent(**ev, wire_bytes=123.5))
    assert priced.snapshot()["bytes_moved"] == 123.5
    # no priced payload on the event -> legacy 2-transfers/client estimate
    legacy = MetricsSink(model_bytes=1000.0)
    legacy.emit(api.RoundEvent(**ev))
    assert legacy.snapshot()["bytes_moved"] == 2 * 3 * 1000.0


def test_aggregation_context_precomputes_norm_weights():
    pspace, _ = _pspace_and_rows()
    ctx = _row_ctx(pspace, 4, [1.0, 2.0, 3.0, 4.0])
    np.testing.assert_allclose(np.asarray(ctx.norm_weights),
                               [0.1, 0.2, 0.3, 0.4], rtol=1e-7)
    assert ctx.norm_weights is ctx.norm_weights  # cached, not rebuilt per read


def test_gossip_rejects_sparsified_pipelines():
    _, _, _, _, _, task = _setup()
    cfg = api.ExperimentConfig(
        training=api.TrainingConfig(**_BASE),
        privacy=api.PrivacyConfig(topk_density=0.1),
        topology=api.TopologyConfig(mode="gossip"),
    )
    with pytest.raises(ValueError, match="sparsify"):
        api.Federation(cfg, task)


def test_privacy_config_round_trips_compression_knobs():
    cfg = api.ExperimentConfig(
        training=api.TrainingConfig(**_BASE),
        privacy=api.PrivacyConfig(dp=DPConfig(clip=1.0, sigma=1.0),
                                  topk_density=0.05, fuse=False),
    )
    back = api.ExperimentConfig.from_dict(json.loads(json.dumps(cfg.to_dict())))
    assert back.privacy.topk_density == 0.05 and back.privacy.fuse is False
    # older configs without the new knobs load with the defaults
    d = cfg.to_dict()
    d["privacy"].pop("topk_density"), d["privacy"].pop("fuse")
    old = api.ExperimentConfig.from_dict(d)
    assert old.privacy.topk_density == 0.0 and old.privacy.fuse is True


# ---------------------------------------------------------------------------
# Per-region subsampled accountant
# ---------------------------------------------------------------------------


def test_subsampled_accountant_reduces_to_schedule_when_homogeneous():
    acc = SubsampledAccountant(1e-5)
    assert acc.epsilon() == 0.0
    for _ in range(5):
        acc.record(q=0.2, sigma=1.5)
    np.testing.assert_allclose(acc.epsilon(), eps_from_rdp(0.2, 1.5, 5, 1e-5), rtol=1e-12)
    assert acc.steps == 5


def test_subsampled_accountant_heterogeneous_and_edge_cases():
    acc = SubsampledAccountant(1e-5)
    acc.record(q=0.5, sigma=1.0)
    e1 = acc.epsilon()
    acc.record(q=0.1, sigma=2.0)
    assert acc.epsilon() > e1  # composition only ever spends more
    with pytest.raises(ValueError, match="sampling rate"):
        acc.record(q=1.5, sigma=1.0)
    acc.record(q=0.2, sigma=0.0)  # disabled noise: guarantee collapses
    assert acc.epsilon() == float("inf")


def test_async_per_region_accounting_reports_regional_epsilons():
    _, _, _, _, _, task = _setup()
    dp = DPConfig(clip=2.0, sigma=1.2, sample_rate=0.5, rounds=4)
    cfg = api.ExperimentConfig(
        training=api.TrainingConfig(**dict(_BASE, rounds=4)),
        privacy=api.PrivacyConfig(dp=dp, accounting="per_region"),
        topology=api.TopologyConfig(mode="async_hier", n_regions=2, buffer_k=2,
                                    concurrency=6),
    )
    h = api.Federation(cfg, task).run()
    assert set(h["eps_by_region"]) == {0, 1}
    assert all(e > 0 for e in h["eps_by_region"].values())
    # per-flush eps_spent is the worst region and never decreases
    assert h["eps_spent"][-1] == pytest.approx(max(h["eps_by_region"].values()))
    assert all(b >= a for a, b in zip(h["eps_spent"], h["eps_spent"][1:]))


# ---------------------------------------------------------------------------
# Straggler EMA as a fourth MARL state factor
# ---------------------------------------------------------------------------


def test_stale_in_state_widens_q_table_and_encoding():
    st = orch.init_state(4)
    assert st.q.shape == (orch.N_STATES, 4)
    st_x = orch.init_state(4, stale_in_state=True)
    assert st_x.q.shape == (orch.N_STATES * orch.N_STALE, 4)
    # bucket thresholds
    assert int(orch.stale_bucket(0.0)) == 0
    assert int(orch.stale_bucket(1.0)) == 1
    assert int(orch.stale_bucket(5.0)) == 2
    # default encoding is untouched (sync anchors stay bitwise)
    idx = orch.state_index(st, jnp.float32(100.0), jnp.bool_(True), jnp.float32(0.1))
    assert idx == orch.encode_state(jnp.float32(100.0), jnp.bool_(True), jnp.float32(0.1))
    # extended encoding appends the stale bucket as the fastest digit
    st_x = orch.observe_staleness(
        st_x, np.ones(4, bool), np.full(4, 8.0, np.float32))
    idx_x = orch.state_index(st_x, jnp.float32(100.0), jnp.bool_(True), jnp.float32(0.1))
    assert int(idx_x) == int(idx) * orch.N_STALE + int(
        orch.stale_bucket(jnp.mean(st_x.stale_ema)))
    # update writes inside the widened table
    st2, _ = orch.update(st_x, np.ones(4, bool), 0.5, 0.0, 100.0, 100.0)
    assert 0 <= int(st2.state_idx) < orch.N_STATES * orch.N_STALE


def test_stale_in_state_flag_runs_through_federation():
    _, _, _, _, _, task = _setup()
    cfg = api.ExperimentConfig(
        training=api.TrainingConfig(**dict(_BASE, rounds=2)),
        orchestrator=api.OrchestratorConfig(selection="rl_green", stale_in_state=True),
    )
    fed = api.Federation(cfg, task)
    assert fed.ctx.orch_state.q.shape[0] == orch.N_STATES * orch.N_STALE
    h = fed.run()
    assert len(h["reward"]) == 2 and np.isfinite(h["reward"]).all()


# ---------------------------------------------------------------------------
# Telemetry
# ---------------------------------------------------------------------------


def test_history_recorder_and_sinks():
    ev = api.RoundEvent(round=0, acc=0.5, loss=1.0, co2_g=10.0, cum_co2_g=10.0,
                        duration_s=3.0, reward=0.1, eps_spent=0.0, selected=(1, 2))
    fl = api.FlushEvent(round=1, acc=0.6, loss=0.9, co2_g=11.0, cum_co2_g=21.0,
                        duration_s=3.0, reward=0.2, eps_spent=0.0, selected=(3,),
                        staleness=1.5, region=1, sim_time_s=42.0)
    rec = api.HistoryRecorder(("round", "acc", "selected"))
    rec.emit(ev)
    rec.emit(fl)
    assert rec.history == {"round": [0, 1], "acc": [0.5, 0.6],
                           "selected": [[1, 2], [3]]}
    seen = []
    api.CallbackSink(seen.append).emit(ev)
    assert seen == [{"round": 0, "acc": 0.5, "co2_g": 10.0, "loss": 1.0}]
    buf = io.StringIO()
    sink = api.ConsoleSink(every=2, stream=buf)
    sink.emit(ev)
    sink.emit(fl)  # skipped by `every`
    out = buf.getvalue()
    assert "round   0" in out and "flush" not in out


def test_progress_callback_still_works_through_federation():
    _, _, _, _, _, task = _setup()
    cfg = api.ExperimentConfig(training=api.TrainingConfig(**dict(_BASE, rounds=1)))
    rows = []
    api.Federation(cfg, task).run(progress=rows.append)
    assert len(rows) == 1 and set(rows[0]) == {"round", "acc", "co2_g", "loss"}
