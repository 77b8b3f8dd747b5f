"""repro.obs: span tracer properties, Chrome-trace schema, JSONL sink
round-trips across all three strategies, metrics folding, run manifests,
the report CLI, and the NullTracer no-op (bitwise-history) guarantee —
plus the engine-scale layer: streaming histograms, simulated-time
timelines, health alerts, sampled tracing, and the bounded-memory
10⁵-update fully observed replay."""
import dataclasses
import json
import math
import os
import pathlib
import tracemalloc

import jax
import numpy as np
import pytest

from repro import api, obs
from repro.api.telemetry import GOSSIP_HISTORY_KEYS
from repro.data.partition import dirichlet_partition
from repro.data.pipeline import build_clients
from repro.data.synthetic import MNIST_LIKE, make_image_dataset
from repro.engine import (DISCIPLINES, ReplayConfig, ReplayEngine,
                          synthetic_trace)
from repro.models.resnet import ResNetConfig, init_resnet, resnet_loss
from repro.obs import report as report_mod
from repro.obs import watch as watch_mod


# ---------------------------------------------------------------------------
# Tracer unit tests (deterministic injected clock)
# ---------------------------------------------------------------------------


def _ticking_clock(step=1.0):
    t = [0.0]

    def clock():
        t[0] += step
        return t[0]

    return clock


def test_span_nesting_and_ordering(tmp_path):
    path = str(tmp_path / "trace.jsonl")
    tr = obs.Tracer(jsonl_path=path, clock=_ticking_clock())
    with tr.span("outer", round=0):
        with tr.span("inner_a"):
            pass
        with tr.span("inner_b"):
            pass
    with tr.span("second"):
        pass
    tr.close()

    # spans record at exit: children before parents, siblings in order
    assert [s.name for s in tr.spans] == ["inner_a", "inner_b", "outer", "second"]
    assert [s.depth for s in tr.spans] == [1, 1, 0, 0]
    by = {s.name: s for s in tr.spans}
    # containment: children inside the parent interval
    for child in ("inner_a", "inner_b"):
        assert by[child].start_s >= by["outer"].start_s
        assert by[child].start_s + by[child].dur_s <= by["outer"].start_s + by["outer"].dur_s
    # sibling ordering on the monotonic clock
    assert by["inner_a"].start_s + by["inner_a"].dur_s <= by["inner_b"].start_s
    assert by["outer"].start_s + by["outer"].dur_s <= by["second"].start_s
    assert by["outer"].attrs == {"round": 0}
    assert all(s.dur_s >= 0 for s in tr.spans)

    # streaming JSONL mirrors the in-memory records
    rows = obs.read_spans(path)
    assert [r["name"] for r in rows] == [s.name for s in tr.spans]
    assert [r["depth"] for r in rows] == [s.depth for s in tr.spans]
    np.testing.assert_allclose([r["ts_us"] for r in rows],
                               [s.start_s * 1e6 for s in tr.spans])


def test_mid_span_attrs_and_depth_recovery():
    tr = obs.Tracer(clock=_ticking_clock())
    with tr.span("round", round=3) as sp:
        sp.set(co2_g=12.5, bytes=1000)
    with tr.span("next"):
        pass
    assert tr.spans[0].attrs == {"round": 3, "co2_g": 12.5, "bytes": 1000}
    assert tr.spans[1].depth == 0  # depth counter recovered after exit


def _validate_chrome(path):
    with open(path) as f:
        trace = json.load(f)
    assert isinstance(trace["traceEvents"], list) and trace["traceEvents"]
    for ev in trace["traceEvents"]:
        assert ev["ph"] == "X"
        assert isinstance(ev["name"], str) and ev["name"]
        assert isinstance(ev["ts"], (int, float)) and ev["ts"] >= 0
        assert isinstance(ev["dur"], (int, float)) and ev["dur"] >= 0
        assert isinstance(ev["pid"], int)
        assert isinstance(ev["tid"], int)
        assert isinstance(ev.get("args", {}), dict)
    return trace


def test_chrome_trace_schema(tmp_path):
    tr = obs.Tracer(clock=_ticking_clock())
    with tr.span("a", tag="x"):
        with tr.span("b"):
            pass
    out = str(tmp_path / "trace.json")
    tr.export_chrome(out)
    trace = _validate_chrome(out)
    assert {e["name"] for e in trace["traceEvents"]} == {"a", "b"}


def _profiled_span_names(log_dir) -> set:
    """Names of the ``fed.*`` events on the host planes of a profiler trace."""
    (path,) = sorted(pathlib.Path(log_dir).rglob("*.xplane.pb"))
    data = jax.profiler.ProfileData.from_file(str(path))
    return {e.name for plane in data.planes if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events if e.name.startswith("fed.")}


def _profile_options():
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    return opts


@pytest.mark.parametrize("tracer, expected", [
    (obs.Tracer(), {"fed.outer", "fed.inner"}),
    (obs.NULL_TRACER, set()),  # opens no annotation
])
def test_spans_are_profiler_annotations(tmp_path, tracer, expected):
    with jax.profiler.trace(str(tmp_path), profiler_options=_profile_options()):
        with tracer.span("outer"):
            with tracer.span("inner"):
                jax.numpy.ones(3).block_until_ready()
    assert _profiled_span_names(tmp_path) == expected


def test_null_tracer_is_free_and_shared():
    cm1 = obs.NULL_TRACER.span("anything", round=1)
    cm2 = obs.NULL_TRACER.span("else")
    assert cm1 is cm2  # shared singleton context manager: no allocation
    with cm1 as sp:
        sp.set(co2_g=1.0)  # accepted and dropped
    assert obs.NULL_TRACER.spans == []
    assert obs.NULL_TRACER.chrome_trace()["traceEvents"] == []
    assert not obs.NULL_TRACER.enabled


def test_truncated_streams_tolerated(tmp_path):
    sp = tmp_path / "trace.jsonl"
    tr = obs.Tracer(jsonl_path=str(sp), clock=_ticking_clock())
    with tr.span("a"):
        pass
    with tr.span("b"):
        pass
    tr.close()
    with open(sp, "a") as f:
        f.write('{"name": "partial", "ts_us": 1.0, "dur')  # crash mid-write
    assert [r["name"] for r in obs.read_spans(str(sp))] == ["a", "b"]

    ep = tmp_path / "events.jsonl"
    sink = obs.JsonlSink(str(ep))
    sink.emit(_round_event())
    sink.close()
    with open(ep, "a") as f:
        f.write('{"event": "RoundEvent", "round": 9')
    assert obs.read_events(str(ep)) == [_round_event()]
    # corruption anywhere but the final line is a real error
    with open(ep, "a") as f:
        f.write('\n{"event": "RoundEvent"}\n')
    with pytest.raises(json.JSONDecodeError):
        obs.read_events(str(ep))


# ---------------------------------------------------------------------------
# Event sinks + metrics (hand-built events)
# ---------------------------------------------------------------------------


def _round_event(**kw):
    base = dict(round=0, acc=0.5, loss=1.25, co2_g=10.0, cum_co2_g=10.0,
                duration_s=3.0, reward=0.1, eps_spent=0.0, selected=(1, 2))
    base.update(kw)
    return api.RoundEvent(**base)


def _flush_event(**kw):
    base = dict(round=1, acc=0.6, loss=0.9, co2_g=11.0, cum_co2_g=21.0,
                duration_s=3.5, reward=0.2, eps_spent=0.7, selected=(3,),
                staleness=1.5, region=1, sim_time_s=42.0)
    base.update(kw)
    return api.FlushEvent(**base)


def _mix_event(**kw):
    base = dict(round=2, acc=0.7, loss=0.8, co2_g=9.0, cum_co2_g=30.0,
                duration_s=2.5, reward=0.0, eps_spent=0.0, selected=(0, 4),
                consensus=0.01, spectral_gap=0.6, mix_steps=2, mix_bytes=4096.0)
    base.update(kw)
    return api.MixEvent(**base)


def test_jsonl_sink_round_trip_unit(tmp_path):
    path = str(tmp_path / "events.jsonl")
    events = [_round_event(), _flush_event(), _mix_event()]
    with obs.JsonlSink(path) as sink:
        for e in events:
            sink.emit(e)
    back = obs.read_events(path)
    assert back == events  # typed, field-exact (frozen-dataclass equality)
    assert [type(e).__name__ for e in back] == ["RoundEvent", "FlushEvent", "MixEvent"]


def test_jsonl_sink_unknown_event_tag(tmp_path):
    path = str(tmp_path / "events.jsonl")
    with open(path, "w") as f:
        f.write('{"event": "MysteryEvent", "selected": []}\n')
    with pytest.raises(ValueError, match="MysteryEvent"):
        obs.read_events(path)


def test_metrics_registry_histogram_percentiles():
    reg = obs.MetricsRegistry()
    h = reg.histogram("lat")
    for v in range(1, 101):
        h.observe(float(v))
    assert h.percentile(50) == pytest.approx(50.5)
    assert h.percentile(99) == pytest.approx(99.01)
    snap = reg.snapshot()["lat"]
    assert snap["count"] == 100 and snap["min"] == 1.0 and snap["max"] == 100.0
    reg.counter("n").inc(2)
    reg.gauge("g").set(7.0)
    assert reg.snapshot()["n"] == 2.0 and reg.snapshot()["g"] == 7.0
    with pytest.raises(TypeError):
        reg.gauge("n")  # name already registered as a Counter


def test_metrics_sink_folds_heterogeneous_stream(tmp_path):
    sink = obs.MetricsSink(model_bytes=100.0)
    for e in (_round_event(), _flush_event(), _mix_event()):
        sink.emit(e)
    snap = sink.snapshot()
    assert snap["events"] == 3.0
    assert snap["rounds"] == 1.0 and snap["flushes"] == 1.0 and snap["mixes"] == 1.0
    assert snap["co2_g_total"] == pytest.approx(30.0)
    assert snap["co2_g_total[region=1]"] == pytest.approx(11.0)
    # bytes: round 2 clients *2*100 + flush 1 client *2*100 + mix 4096
    assert snap["bytes_moved"] == pytest.approx(400.0 + 200.0 + 4096.0)
    assert snap["eps_spent"] == pytest.approx(0.0)  # last event's value
    assert snap["consensus"]["count"] == 1
    assert snap["staleness"]["p50"] == pytest.approx(1.5)
    out = sink.to_json(str(tmp_path / "metrics.json"))
    assert json.load(open(out)) == json.loads(json.dumps(snap))


def test_history_recorder_tolerates_heterogeneous_streams():
    rec = api.HistoryRecorder(GOSSIP_HISTORY_KEYS)
    rec.emit(_round_event())      # no consensus/spectral_gap/mix_* fields
    rec.emit(_mix_event())
    assert rec.history["consensus"] == [None, 0.01]
    assert rec.history["acc"] == [0.5, 0.7]


def test_console_sink_tags_by_event_type():
    import io

    buf = io.StringIO()
    sink = api.ConsoleSink(stream=buf)
    sink.emit(_round_event())
    sink.emit(_flush_event())
    sink.emit(_mix_event())
    lines = buf.getvalue().splitlines()
    assert lines[0].startswith("round") and "staleness" not in lines[0]
    assert lines[1].startswith("flush") and "staleness=1.50" in lines[1]
    assert lines[2].startswith("mix") and "consensus=0.0100" in lines[2]


def test_manifest_round_trip_and_config_hash(tmp_path):
    cfg = api.ExperimentConfig()
    path = str(tmp_path / "run.json")
    man = obs.write_manifest(path, cfg=cfg, strategy="sync",
                             extra={"summary": {"final_acc": 0.9}})
    back = obs.read_manifest(path)
    assert back["schema"] == obs.MANIFEST_SCHEMA
    assert back["strategy"] == "sync"
    assert back["config_hash"] == obs.config_hash(cfg) == man["config_hash"]
    assert back["config"]["training"]["rounds"] == cfg.training.rounds
    assert back["jax_version"] == jax.__version__
    assert back["summary"] == {"final_acc": 0.9}
    # the hash keys the experiment definition: any field change moves it
    cfg2 = api.ExperimentConfig(training=api.TrainingConfig(rounds=7))
    assert obs.config_hash(cfg2) != obs.config_hash(cfg)


# ---------------------------------------------------------------------------
# Integration: traced runs across all three strategies
# ---------------------------------------------------------------------------

_BASE = dict(n_clients=6, clients_per_round=3, rounds=2, local_steps=2,
             batch_size=16, eval_every=1, seed=3)

_EXPECTED_SPANS = {
    "sync": {"run", "round", "select", "train", "cohort_inputs", "aggregate",
             "server_update", "accounting", "policy_update", "eval"},
    "async_hier": {"run", "select", "train", "cohort_inputs", "flush",
                   "aggregate", "edge_sync", "eval"},
    "gossip": {"run", "round", "select", "train", "cohort_inputs", "mix",
               "accounting", "policy_update", "eval"},
}

# (strategy, span, the span it sits directly inside), once per round
_NESTED_ONCE_PER_ROUND = [
    ("sync", "cohort_inputs", "train"),
    ("sync", "server_update", "aggregate"),
    ("sync", "accounting", "round"),
    ("sync", "policy_update", "round"),
    ("gossip", "cohort_inputs", "train"),
    ("gossip", "accounting", "round"),
    ("gossip", "policy_update", "round"),
]

_EXPECTED_EVENT = {"sync": "RoundEvent", "async_hier": "FlushEvent",
                   "gossip": "MixEvent"}


def _task():
    data = make_image_dataset(MNIST_LIKE, seed=1, n_train=256, n_test=96)
    parts = dirichlet_partition(data["train"]["label"], _BASE["n_clients"], 0.5, seed=1)
    clients = build_clients(data["train"], parts)
    rcfg = ResNetConfig(name="t", widths=(8, 16), depths=(1, 1),
                        in_channels=1, num_classes=10)
    params = init_resnet(jax.random.PRNGKey(0), rcfg)
    return api.FederatedTask(
        loss_fn=lambda p, b: resnet_loss(p, rcfg, b),
        eval_fn=lambda p, b: resnet_loss(p, rcfg, b)[1],
        params0=params, clients=clients, test_data=data["test"],
    )


def _cfg(mode):
    topo = {
        "sync": api.TopologyConfig(),
        "async_hier": api.TopologyConfig(mode="async_hier", n_regions=2,
                                         buffer_k=2, concurrency=4),
        "gossip": api.TopologyConfig(mode="gossip", graph="ring", mixing_steps=2),
    }[mode]
    return api.ExperimentConfig(training=api.TrainingConfig(**_BASE), topology=topo)


class _Capture:
    def __init__(self):
        self.events = []

    def emit(self, event):
        self.events.append(event)


@pytest.fixture(scope="module")
def observed_runs(tmp_path_factory):
    """One traced (full RunArtifacts) + one untraced run per strategy."""
    runs = {}
    for mode in ("sync", "async_hier", "gossip"):
        d = str(tmp_path_factory.mktemp(f"obs_{mode}"))
        arts = obs.RunArtifacts(d)
        cap = _Capture()
        fed = api.Federation(_cfg(mode), _task(), telemetry=[*arts.sinks, cap],
                             tracer=arts.tracer)
        arts.metrics.model_bytes = fed.ctx.model_bytes
        hist = fed.run()
        arts.finalize(cfg=_cfg(mode), strategy=fed.strategy.name,
                      summary={"final_acc": hist["final_acc"]})
        hist_plain = api.Federation(_cfg(mode), _task()).run()
        runs[mode] = dict(dir=d, hist=hist, hist_plain=hist_plain,
                          events=cap.events)
    return runs


def test_federation_spans_in_profiler_trace(tmp_path):
    """A traced run under the profiler puts the round loop's spans on the
    profiler's clock, beside the device's operations."""
    fed = api.Federation(_cfg("sync"), _task(), tracer=obs.Tracer())
    with jax.profiler.trace(str(tmp_path), profiler_options=_profile_options()):
        fed.run()
    assert {"fed.round", "fed.cohort_inputs", "fed.accounting"} <= _profiled_span_names(tmp_path)


@pytest.mark.parametrize("mode", ["sync", "async_hier", "gossip"])
def test_event_log_round_trips(observed_runs, mode):
    run = observed_runs[mode]
    back = obs.read_events(os.path.join(run["dir"], "events.jsonl"))
    assert back == run["events"]  # field-exact typed round-trip
    assert len(back) == _BASE["rounds"]
    assert all(type(e).__name__ == _EXPECTED_EVENT[mode] for e in back)


@pytest.mark.parametrize("mode", ["sync", "async_hier", "gossip"])
def test_trace_artifacts_and_manifest(observed_runs, mode):
    run = observed_runs[mode]
    rows = obs.read_spans(os.path.join(run["dir"], "trace.jsonl"))
    names = {r["name"] for r in rows}
    assert _EXPECTED_SPANS[mode] <= names
    # the root span is the strategy run and every other span nests inside it
    roots = [r for r in rows if r["depth"] == 0]
    assert len(roots) == 1 and roots[0]["name"] == "run"
    assert roots[0]["attrs"]["strategy"] == mode
    end = roots[0]["ts_us"] + roots[0]["dur_us"]
    assert all(r["ts_us"] + r["dur_us"] <= end + 1.0 for r in rows)
    # instrumented spans carry the CO2/bytes the report attributes per phase
    outer = "flush" if mode == "async_hier" else "round"
    attrs = [r["attrs"] for r in rows if r["name"] == outer]
    assert len(attrs) == _BASE["rounds"]
    assert sum(a["co2_g"] for a in attrs) > 0

    _validate_chrome(os.path.join(run["dir"], "trace.json"))
    man = obs.read_manifest(os.path.join(run["dir"], "run.json"))
    assert man["strategy"] == mode
    assert man["config_hash"] == obs.config_hash(_cfg(mode))

    snap = json.load(open(os.path.join(run["dir"], "metrics.json")))
    assert snap["events"] == _BASE["rounds"]
    assert snap["co2_g_total"] > 0


@pytest.mark.parametrize("mode, name, parent", _NESTED_ONCE_PER_ROUND)
def test_phase_spans_nest_once_per_round(observed_runs, mode, name, parent):
    rows = obs.read_spans(os.path.join(observed_runs[mode]["dir"], "trace.jsonl"))
    spans = [r for r in rows if r["name"] == name]
    parents = [r for r in rows if r["name"] == parent]
    assert len(spans) == _BASE["rounds"]
    for r in spans:
        assert any(p["depth"] == r["depth"] - 1 and p["ts_us"] <= r["ts_us"]
                   and r["ts_us"] + r["dur_us"] <= p["ts_us"] + p["dur_us"]
                   for p in parents), (name, parent)


@pytest.mark.parametrize("mode", ["sync", "async_hier", "gossip"])
def test_train_span_records_clients_per_pass(observed_runs, mode):
    """The cohort trainers run one client per pass of the model."""
    rows = obs.read_spans(os.path.join(observed_runs[mode]["dir"], "trace.jsonl"))
    trains = [r for r in rows if r["name"] == "train"]
    assert trains and all(r["attrs"]["clients_per_pass"] == 1 for r in trains)


@pytest.mark.parametrize("mode", ["sync", "async_hier", "gossip"])
def test_tracing_leaves_history_bitwise_identical(observed_runs, mode):
    run = observed_runs[mode]
    assert run["hist"] == run["hist_plain"]


def test_report_cli_summarizes_run_dir(observed_runs, capsys):
    rc = report_mod.main([observed_runs["async_hier"]["dir"]])
    out = capsys.readouterr().out
    assert rc == 0
    assert "per-phase breakdown" in out
    assert "flush" in out and "train" in out
    assert "strategy=async_hier" in out
    assert "CO2 by region" in out

    rc = report_mod.main([observed_runs["gossip"]["dir"]])
    out = capsys.readouterr().out
    assert rc == 0 and "mix" in out and "final consensus distance" in out


# ---------------------------------------------------------------------------
# simulated-clock column (engine-driven runs)
# ---------------------------------------------------------------------------


def test_report_sim_clock_column_from_engine_spans(tmp_path):
    """Engine-driven spans attribute simulated time (``sim_s`` per phase,
    ``sim_time_s`` clock stamps): the summary aggregates them and the
    rendered table gains a ``sim_s`` column — sum for phases that account
    simulated duration, furthest clock instant for ones that only stamp it,
    '-' for phases the simulated clock never touched."""
    path = str(tmp_path / "trace.jsonl")
    tr = obs.Tracer(jsonl_path=path, clock=_ticking_clock())
    with tr.span("round", round=0) as sp:
        sp.set(sim_s=120.0, sim_time_s=120.0)
    with tr.span("round", round=1) as sp:
        sp.set(sim_s=240.0, sim_time_s=360.0)
    with tr.span("flush") as sp:
        sp.set(sim_time_s=500.0)  # stamp only: no per-phase duration
    with tr.span("eval"):
        pass  # untouched by the simulated clock
    tr.close()

    rows = obs.read_spans(path)
    agg = {a["phase"]: a for a in report_mod.summarize_spans(rows)}
    assert agg["round"]["sim_s"] == 360.0          # summed across rounds
    assert agg["round"]["sim_time_max"] == 360.0   # furthest instant
    assert agg["flush"]["sim_s"] == 0.0
    assert agg["flush"]["sim_time_max"] == 500.0
    assert agg["eval"]["sim_s"] == 0.0 and agg["eval"]["sim_time_max"] == 0.0

    out = report_mod.render({"spans": rows, "events": [], "manifest": None})
    header = next(l for l in out.splitlines() if l.startswith("  phase"))
    assert header.rstrip().endswith("sim_s")
    by_line = {l.split()[0]: l for l in out.splitlines() if l.startswith("  ")}
    assert by_line["round"].rstrip().endswith("360.0")
    assert by_line["flush"].rstrip().endswith("500.0")  # clock-stamp fallback
    assert by_line["eval"].rstrip().endswith("-")


def test_report_without_sim_attrs_renders_legacy_table(tmp_path):
    """Wall-clock-only runs must render exactly as before: no sim column."""
    path = str(tmp_path / "trace.jsonl")
    tr = obs.Tracer(jsonl_path=path, clock=_ticking_clock())
    with tr.span("round", round=0) as sp:
        sp.set(co2_g=5.0)
    with tr.span("eval"):
        pass
    tr.close()
    out = report_mod.render(
        {"spans": obs.read_spans(path), "events": [], "manifest": None}
    )
    assert "sim_s" not in out
    assert "per-phase breakdown" in out


# ---------------------------------------------------------------------------
# Streaming primitives (obs.streaming)
# ---------------------------------------------------------------------------


def test_streaming_histogram_quantiles_within_relative_error():
    rng = np.random.default_rng(0)
    vs = rng.lognormal(mean=1.0, sigma=1.5, size=20_000)
    h = obs.StreamingHistogram(rel_err=0.01)
    for v in vs:
        h.observe(float(v))
    assert h.count == len(vs)
    assert h.min == pytest.approx(float(vs.min()))
    assert h.max == pytest.approx(float(vs.max()))
    assert h.sum == pytest.approx(float(vs.sum()), rel=1e-9)
    for q in (1, 25, 50, 90, 99):
        exact = float(np.percentile(vs, q))
        # rel_err-bounded bucket representative + rank-vs-interpolation slack
        assert abs(h.percentile(q) - exact) <= 0.05 * exact, q
    # the whole histogram is a few dozen occupied log buckets, not 20k floats
    assert h.n_buckets < 2_000
    with pytest.raises(ValueError):
        obs.StreamingHistogram(rel_err=0.0)


def test_streaming_histogram_signed_and_zero_values():
    h = obs.StreamingHistogram()
    for v in (-100.0, -1.0, 0.0, 0.0, 1.0, 100.0):
        h.observe(v)
    assert h.count == 6 and h.zero_count == 2
    assert h.min == -100.0 and h.max == 100.0
    assert h.percentile(0) == pytest.approx(-100.0, rel=0.03)
    assert h.percentile(50) == 0.0
    assert h.percentile(100) == pytest.approx(100.0, rel=0.03)
    assert obs.StreamingHistogram().snapshot() == {"count": 0}
    assert math.isnan(obs.StreamingHistogram().percentile(50))


def test_streaming_histogram_merge_matches_single_pass():
    rng = np.random.default_rng(1)
    va, vb = rng.exponential(5.0, 3000), rng.exponential(50.0, 3000)
    a, b, both = (obs.StreamingHistogram() for _ in range(3))
    for v in va:
        a.observe(float(v))
        both.observe(float(v))
    for v in vb:
        b.observe(float(v))
        both.observe(float(v))
    a.merge(b)
    # merging same-rel_err histograms is bucket-exact
    assert a.count == both.count and a.n_buckets == both.n_buckets
    assert a.sum == pytest.approx(both.sum)
    for q in (10, 50, 90, 99):
        assert a.percentile(q) == both.percentile(q)
    with pytest.raises(ValueError):
        a.merge(obs.StreamingHistogram(rel_err=0.05))


def test_windowed_rate_slides_and_expires():
    t = [0.0]
    r = obs.WindowedRate(window_s=10.0, n_slots=10, clock=lambda: t[0])
    assert r.rate() == 0.0  # before any add
    for i in range(5):
        t[0] = float(i)
        r.add()
    t[0] = 4.0
    assert r.rate() == pytest.approx(5 / 4)  # 5 events over the 4 s covered
    t[0] = 20.0  # the clock lapped every slot: the window is empty
    assert r.rate() == 0.0
    r.add(3.0)
    assert r.rate() == pytest.approx(3.0 / 10.0)  # full window covered now
    with pytest.raises(ValueError):
        obs.WindowedRate(window_s=0.0)


def test_histogram_spills_to_streaming_at_threshold():
    h = obs.Histogram(spill_at=100)
    for v in range(1, 100):
        h.observe(float(v))
    assert not h.streaming and h.percentile(50) == pytest.approx(50.0)
    h.observe(100.0)  # the 100th observation trips the spill
    assert h.streaming and h.values == [] and h.count == 100
    snap = h.snapshot()
    assert snap["streaming"] is True and snap["count"] == 100
    assert snap["min"] == 1.0 and snap["max"] == 100.0
    assert snap["mean"] == pytest.approx(50.5)
    assert snap["p50"] == pytest.approx(50.5, rel=0.03)
    for _ in range(1000):  # post-spill observations fold in, memory fixed
        h.observe(50.0)
    assert h.count == 1100 and h.values == []
    # the registry default keeps batch runs on the exact path
    assert obs.Histogram().spill_at == obs.Histogram.SPILL_AT == 4096


# ---------------------------------------------------------------------------
# Simulated-time timelines (obs.timeline)
# ---------------------------------------------------------------------------


def test_timeline_bins_series_by_kind():
    tl = obs.Timeline(max_bins=16, bin_s=10.0)
    tl.record("events", 0.0, 1.0)
    tl.record("events", 5.0, 2.0)
    tl.record("events", 15.0, 4.0)
    tl.record("stale", 5.0, 1.0, kind="mean")
    tl.record("stale", 7.0, 3.0, kind="mean")
    tl.record("active", 5.0, 10.0, kind="max")
    tl.record("active", 6.0, 4.0, kind="max")
    tl.record("err", 5.0, 9.0, kind="last")
    tl.record("err", 6.0, 5.0, kind="last")
    d = tl.to_dict()
    assert d["schema"] == obs.TIMELINE_SCHEMA and d["n_bins"] == 2
    assert d["series"]["events"]["values"] == [3.0, 4.0]   # sum per bin
    assert d["series"]["stale"]["values"] == [2.0, None]   # mean of samples
    assert d["series"]["active"]["values"] == [10.0, None]  # max
    assert d["series"]["err"]["values"] == [5.0, None]     # last sample wins
    assert tl.rate_per_s("events") == [0.3, 0.4]
    with pytest.raises(TypeError):
        tl.record("events", 0.0, 1.0, kind="mean")  # kind fixed at creation
    with pytest.raises(TypeError):
        tl.rate_per_s("stale")
    with pytest.raises(ValueError):
        tl.record("events", -1.0, 1.0)
    with pytest.raises(ValueError):
        tl.record("events", float("nan"), 1.0)


def test_timeline_bin_doubling_keeps_memory_fixed():
    tl = obs.Timeline(max_bins=16, bin_s=1.0)
    n = 10_000
    for t in range(n):
        tl.record("events", float(t), 1.0)
        tl.record("err", float(t), float(n - t), kind="last")
    # 10⁴ seconds into 16 bins: the width doubled 1 -> 1024 s
    assert tl.bin_s == 1024.0
    assert tl.n_bins == math.ceil(n / tl.bin_s) <= 16
    d = tl.to_dict()
    assert sum(v for v in d["series"]["events"]["values"] if v) == n
    # 'last' keeps the latest sample through every compaction
    assert d["series"]["err"]["values"][-1] == 1.0


def test_timeline_save_read_and_carbon_curves(tmp_path):
    trace = synthetic_trace(50, 2.0, n_regions=3, seed=2)
    tl = obs.Timeline(max_bins=64, bin_s=300.0, meta={"strategy": "sync"})
    tl.record_carbon(trace, horizon_s=3600.0)
    assert tl.series_names == [f"carbon_intensity/r{r}" for r in range(3)]
    # the horizon cap kept the bins inside the first simulated hour: no
    # widening for curve samples the replay never reaches
    assert tl.bin_s == 300.0 and tl.n_bins * tl.bin_s <= 3600.0 + tl.bin_s
    assert tl.meta["horizon_s"] == 3600.0 and tl.meta["strategy"] == "sync"
    p = tl.save(str(tmp_path / "timeline.json"))
    assert obs.read_timeline(p) == json.loads(json.dumps(tl.to_dict()))
    bad = tmp_path / "bad.json"
    bad.write_text('{"schema": "other/v1"}')
    with pytest.raises(ValueError, match="timeline"):
        obs.read_timeline(str(bad))


# ---------------------------------------------------------------------------
# Span sampling + rollups (obs.trace at engine scale)
# ---------------------------------------------------------------------------


def test_tracer_sampling_is_deterministic_per_name():
    tr = obs.Tracer(clock=_ticking_clock(), sample=0.1)
    for i in range(100):
        with tr.span("round", round=i):
            pass
    with tr.span("rare"):
        pass
    # 1-in-10 is deterministic per name: the first of every 10 occurrences
    kept = [s.attrs["round"] for s in tr.spans if s.name == "round"]
    assert kept == list(range(0, 100, 10))
    # a rare phase always keeps its first occurrence
    assert [s.name for s in tr.spans if s.name == "rare"] == ["rare"]
    # ...while the rollup covers every span, sampled or not
    roll = tr.rollup()
    assert roll["round"]["count"] == 100 and roll["rare"]["count"] == 1
    assert roll["round"]["total_s"] == pytest.approx(tr.stats["round"].total_s)
    assert roll["round"]["p50_ms"] > 0
    with pytest.raises(ValueError):
        obs.Tracer(sample=0.0)
    with pytest.raises(ValueError):
        obs.Tracer(sample=1.5)


def test_tracer_max_spans_caps_memory_not_the_stream(tmp_path):
    path = str(tmp_path / "trace.jsonl")
    tr = obs.Tracer(jsonl_path=path, clock=_ticking_clock(), max_spans=5)
    for i in range(20):
        with tr.span("round", round=i):
            pass
    tr.close()
    assert len(tr.spans) == 5 and tr.dropped_spans == 15
    assert tr.stats["round"].count == 20       # rollups never drop
    assert len(obs.read_spans(path)) == 20     # the JSONL keeps flowing
    out = tr.export_rollup(str(tmp_path / "rollup.json"))
    doc = json.load(open(out))
    assert doc["dropped_spans"] == 15 and doc["spans"]["round"]["count"] == 20


def test_null_tracer_has_empty_rollup():
    assert obs.NULL_TRACER.stats == {}
    assert obs.NULL_TRACER.rollup() == {}


# ---------------------------------------------------------------------------
# Health monitor (obs.health)
# ---------------------------------------------------------------------------


def test_health_nan_and_divergence_detectors():
    hm = obs.HealthMonitor(warmup=5)
    for i in range(10):
        hm.emit(_round_event(round=i, loss=1.0 / (i + 1)))
    assert hm.ok and hm.counts == {}
    hm.emit(_round_event(round=10, loss=float("nan")))
    assert not hm.ok and hm.counts["nan"] == 1
    hm.emit(_round_event(round=11, loss=50.0))  # 500x the best of 0.1
    assert hm.counts["divergence"] == 1
    a = next(x for x in hm.alerts if x.kind == "divergence")
    assert a.severity == "warn" and "best" in a.message


def test_health_budget_alarms_fire_once():
    hm = obs.HealthMonitor(eps_budget=1.0, carbon_budget_g=100.0)
    for i in range(5):
        hm.emit(_round_event(round=i, eps_spent=2.0, cum_co2_g=500.0))
    assert hm.counts == {"carbon_budget": 1, "eps_budget": 1}
    assert not hm.ok
    snap = hm.snapshot()
    assert snap["schema"] == obs.HEALTH_SCHEMA
    assert snap["ok"] is False and snap["events_seen"] == 5


def test_health_straggler_z_score_carries_region():
    hm = obs.HealthMonitor(warmup=10, z_thresh=4.0)
    for i in range(40):
        hm.emit(_flush_event(round=i, duration_s=1.0 + 0.01 * (i % 5),
                             sim_time_s=float(i)))
    assert "straggler" not in hm.counts
    hm.emit(_flush_event(round=40, duration_s=30.0, sim_time_s=40.0))
    assert hm.counts["straggler"] == 1
    a = hm.alerts[-1]
    assert a.kind == "straggler" and a.severity == "warn"
    assert a.context["region"] == 1 and a.context["z"] > 4.0
    assert hm.ok  # warns alone don't fail health


def test_health_alert_records_bounded_counts_exact():
    hm = obs.HealthMonitor(max_alerts_per_kind=3)
    for i in range(10):
        hm.emit(_round_event(round=i, loss=float("nan")))
    assert hm.counts["nan"] == 10      # counts stay exact
    assert len(hm.alerts) == 3         # retained records are capped


def test_health_sim_stall_detector():
    hm = obs.HealthMonitor(stall_after_events=5)
    for i in range(20):
        hm.emit(_round_event(round=i))  # sim_time_s all 0: batch run
    assert "sim_stall" not in hm.counts
    hm2 = obs.HealthMonitor(stall_after_events=5)
    for i in range(8):
        hm2.emit(_round_event(round=i, sim_time_s=10.0))  # stuck clock
    assert hm2.counts["sim_stall"] == 1  # fires once at the threshold


def test_health_round_reset_starts_new_segment():
    hm = obs.HealthMonitor(warmup=3)
    for i in range(20):
        hm.emit(_round_event(round=i, loss=0.01))
    # the next strategy reuses the monitor: its round counter restarts and
    # its (higher) loss regime must not read as divergence of the first
    for i in range(20):
        hm.emit(_round_event(round=i, loss=5.0))
    assert "divergence" not in hm.counts


def test_health_json_round_trip(tmp_path):
    hm = obs.HealthMonitor(carbon_budget_g=1.0)
    hm.emit(_round_event())  # cum_co2_g=10 >= budget: error alert
    p = hm.to_json(str(tmp_path / "health.json"))
    doc = obs.read_health(p)
    assert doc == json.loads(json.dumps(hm.snapshot()))
    assert doc["ok"] is False
    bad = tmp_path / "bad.json"
    bad.write_text('{"schema": "nope"}')
    with pytest.raises(ValueError, match="health"):
        obs.read_health(str(bad))


# ---------------------------------------------------------------------------
# Engine-scale observation (ReplayEngine through the obs v2 layer)
# ---------------------------------------------------------------------------

_ENGINE_EVENT = {"sync": api.RoundEvent, "async_hier": api.FlushEvent,
                 "gossip": api.MixEvent}


@pytest.fixture(scope="module")
def engine_trace():
    return synthetic_trace(500, 2.0, rate_per_client_per_h=2.0, n_regions=4,
                           seed=9)


@pytest.mark.parametrize("mode", list(DISCIPLINES))
def test_engine_observed_run_bitwise_identical(engine_trace, mode):
    cfg = ReplayConfig(strategy=mode, dim=8, cohort=16, buffer_k=8, seed=3)
    plain = ReplayEngine(engine_trace, cfg).run()
    eng = ReplayEngine(engine_trace, cfg)
    cap = _Capture()
    sink = obs.MetricsSink()
    hm = obs.HealthMonitor()
    tl = obs.Timeline(max_bins=64)
    rep = eng.run(tracer=obs.Tracer(clock=_ticking_clock(), sample=0.5),
                  telemetry=[cap, sink, hm], timeline=tl)
    # observation is read-only: the trajectory is bitwise identical
    for k in plain:
        if k not in ("host_s", "events_per_s"):
            assert rep[k] == plain[k], k
    # one typed event per applied update, stamped with the simulated clock
    assert len(cap.events) == rep["updates"] > 0
    assert all(type(e) is _ENGINE_EVENT[mode] for e in cap.events)
    stamps = [e.sim_time_s for e in cap.events]
    assert stamps == sorted(stamps) and stamps[-1] > 0
    if mode == "async_hier":
        # completions after the last flush still charge CO₂ but are no update
        assert cap.events[-1].cum_co2_g <= rep["co2_kg"] * 1e3
    else:
        assert cap.events[-1].cum_co2_g == pytest.approx(rep["co2_kg"] * 1e3)
    assert sink.snapshot()["events"] == rep["updates"]
    assert hm.events_seen == rep["updates"]
    # the timeline binned the run against simulated time
    assert 0 < tl.n_bins <= 64
    total = sum(v for v in tl.to_dict()["series"]["events"]["values"] if v)
    if mode == "async_hier":
        # completions buffered past the last flush are never an update
        assert 0 < total <= rep["events"]
    else:
        assert total == rep["events"]
    assert any(n.startswith("carbon_intensity/") for n in tl.series_names)
    assert "error" in tl.series_names and "wire_bytes" in tl.series_names
    if mode == "async_hier":
        assert "staleness" in tl.series_names
    if mode == "gossip":
        assert "consensus" in tl.series_names
        assert all(e.mix_steps >= 1 for e in cap.events)


def test_engine_100k_update_fully_observed_replay_memory_bounded(tmp_path):
    """The acceptance bar: a 10⁵-update replay with tracer + metrics +
    health + timeline all on stays inside a fixed memory envelope."""
    trace = synthetic_trace(20_000, 5.0, rate_per_client_per_h=1.0, seed=4)
    assert trace.n_events >= 90_000
    cfg = ReplayConfig(strategy="sync", dim=4, cohort=1, seed=0)
    eng = ReplayEngine(trace, cfg)
    tracer = obs.Tracer(sample=0.01, max_spans=1_000)
    sink = obs.MetricsSink()
    hm = obs.HealthMonitor()
    tl = obs.Timeline()
    tracemalloc.start()
    rep = eng.run(tracer=tracer, telemetry=[sink, hm], timeline=tl)
    _, peak = tracemalloc.get_traced_memory()
    tracemalloc.stop()
    assert rep["updates"] >= 90_000
    assert peak < 64 * 1024 * 1024, f"peak {peak / 1e6:.1f} MB"
    # every bounded structure actually engaged its bound
    assert sink.registry.histogram("duration_s").streaming
    assert len(tracer.spans) <= 1_000
    assert tracer.stats["round"].count == rep["updates"]
    assert tl.n_bins <= tl.max_bins
    assert sum(v for v in tl.to_dict()["series"]["events"]["values"] if v) \
        == rep["events"]
    assert not any(a.severity == "error" for a in hm.alerts)
    # the durable forms round-trip
    doc = obs.read_timeline(tl.save(str(tmp_path / "timeline.json")))
    assert doc["n_bins"] == tl.n_bins
    assert obs.read_health(hm.to_json(str(tmp_path / "health.json")))["ok"]


# ---------------------------------------------------------------------------
# RunArtifacts v2 bundle, report --strict, and the live tailer
# ---------------------------------------------------------------------------


def test_run_artifacts_v2_bundle(tmp_path):
    d = str(tmp_path / "run")
    arts = obs.RunArtifacts(d)
    with arts.tracer.span("round", round=0):
        pass
    for s in arts.sinks:
        s.emit(_round_event())
    arts.new_timeline().record("events", 0.0, 1.0)
    arts.new_timeline("gossip").record("events", 0.0, 2.0)
    with pytest.raises(ValueError):
        arts.new_timeline("gossip")
    arts.finalize(strategy="sync", summary={"x": 1})
    assert sorted(os.listdir(d)) == [
        "events.jsonl", "health.json", "metrics.json", "run.json",
        "spans_rollup.json", "timeline.json", "timeline_gossip.json",
        "trace.json", "trace.jsonl",
    ]
    roll = json.load(open(os.path.join(d, "spans_rollup.json")))
    assert roll["sample"] == 1.0 and roll["spans"]["round"]["count"] == 1
    assert obs.read_health(os.path.join(d, "health.json"))["events_seen"] == 1
    tl_doc = obs.read_timeline(os.path.join(d, obs.RunArtifacts.TIMELINE_JSON))
    assert tl_doc["series"]["events"]["values"] == [1.0]
    assert obs.read_timeline(arts.timeline_path("gossip"))[
        "series"]["events"]["values"] == [2.0]


def test_report_strict_gates_on_health(tmp_path, capsys):
    d = str(tmp_path / "run")
    arts = obs.RunArtifacts(d, health=obs.HealthMonitor(carbon_budget_g=1.0))
    with arts.tracer.span("round", round=0):
        pass
    for s in arts.sinks:
        s.emit(_round_event())  # cum_co2_g=10 >= budget 1: error alert
    arts.new_timeline(bin_s=30.0).record("events", 0.0, 1.0)
    arts.finalize(strategy="sync")
    rc = report_mod.main([d])
    out = capsys.readouterr().out
    assert rc == 0  # non-strict: alerts render but don't gate
    assert "alerts: 1 (UNHEALTHY)" in out and "carbon_budget" in out
    assert "span rollups" in out
    assert "timeline timeline.json: 1 bins x 30 s" in out
    rc = report_mod.main([d, "--strict"])
    capsys.readouterr()
    assert rc == 2


def test_report_alerts_section_when_healthy(tmp_path, capsys):
    d = str(tmp_path / "run")
    arts = obs.RunArtifacts(d)
    for s in arts.sinks:
        s.emit(_round_event())
    arts.finalize(strategy="sync")
    rc = report_mod.main([d, "--strict"])
    out = capsys.readouterr().out
    assert rc == 0
    assert "alerts: none (1 events monitored)" in out


def test_watch_event_tail_and_once(tmp_path):
    import io

    d = str(tmp_path / "run")
    os.makedirs(d)
    path = os.path.join(d, "events.jsonl")
    sink = obs.JsonlSink(path)
    first = [_round_event(round=0, sim_time_s=100.0),
             _flush_event(round=1, sim_time_s=200.0)]
    for e in first:
        sink.emit(e)
    tail = watch_mod.EventTail(path)
    assert tail.poll() == first          # typed, field-exact
    assert tail.poll() == []             # nothing new
    sink.emit(_mix_event(round=2))
    assert [type(e).__name__ for e in tail.poll()] == ["MixEvent"]
    sink.close()
    # a partial trailing line stays buffered until its newline arrives
    line = json.dumps({"event": "RoundEvent",
                       **dataclasses.asdict(_round_event(round=3))}) + "\n"
    with open(path, "a") as f:
        f.write(line[:20])
    assert tail.poll() == []
    with open(path, "a") as f:
        f.write(line[20:])
    assert tail.poll() == [_round_event(round=3)]

    buf = io.StringIO()
    rc = watch_mod.watch(d, once=True, stream=buf)
    out = buf.getvalue()
    assert rc == 0
    assert "events=4" in out and "sim=" in out and "alerts=0" in out
    assert watch_mod.main([path, "--once"]) == 0
