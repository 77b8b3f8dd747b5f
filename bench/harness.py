"""One run of one benchmark cell: set-up, the timed window, the check.

A cell is a configuration (``bench/configs/<name>.json``, with its plain
reference ``bench/configs/<reference>.py`` and its data generator
``bench/datasets/<generator>.py``), a traffic mix
(``bench/traffic/<name>.json``) and the limits its check was calibrated to
(``bench/cells/<cell>.json``).  Per-layer metrics are readers in
``bench/metrics/<metric>.py``.  All of them are found by the names in
``BENCHMARK.json`` and the files it names, so a new cell, configuration,
kind of data or metric is new files.

A configuration's ``dataset`` names its ``generator``, whose
``make(spec, seed)`` returns ``{"train": {key: array}, "test": {...}}``
keyed by the program's batch keys, and ``partition_by``, the integer key
(a label, a domain) that the clients' Dirichlet skew is drawn over.

A run:

1. makes the data, the Dirichlet partition and the initial weights from
   the seed (the generator, ``generate``, the reference module's
   ``init_params``);
2. builds ``repro.api.Federation`` with the cell's experiment;
3. runs the initial evaluation and ``warmup_rounds`` rounds: every program
   the window uses is compiled there (the trainer, the privacy pipeline
   and its kernel, the server update, the evaluation);
4. times whole rounds: for ``--seconds`` with ``--trace 0``; with
   ``--trace 1`` it traces two evaluation periods of rounds under the
   profiler and reads the per-layer metrics from the spans and the trace;
5. frees the program, then runs the plain reference over the first
   warm-up round from the same weights and cohort and compares.

The check's copies of the weights (the initial ones and each warm-up
round's) wait on the host, so the device holds only the program's arrays
while the window runs and only the reference's while the check runs.  The
device's bytes in use when the window opens and when the check starts, and
its peak after the check, go to stderr.

The window is stopped from a telemetry sink, which the program calls with
each round's event at the end of the round (as it calls ``progress``).
"""
from __future__ import annotations

import functools
import gc
import importlib
import importlib.util
import json
import math
import shutil
import sys
import tempfile
import time
from pathlib import Path
from typing import Callable, Optional

import jax
import numpy as np

from bench import generate, spans, trace_reduce
from bench.peaks import peaks

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_EVENTS = ("/jax/compilation_cache/cache_hits", "/jax/compilation_cache/cache_misses")
HUGE_ROUNDS = 10 ** 9  # the window, not the round count, ends a run
DATASET_KEYS = ("generator", "partition_by")


def bytes_on_device(devices, key: str = "bytes_in_use") -> int:
    """A memory statistic of the fullest device; 0 where none is reported."""
    return max(int((d.memory_stats() or {}).get(key, 0)) for d in devices)


def cache_size(path: Path) -> str:
    files = list(path.glob("*")) if path.is_dir() else []
    return f"{len(files)} files, {sum(f.stat().st_size for f in files) / 2**20:.1f} MiB"


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


class WindowClosed(Exception):
    """Raised from the progress callback to end ``Federation.run``."""


class CompileClock:
    """Counts the XLA backend compiles JAX reports, and their seconds."""

    def __init__(self):
        self.seconds = 0.0
        self.count = 0
        self.cache = {e: 0 for e in CACHE_EVENTS}
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event: str, duration: float, **_) -> None:
        if event == COMPILE_EVENT:
            self.seconds += duration
            self.count += 1

    def _on_event(self, event: str, **_) -> None:
        if event in self.cache:
            self.cache[event] += 1

    def mark(self) -> tuple[int, float]:
        return self.count, self.seconds


@functools.lru_cache(maxsize=None)
def load_module(path: Path):
    """A benchmark file found by name, loaded once per process."""
    spec = importlib.util.spec_from_file_location(f"bench_file_{path.stem.replace('-', '_')}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _merge(base: dict, extra: dict) -> dict:
    out = dict(base)
    for k, v in extra.items():
        out[k] = _merge(out[k], v) if isinstance(v, dict) and isinstance(out.get(k), dict) else v
    return out


def load_cell(root: Path, workload: str, overrides: Optional[dict] = None) -> dict:
    """Everything a run of ``workload`` needs, found by name.  ``overrides``
    (tests) deep-merge into the configuration and the traffic."""
    spec = json.loads((root / "BENCHMARK.json").read_text())
    by_name = {w["name"]: w for w in spec["workloads"]}
    if workload not in by_name:
        raise SystemExit(f"unknown workload {workload!r}; known: {sorted(by_name)}")
    w = by_name[workload]
    entry = next(c for c in spec["configs"] if c["name"] == w["config"])
    config = json.loads((root / entry["file"]).read_text())
    traffic = json.loads((root / "bench" / "traffic" / f"{w['traffic']}.json").read_text())
    limits = json.loads((root / "bench" / "cells" / f"{workload}.json").read_text())["limits"]
    overrides = overrides or {}
    config = _merge(config, overrides.get("config", {}))
    traffic = _merge(traffic, overrides.get("traffic", {}))
    missing = [k for k in DATASET_KEYS if k not in config["dataset"]]
    if missing:
        raise ValueError(f"{entry['file']}: \"dataset\" lacks {' and '.join(missing)}, which have "
                         f"no default: it names its generator (bench/datasets/<generator>.py) "
                         f"and the integer key that the partition is drawn over")

    def for_cell(metrics):
        return [m for m in metrics if workload in m.get("workloads", [workload])]

    return {
        "name": workload, "chips": w["chips"], "config": config, "traffic": traffic,
        "limits": limits, "end_to_end": for_cell(spec["end_to_end"]),
        "per_layer": for_cell(spec["per_layer"]),
        "reference": load_module(root / "bench" / "configs" / f"{config['reference']}.py"),
        "generator": load_module(root / "bench" / "datasets" / f"{config['dataset']['generator']}.py"),
        "check": load_module(root / "bench" / "checks" / f"{traffic['check']}.py"),
        "metrics_dir": root / "bench" / "metrics",
    }


def experiment(cell: dict, seed: int):
    from repro.api import ExperimentConfig

    proto, traffic = cell["config"]["protocol"], cell["traffic"]
    training = {k: proto[k] for k in ("algorithm", "n_clients", "clients_per_round", "batch_size",
                                      "client_lr", "client_momentum", "server_lr", "eval_every",
                                      "max_eval_batches")}
    training.update(rounds=HUGE_ROUNDS, local_steps=traffic["local_steps"], seed=seed)
    return ExperimentConfig.from_dict(_merge({"training": training}, traffic["experiment"]))


def program_task(cell: dict, params0, data: dict, parts, client_seed: int):
    from repro.api import FederatedTask
    from repro.data.pipeline import build_clients

    prog = cell["config"]["program"]
    module = importlib.import_module(prog["module"])
    model = {k: tuple(v) if isinstance(v, list) else v for k, v in cell["config"]["model"].items()}
    model_cfg = getattr(module, prog["config"])(**model)
    loss = getattr(module, prog["loss"])
    return FederatedTask(
        loss_fn=lambda p, b: loss(p, model_cfg, b),
        eval_fn=lambda p, b: loss(p, model_cfg, b)[1],
        params0=params0,
        clients=build_clients(data["train"], parts, seed=client_seed),
        test_data=data["test"],
    )


class TracedRun:
    """What a per-layer reader sees of a ``--trace 1`` run."""

    def __init__(self, cell, rounds, summary, device_kind, chips):
        proto = cell["config"]["protocol"]
        self.cell = cell
        self.rounds = rounds
        self.summary = summary
        self.chips = chips
        self.peaks = peaks(device_kind)
        self.cohort = proto["clients_per_round"]
        self.param_dim = cell["reference"].param_count(cell["config"]["model"])
        self.samples = len(rounds) * self.cohort * cell["traffic"]["local_steps"] * proto["batch_size"]
        self.train_flops_per_sample = cell["reference"].train_flops_per_sample(
            cell["config"]["model"], cell["config"]["dataset"])


class _RoundSink:
    """Telemetry sink: the program emits one event at the end of each round."""

    fn: Callable = None

    def emit(self, event) -> None:
        self.fn(event)


def _top(ns: dict, n: int) -> list:
    return sorted(ns.items(), key=lambda kv: -kv[1])[:n]


def _profile_options():
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    return opts


def run_cell(root: Path, workload: str, seed: int, seconds: float, trace: bool, *,
             t0: Optional[float] = None, require_tpu: bool = True,
             overrides: Optional[dict] = None, fault: Optional[Callable] = None,
             measure: bool = True) -> tuple[dict, dict]:
    """One run.  Returns (result line, internals for calibration and tests)."""
    t0 = time.perf_counter() if t0 is None else t0
    cell = load_cell(root, workload, overrides)
    devices = jax.devices()
    if require_tpu and devices[0].platform != "tpu":
        raise NoChip(f"JAX found no TPU (platform {devices[0].platform!r})")
    if len(devices) < cell["chips"]:
        raise NoChip(f"the cell asks for {cell['chips']} chips, JAX sees {len(devices)}")
    used = devices[: cell["chips"]]
    clock = CompileClock()
    cfg, traffic, proto = cell["config"], cell["traffic"], cell["config"]["protocol"]
    seeds = generate.derive_seeds(seed)

    from repro.api import Federation

    data = cell["generator"].make(cfg["dataset"], seeds["data"])
    parts = generate.dirichlet_partition(data["train"][cfg["dataset"]["partition_by"]],
                                         proto["n_clients"], proto["dirichlet_alpha"],
                                         seeds["partition"])
    params0 = cell["reference"].init_params(cfg["model"], seeds["weights"])
    task = program_task(cell, params0, data, parts, seeds["clients"])
    # the program keeps the device arrays; the check's copy waits on the host
    params0 = jax.device_get(params0)
    tracer = spans.SpanTracer() if trace else None
    sink = _RoundSink()
    fed = Federation(experiment(cell, seeds["federation"]), task, tracer=tracer, telemetry=[sink])
    if fed.ctx.pspace.dim != cfg["param_count"]:
        raise RuntimeError(f"program holds {fed.ctx.pspace.dim} parameters, the configuration "
                           f"states {cfg['param_count']}")
    warmup = traffic["warmup_rounds"]
    trace_rounds = 2 * proto["eval_every"]
    trace_dir = Path(tempfile.mkdtemp(prefix="bench-trace-")) if trace else None
    st = {"done": 0, "checked": [], "stamps": [], "window_ann": None, "open_bytes": 0}
    if fault is not None:
        fault(fed)
    # the clients' first-step losses of round 1, read where the trainer
    # returns them; the wrapper is taken off after that round
    train_cohort = fed.ctx.train_cohort

    def first_round(params, sel, step, corrections=None):
        res = train_cohort(params, sel, step, corrections=corrections)
        st["first_losses"] = np.asarray(res.loss_first)
        fed.ctx.train_cohort = train_cohort
        return res

    fed.ctx.train_cohort = first_round

    def on_round(event) -> None:
        st["done"] += 1
        if st["done"] <= warmup:
            # the host copy waits for the round's server update
            st["checked"].append({"loss": event.loss, "selected": list(event.selected),
                                  "params": jax.device_get(fed.ctx.server_state.params)})
            if st["done"] < warmup:
                return
            st["open_bytes"] = bytes_on_device(used)
            if not measure:
                raise WindowClosed
            if trace:
                jax.profiler.start_trace(str(trace_dir), profiler_options=_profile_options())
                st["window_ann"] = jax.profiler.TraceAnnotation(trace_reduce.WINDOW)
                st["window_ann"].__enter__()
            st["compile_mark"] = clock.mark()
            st["start"] = time.perf_counter()
            return
        now = time.perf_counter()
        st["stamps"].append(now)
        in_window = len(st["stamps"])
        if (trace and in_window >= trace_rounds) or (not trace and now - st["start"] >= seconds):
            jax.block_until_ready(fed.ctx.server_state.params)
            st["stamps"][-1] = time.perf_counter()
            raise WindowClosed

    sink.fn = on_round
    try:
        fed.run()
    except WindowClosed:
        pass
    else:
        raise RuntimeError("the federation ended before the window closed")
    if trace and st["window_ann"] is not None:
        st["window_ann"].__exit__(None, None, None)
        jax.profiler.stop_trace()

    n_compiles = clock.count - st.get("compile_mark", clock.mark())[0]
    compile_in_window = clock.seconds - st.get("compile_mark", clock.mark())[1]
    peak_bytes = bytes_on_device(used, "peak_bytes_in_use")
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": peak_bytes}

    metrics: dict = {}
    breakdown = None
    rounds = len(st["stamps"])
    if measure:
        start, stamps = st["start"], st["stamps"]
        durs = np.diff([start] + stamps)
        window_s = stamps[-1] - start
        samples = rounds * proto["clients_per_round"] * traffic["local_steps"] * proto["batch_size"]
        print(f"window: {rounds} rounds in {window_s:.3f} s; round s min {durs.min():.4f} "
            f"median {float(np.median(durs)):.4f} max {durs.max():.4f}; compiles in window "
            f"{n_compiles} ({compile_in_window:.2f} s); set-up compile {st['compile_mark'][1]:.2f} s "
            f"in {st['compile_mark'][0]} compiles; cache {clock.cache}", file=sys.stderr)
        if not trace:
            values = {"samples_per_s": samples / window_s,
                      "round_s_p90": float(np.percentile(durs, 90)),
                      "setup_s": st["start"] - t0}
            for m in cell["end_to_end"]:
                metrics[m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}
        else:
            xplanes = sorted(trace_dir.rglob("*.xplane.pb"))
            summary = trace_reduce.summarize(trace_reduce.load(str(xplanes[0]))) if xplanes else None
            shutil.rmtree(trace_dir, ignore_errors=True)
            in_window = spans.rounds_in(tracer.spans, start, stamps[-1] + 1.0)
            run = TracedRun(cell, in_window, summary, devices[0].device_kind, cell["chips"])
            for m in cell["per_layer"]:
                value = load_module(cell["metrics_dir"] / f"{m['name']}.py").read(run)
                if value is not None:
                    metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
            if summary is not None:
                device.update(busy_s=summary.busy_s, window_s=summary.window_s)
                breakdown = {"device_ops": [[f"program {k}", v / 1e9] for k, v in _top(summary.module_ns, 5)]
                                           + [[k, v / 1e9] for k, v in _top(summary.op_ns, 5)],
                             "idle_gaps": [[k, v / 1e9] for k, v in _top(summary.idle_ns_by_span, 10)]}

    # free the program before the reference runs
    params_checked = st["checked"]
    del fed, task, tracer, train_cohort
    gc.collect()
    live = [a.nbytes for a in jax.live_arrays()]
    held = (f"device bytes in use: window open {st['open_bytes']}, check start "
            f"{bytes_on_device(used)} ({len(live)} live arrays of {sum(live)} bytes, the largest "
            f"{max(live, default=0)}; the weights take {sum(x.nbytes for x in jax.tree.leaves(params0))})")

    check = cell["check"]
    params_checked[0]["first_losses"] = st["first_losses"]
    reference = check.reference_rounds(cell["reference"], cell, data, parts, seeds["clients"],
                                       params0, [params_checked[0]["selected"]])
    readings = check.readings(params_checked, reference, params0)
    print(f"{held}; peak after the check {bytes_on_device(used, 'peak_bytes_in_use')}", file=sys.stderr)
    # the numbers compared are those the cell's file gives a limit
    limits = cell["limits"]
    correct = all(math.isfinite(readings[n]) and readings[n] <= limit for n, limit in limits.items())
    checks = {n: {"value": readings[n], "limit": limit} for n, limit in limits.items()}

    result = {"correct": bool(correct), "attempted": rounds, "failed": 0,
              "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    internals = {"cell": cell, "data": data, "parts": parts, "params0": params0, "seeds": seeds,
                 "program": params_checked, "reference": reference, "readings": readings}
    return result, internals
