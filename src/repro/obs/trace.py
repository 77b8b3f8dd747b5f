"""Nestable span tracing for federation runs.

A :class:`Tracer` hands out context-manager *spans*::

    with tracer.span("train", round=r, cohort=k):
        ...hot path...

Each span records a monotonic start offset (``time.perf_counter`` relative
to the tracer's epoch), a duration, its nesting depth, and an attribute
dict.  For its lifetime it also holds a ``jax.profiler.TraceAnnotation``
named ``fed.<name>`` (:data:`SPAN_PREFIX`), so a run under
``jax.profiler.trace(dir)`` finds the program's spans on the profiler's
clock, beside the device's operations: an idle gap on the device can be
read against what the host was doing.  With no profiler running the
annotation is a cheap no-op.

Completed spans are kept in memory for Chrome-trace export
(:meth:`Tracer.chrome_trace` / :meth:`Tracer.export_chrome` — the
``traceEvents`` "X" complete-event form that Perfetto and ``chrome://tracing``
load directly) and, when a ``jsonl_path`` is given, streamed one JSON line
per span as they close, flushed per line so a crash loses at most the
partial final line.

At engine scale (10⁵–10⁶ spans per run) retaining every span would defeat
the observability layer's own memory bound, so a tracer can *sample*:
``Tracer(sample=0.01)`` keeps one span in 100 per name (deterministic —
the first of every period, so rare span names always keep their first
occurrence) in memory and in the JSONL stream, while per-name
:class:`SpanStats` rollups (count / total / min / max / p50 / p99 via
bounded log-bucket histograms) are updated for **every** span, sampled or
not — aggregate attribution survives sampling exactly.  ``max_spans``
additionally hard-caps the in-memory list (the JSONL stream keeps
flowing; ``dropped_spans`` counts what the cap shed).

:class:`NullTracer` is the default everywhere a tracer is optional: its
``span`` returns a shared no-op context manager (no allocation, no clock
read), so instrumented hot paths cost nothing when tracing is off.  The
module-level :data:`NULL_TRACER` singleton is what uninstrumented runs
share.
"""
from __future__ import annotations

import dataclasses
import json
import math
import os
import time
from typing import Any, Callable, Optional, TextIO

from jax.profiler import TraceAnnotation

from repro.obs.streaming import StreamingHistogram

#: prefix of the profiler annotation each span opens (``fed.round``, ...)
SPAN_PREFIX = "fed."


def _json_safe(v: Any) -> Any:
    """Attribute values must survive json.dumps; coerce exotic ones."""
    if isinstance(v, (bool, int, float, str)) or v is None:
        return v
    if isinstance(v, (tuple, list)):
        return [_json_safe(x) for x in v]
    try:  # numpy / jax scalars
        return float(v)
    except (TypeError, ValueError):
        return str(v)


@dataclasses.dataclass(frozen=True)
class SpanRecord:
    """One completed span: offsets are seconds from the tracer's epoch."""

    name: str
    start_s: float
    dur_s: float
    depth: int       # 0 = root span, 1 = nested once, ...
    attrs: dict

    def jsonl_row(self) -> dict:
        return {
            "name": self.name,
            "ts_us": self.start_s * 1e6,
            "dur_us": self.dur_s * 1e6,
            "depth": self.depth,
            "attrs": self.attrs,
        }

    def chrome_event(self, pid: int, tid: int) -> dict:
        """Chrome trace-event "X" (complete) form; ts/dur in microseconds."""
        return {
            "name": self.name,
            "ph": "X",
            "ts": self.start_s * 1e6,
            "dur": self.dur_s * 1e6,
            "pid": pid,
            "tid": tid,
            "cat": "repro",
            "args": self.attrs,
        }


class SpanStats:
    """Per-name duration rollup, updated for every span (sampled or not).

    count/total/min/max are exact; p50/p99 come from a bounded
    :class:`~repro.obs.streaming.StreamingHistogram` (1% relative error),
    so a million spans of one name cost a few dozen buckets.
    """

    __slots__ = ("count", "total_s", "min_s", "max_s", "hist")

    def __init__(self) -> None:
        self.count = 0
        self.total_s = 0.0
        self.min_s = math.inf
        self.max_s = 0.0
        self.hist = StreamingHistogram()

    def observe(self, dur_s: float) -> None:
        self.count += 1
        self.total_s += dur_s
        if dur_s < self.min_s:
            self.min_s = dur_s
        if dur_s > self.max_s:
            self.max_s = dur_s
        self.hist.observe(dur_s)

    def snapshot(self) -> dict:
        return {
            "count": self.count,
            "total_s": self.total_s,
            "mean_ms": 1e3 * self.total_s / self.count if self.count else 0.0,
            "min_ms": 1e3 * self.min_s if self.count else 0.0,
            "max_ms": 1e3 * self.max_s,
            "p50_ms": 1e3 * self.hist.percentile(50) if self.count else 0.0,
            "p99_ms": 1e3 * self.hist.percentile(99) if self.count else 0.0,
        }


class _Span:
    """Live span handed out by :meth:`Tracer.span`; records itself on exit
    and holds a ``fed.<name>`` profiler annotation while it is open."""

    __slots__ = ("_tracer", "name", "attrs", "_t0", "_depth", "_ann")

    def __init__(self, tracer: "Tracer", name: str, attrs: dict):
        self._tracer = tracer
        self.name = name
        self.attrs = attrs

    def __enter__(self) -> "_Span":
        self._ann = TraceAnnotation(SPAN_PREFIX + self.name)
        self._ann.__enter__()
        self._depth = self._tracer._enter()
        self._t0 = self._tracer._clock()
        return self

    def set(self, **attrs) -> None:
        """Attach attributes mid-span (recorded at exit) — e.g. a round's
        CO₂ is only known after the accounting step inside the span."""
        self.attrs.update({k: _json_safe(v) for k, v in attrs.items()})

    def __exit__(self, *exc) -> None:
        t1 = self._tracer._clock()
        self._ann.__exit__(*exc)
        self._tracer._exit(self.name, self._t0, t1 - self._t0, self._depth, self.attrs)


class _NullSpan:
    """Shared do-nothing context manager (see :class:`NullTracer`)."""

    __slots__ = ()

    def __enter__(self) -> "_NullSpan":
        return self

    def __exit__(self, *exc) -> None:
        pass

    def set(self, **attrs) -> None:
        pass


_NULL_SPAN = _NullSpan()


class NullTracer:
    """The no-op default: ``span`` returns a shared empty context manager.

    The instrumented engines call ``ctx.tracer.span(...)`` unconditionally;
    with this tracer that is one method call returning a cached object and
    two empty dunder calls — no clock reads, no allocation, no record, no
    profiler annotation — which is what keeps untraced runs bitwise
    identical to pre-tracing behavior (see ``tests/test_obs.py``).
    """

    enabled = False

    def span(self, name: str, **attrs) -> _NullSpan:
        return _NULL_SPAN

    @property
    def spans(self) -> list:
        return []

    @property
    def stats(self) -> dict:
        return {}

    def rollup(self) -> dict:
        return {}

    def chrome_trace(self) -> dict:
        return {"traceEvents": [], "displayTimeUnit": "ms"}

    def close(self) -> None:
        pass


#: process-wide shared no-op tracer — the default for every RuntimeContext
NULL_TRACER = NullTracer()


class Tracer:
    """Collects nested spans on a monotonic clock; exports Chrome traces.

    Parameters
    ----------
    jsonl_path:
        When given, sampled spans are appended to this file as one JSON
        line each (flushed immediately — crash-safe up to the last line).
    clock:
        Monotonic second counter; ``time.perf_counter`` by default
        (injectable for deterministic tests).
    sample:
        Fraction of spans to *record* (in memory + JSONL), per name.
        Deterministic: with ``sample=0.01`` the 1st, 101st, 201st, ...
        occurrence of each name is kept, so every span name appears at
        least once.  :class:`SpanStats` rollups see every span regardless.
    max_spans:
        Hard cap on the in-memory span list (the JSONL stream keeps
        flowing past it); ``dropped_spans`` counts what the cap shed.
    """

    enabled = True

    def __init__(self, jsonl_path: Optional[str] = None,
                 clock: Callable[[], float] = time.perf_counter,
                 sample: float = 1.0,
                 max_spans: Optional[int] = None):
        if not 0.0 < sample <= 1.0:
            raise ValueError(f"sample must be in (0, 1], got {sample}")
        self._clock = clock
        self._epoch = clock()
        self._depth = 0
        self.spans: list[SpanRecord] = []
        self.stats: dict[str, SpanStats] = {}
        self.sample = float(sample)
        self._period = max(1, round(1.0 / self.sample))
        self.max_spans = max_spans
        self.dropped_spans = 0
        self._jsonl: Optional[TextIO] = None
        self.jsonl_path = jsonl_path
        if jsonl_path is not None:
            os.makedirs(os.path.dirname(os.path.abspath(jsonl_path)), exist_ok=True)
            self._jsonl = open(jsonl_path, "w")

    # ------------------------------------------------------------------
    def span(self, name: str, **attrs) -> _Span:
        return _Span(self, name, {k: _json_safe(v) for k, v in attrs.items()})

    def _enter(self) -> int:
        d = self._depth
        self._depth += 1
        return d

    def _exit(self, name: str, t0: float, dur: float, depth: int, attrs: dict) -> None:
        self._depth = depth
        st = self.stats.get(name)
        if st is None:
            st = self.stats[name] = SpanStats()
        st.observe(dur)
        if (st.count - 1) % self._period != 0:  # not this name's sample turn
            return
        rec = SpanRecord(name=name, start_s=t0 - self._epoch, dur_s=dur,
                         depth=depth, attrs=attrs)
        if self.max_spans is None or len(self.spans) < self.max_spans:
            self.spans.append(rec)
        else:
            self.dropped_spans += 1
        if self._jsonl is not None:
            self._jsonl.write(json.dumps(rec.jsonl_row()) + "\n")
            self._jsonl.flush()

    # ------------------------------------------------------------------
    def chrome_trace(self) -> dict:
        """The Perfetto-loadable trace dict (one "X" event per span).

        Spans are recorded at *exit* (children before parents in
        ``self.spans``); Chrome trace viewers reconstruct nesting from the
        ts/dur intervals on a (pid, tid) track, so emission order is
        irrelevant.
        """
        pid = os.getpid()
        return {
            "traceEvents": [s.chrome_event(pid, 0) for s in self.spans],
            "displayTimeUnit": "ms",
        }

    def export_chrome(self, path: str) -> str:
        os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
        with open(path, "w") as f:
            json.dump(self.chrome_trace(), f)
        return path

    def rollup(self) -> dict:
        """Per-name duration rollups over **every** span (sampling-proof)."""
        return {name: st.snapshot() for name, st in sorted(self.stats.items())}

    def export_rollup(self, path: str) -> str:
        os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
        with open(path, "w") as f:
            json.dump({"sample": self.sample,
                       "dropped_spans": self.dropped_spans,
                       "spans": self.rollup()}, f, indent=1, sort_keys=True)
        return path

    def close(self) -> None:
        if self._jsonl is not None:
            self._jsonl.close()
            self._jsonl = None

    def __enter__(self) -> "Tracer":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


def read_spans(path: str) -> list[dict]:
    """Parse a span-JSONL stream back to row dicts.

    A truncated final line (crash mid-write) is silently dropped — every
    complete line was flushed before the next span started, so the prefix
    is always valid.
    """
    rows: list[dict] = []
    with open(path) as f:
        lines = f.readlines()
    for i, line in enumerate(lines):
        line = line.strip()
        if not line:
            continue
        try:
            rows.append(json.loads(line))
        except json.JSONDecodeError:
            if i == len(lines) - 1:
                break
            raise
    return rows
