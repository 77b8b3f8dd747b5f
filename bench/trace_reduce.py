"""Profiler trace (``.xplane.pb``) to the numbers the per-layer metrics read.

A trace holds one plane per device (``/device:TPU:<n>``), whose ``XLA Ops``
line has an event per operation that ran, and the host plane, where the
benchmark's ``TraceAnnotation``s appear as events named ``fed.<span>``.
The window is the ``fed.window`` annotation: from the end of the warm-up
to the moment the last traced round's results were on the host.

From these:

- busy: the union of the operation intervals of each device inside the
  window, and the idle share, 1 - busy / window;
- kernel time: the summed device time of the Mosaic kernels' custom-call
  operations, which XLA names after the ``repro.kernels/<kernel>`` scope
  (the program's ``jax.named_scope``) they run under, and their count;
- device time by program (``XLA Modules``) and by operation;
- idle gaps: each stretch of the window in which the device ran nothing,
  labelled by the innermost ``fed.*`` span open on the host at its middle.
"""
from __future__ import annotations

import collections
import dataclasses
import re
from typing import Optional

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "fed."
WINDOW = "fed.window"
KERNEL_CALL = 'custom_call_target="tpu_custom_call"'
# "%masked_agg.1 = f32[...] custom-call(...)" -> "masked_agg"
OP_BASE = re.compile(r"^%?([A-Za-z0-9_\-]+?)(?:\.\d+)?\s*=")


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


@dataclasses.dataclass
class Trace:
    devices: dict[str, list[Event]]   # plane name -> its operation events
    modules: dict[str, list[Event]]   # plane name -> its program runs
    spans: list[Event]                # host fed.* annotations


def load(path: str) -> Trace:
    """Read the device operations and the host's ``fed.*`` spans."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices: dict[str, list[Event]] = {}
    modules: dict[str, list[Event]] = {}
    spans: list[Event] = []
    for plane in data.planes:
        on_device = bool(DEVICE_PLANE.match(plane.name))
        for line in plane.lines:
            if on_device and line.name in (OPS_LINE, MODULES_LINE):
                into = devices if line.name == OPS_LINE else modules
                into.setdefault(plane.name, []).extend(
                    Event(e.name, float(e.start_ns), float(e.duration_ns)) for e in line.events)
            elif not on_device:
                spans.extend(Event(e.name, float(e.start_ns), float(e.duration_ns))
                             for e in line.events if e.name.startswith(SPAN_PREFIX))
    return Trace(devices, modules, spans)


def merge(intervals) -> list[tuple[float, float]]:
    """Union of (start, end) intervals, as sorted disjoint intervals."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def op_base(name: str) -> str:
    """An operation's HLO name without its number: the kernel's name for a
    Mosaic custom call."""
    m = OP_BASE.match(name)
    return m.group(1) if m else name


def module_base(name: str) -> str:
    """``jit_run(5240...)`` -> ``jit_run``."""
    return name.split("(", 1)[0]


@dataclasses.dataclass
class Summary:
    window_ns: float
    busy_ns: dict[str, float]                  # per device plane
    op_ns: dict[str, float]                    # device time by operation, all devices
    module_ns: dict[str, float]                # device time by program, all devices
    kernel_ns: dict[str, float]                # device time by Mosaic kernel
    kernel_runs: dict[str, int]                # calls per Mosaic kernel
    idle_ns_by_span: dict[str, float]          # idle time of device 0 by host span
    gaps: list[tuple[str, float]]              # longest idle gaps of device 0

    @property
    def busy_s(self) -> float:
        return sum(self.busy_ns.values()) / len(self.busy_ns) / 1e9 if self.busy_ns else 0.0

    @property
    def window_s(self) -> float:
        return self.window_ns / 1e9


def summarize(trace: Trace, window: Optional[tuple[float, float]] = None) -> Optional[Summary]:
    """Reduce a trace to its summary over the window; None where the trace
    has no window or no device operation in it."""
    if window is None:
        marks = [s for s in trace.spans if s.name == WINDOW]
        if not marks:
            return None
        window = (marks[0].start_ns, marks[0].end_ns)
    lo, hi = window
    busy: dict[str, float] = {}
    op_ns, module_ns = collections.Counter(), collections.Counter()
    kernel_ns, kernel_runs = collections.Counter(), collections.Counter()

    def overlap(e: Event) -> float:
        return min(e.end_ns, hi) - max(e.start_ns, lo)

    for plane, events in sorted(trace.devices.items()):
        inside = [e for e in events if e.end_ns > lo and e.start_ns < hi]
        busy[plane] = sum(e - s for s, e in merge(clip([(e.start_ns, e.end_ns) for e in inside], lo, hi)))
        for e in inside:
            op_ns[e.name.split(" = ", 1)[0].lstrip("%")] += overlap(e)
            if KERNEL_CALL in e.name:
                kernel_ns[op_base(e.name)] += overlap(e)
                kernel_runs[op_base(e.name)] += 1
    for events in trace.modules.values():
        for e in events:
            if e.end_ns > lo and e.start_ns < hi:
                module_ns[module_base(e.name)] += overlap(e)
    if not busy or not any(busy.values()):
        return None
    first = sorted(trace.devices)[0]
    merged = merge(clip([(e.start_ns, e.end_ns) for e in trace.devices[first]], lo, hi))
    edges = [lo] + [x for iv in merged for x in iv] + [hi]
    host = [s for s in trace.spans if s.name != WINDOW]
    idle_by_span: collections.Counter = collections.Counter()
    gaps = []
    for s, e in zip(edges[::2], edges[1::2]):
        if e <= s:
            continue
        mid = 0.5 * (s + e)
        open_ = [h for h in host if h.start_ns <= mid <= h.end_ns]
        label = min(open_, key=lambda h: h.dur_ns).name[len(SPAN_PREFIX):] if open_ else "outside spans"
        idle_by_span[label] += e - s
        gaps.append((label, e - s))
    gaps.sort(key=lambda g: -g[1])
    return Summary(hi - lo, busy, dict(op_ns), dict(module_ns), dict(kernel_ns),
                   dict(kernel_runs), dict(idle_by_span), gaps[:10])
