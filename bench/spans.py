"""The benchmark's span tracer, handed to ``Federation(tracer=...)``.

It records each of the program's spans (``round``, ``select``, ``train``,
``aggregate``, ``eval``) on the host clock and opens a
``jax.profiler.TraceAnnotation`` named ``fed.<span>`` for it, so that the
profiler's trace holds the same spans on the device's clock and idle gaps
can be labelled by what the host was doing.
"""
from __future__ import annotations

import dataclasses
import time

import jax

PREFIX = "fed."


@dataclasses.dataclass
class Span:
    name: str
    start_s: float
    end_s: float
    depth: int
    attrs: dict

    @property
    def dur_s(self) -> float:
        return self.end_s - self.start_s


class _Live:
    __slots__ = ("_tracer", "name", "attrs", "_ann", "_t0", "_depth")

    def __init__(self, tracer: "SpanTracer", name: str, attrs: dict):
        self._tracer, self.name, self.attrs = tracer, name, attrs

    def __enter__(self) -> "_Live":
        self._ann = jax.profiler.TraceAnnotation(PREFIX + self.name)
        self._ann.__enter__()
        self._depth = self._tracer._depth
        self._tracer._depth += 1
        self._t0 = time.perf_counter()
        return self

    def set(self, **attrs) -> None:
        self.attrs.update(attrs)

    def __exit__(self, *exc) -> None:
        t1 = time.perf_counter()
        self._ann.__exit__(*exc)
        self._tracer._depth = self._depth
        self._tracer.spans.append(Span(self.name, self._t0, t1, self._depth, self.attrs))


class SpanTracer:
    """Duck-typed ``repro.obs.Tracer``: ``span(name, **attrs)`` as a
    context manager with ``set``.  Spans are kept in memory, in the order
    they close."""

    enabled = True

    def __init__(self):
        self.spans: list[Span] = []
        self._depth = 0

    def span(self, name: str, **attrs) -> _Live:
        return _Live(self, name, dict(attrs))


def rounds_in(spans: list[Span], start_s: float, end_s: float) -> list[tuple[Span, list[Span]]]:
    """Each ``round`` span wholly inside [start_s, end_s], with the spans
    nested in it."""
    rounds = [s for s in spans if s.name == "round" and start_s <= s.start_s and s.end_s <= end_s]
    out = []
    for r in rounds:
        inner = [s for s in spans if s is not r and r.start_s <= s.start_s and s.end_s <= r.end_s]
        out.append((r, inner))
    return out
