"""Shared latency-metric helpers and host spans for the engine, launcher
and benches.

One percentile implementation so ``p50``/``p99`` mean the same thing in
``Engine.metrics``, the serve CLI summary and the scheduler benches
(the old per-call-site ``xs[len(xs)//2]`` index-median disagreed with
itself at even lengths and could not express tails at all — and SLO
policy evaluation lives in the tail).

:class:`Spans` is the served path's one tracing helper: each span is a
``jax.profiler`` host event (nested inside the span that encloses it,
on the clock of the device planes) and adds its seconds to a counter
that ``GET /v1/metrics`` exports.
"""
from __future__ import annotations

import time

import numpy as np


def percentile(xs, q: float) -> float:
    """numpy's default linear-interpolation percentile, with an
    empty-sample guard so metric dicts stay total."""
    xs = list(xs)
    if not xs:
        return 0.0
    return float(np.percentile(xs, q))


def latency_summary(xs, prefix: str, digits: int = 4) -> dict:
    """p50/p99/max summary of a latency sample under ``prefix_``-keys."""
    return {
        f"p50_{prefix}_s": round(percentile(xs, 50), digits),
        f"p99_{prefix}_s": round(percentile(xs, 99), digits),
        f"max_{prefix}_s": round(max(xs), digits) if xs else 0.0,
    }


def request_latency(req) -> tuple[float | None, float | None]:
    """A finished request's (TTFT, TPOT) in seconds; None where it has
    none: no TTFT without an output token, no TPOT without two."""
    n = len(req.out_tokens)
    return (req.ttft_s if n else None, req.tpot_s if n > 1 else None)


def latency_metrics(pairs) -> dict:
    """TTFT and TPOT summaries over a list of (TTFT, TPOT) pairs of
    :func:`request_latency`."""
    return {**latency_summary([t for t, _ in pairs if t is not None],
                              "ttft"),
            **latency_summary([t for _, t in pairs if t is not None],
                              "tpot")}


def counter_name(span: str) -> str:
    """The seconds counter of a span: ``engine.tick`` -> ``engine_tick_s``."""
    return span.replace(".", "_") + "_s"


class Spans:
    """Named host spans of one component (the engine, the server).

    ``span(name, **args)`` is a context manager. It opens
    ``jax.profiler.TraceAnnotation(name, **args)`` — a host event that
    records its name, start, end and arguments, nested under the span
    enclosing it — and adds its elapsed ``time.monotonic()`` seconds to
    ``seconds[counter_name(name)]``. With no profiler running the
    annotation records nothing; the spans are always on.
    ``names`` are the spans whose counters exist from the start, so a
    counter snapshot has the same keys before and after the first span.
    """

    def __init__(self, *names: str):
        # imported here: the server's module stays free of JAX at import
        from jax.profiler import TraceAnnotation
        self._annotation = TraceAnnotation
        self.seconds: dict[str, float] = {counter_name(n): 0.0
                                          for n in names}

    def span(self, name: str, **args) -> "_Span":
        return _Span(self, name, args)


class _Span:
    __slots__ = ("_spans", "_key", "_trace", "_t0", "seconds")

    def __init__(self, spans: Spans, name: str, args: dict):
        self._spans = spans
        self._key = counter_name(name)
        self._trace = spans._annotation(name, **args)
        self.seconds = 0.0

    def __enter__(self) -> "_Span":
        self._trace.__enter__()
        self._t0 = time.monotonic()
        return self

    def __exit__(self, *exc):
        self.seconds = time.monotonic() - self._t0
        acc = self._spans.seconds
        acc[self._key] = acc.get(self._key, 0.0) + self.seconds
        self._trace.__exit__(*exc)
        return False

    def set_metadata(self, **args) -> None:
        """Add arguments known only inside the span (they reach the
        trace only while a profiler runs)."""
        self._trace.set_metadata(**args)
