"""What a run observed, and the arithmetic every metric reader shares.

A :class:`Run` holds the load generator's records, the window, the
set-up time, the program's counters at the window's edges and, in a
traced run, the trace's reduction. Metric readers
(``bench/metrics/<name>.py``) take one and return a number, or None
where they find nothing to read.
"""
from __future__ import annotations

import dataclasses
import math

from bench import flops as flops_lib


def percentile(xs, q: float) -> float:
    """Linear-interpolation percentile (numpy's default), where an
    infinite sample (a request that never got its token) is kept in
    the order and not turned into a NaN."""
    v = sorted(xs)
    if not v:
        raise ValueError("percentile of an empty sample")
    k = (len(v) - 1) * q / 100.0
    lo, hi = math.floor(k), math.ceil(k)
    if lo == hi or v[hi] == v[lo]:
        return float(v[lo])
    return float(v[lo] + (v[hi] - v[lo]) * (k - lo))


def failed(rec: dict) -> bool:
    """Refused, errored, or never answered. A closed-loop request that
    was hung up on at the window's end is none of these."""
    return rec["error"] is not None or (rec["status"] not in (None, 200))


@dataclasses.dataclass
class Run:
    records: list
    t0: float
    t1: float
    mode: str
    setup_s: float
    shape: object
    chips: int
    peak_flops: float
    prompts: list               # per record: its prompt length
    counters0: dict = dataclasses.field(default_factory=dict)
    counters1: dict = dataclasses.field(default_factory=dict)
    trace: dict | None = None

    @property
    def window_s(self) -> float:
        return self.t1 - self.t0

    def counter(self, name: str) -> float | None:
        if name not in self.counters0 or name not in self.counters1:
            return None
        return self.counters1[name] - self.counters0[name]

    # ----------------------------------------------------------- latency
    def ttft_ms(self) -> list[float]:
        """Per request due in the window: first token on the wire minus
        when it was due. Failed requests are infinite."""
        out = []
        for r in self.records:
            if failed(r) or not r["events"]:
                out.append(math.inf)
            else:
                out.append((r["events"][0][0] - (self.t0 + r["due"])) * 1e3)
        return out

    def tpot_ms(self) -> list[float]:
        """Per request: (last token time - first token time) / (tokens -
        1). In a closed loop only the tokens inside the window count,
        and a request hung up on at its end counts with the gaps it
        had. Failed requests are infinite."""
        out = []
        for r in self.records:
            if failed(r):
                out.append(math.inf)
                continue
            ev = r["events"]
            if self.mode == "closed":
                ev = [e for e in ev if self.t0 <= e[0] <= self.t1]
            n = sum(e[1] for e in ev)
            if n >= 2:
                out.append((ev[-1][0] - ev[0][0]) / (n - 1) * 1e3)
        return out

    def output_tokens_in_window(self) -> int:
        return sum(e[1] for r in self.records for e in r["events"]
                   if self.t0 <= e[0] <= self.t1)

    def lateness_s(self) -> list[float]:
        """How late the generator sent each request it sent."""
        return [r["sent"] - (self.t0 + r["due"]) for r in self.records
                if r["sent"] is not None]

    # -------------------------------------------------------------- FLOPs
    def window_flops(self) -> int:
        """Model FLOPs of the forwards whose token reached a client in
        the window: a request's prompt and the forward that chose its
        first token count when that token arrives; the forward that
        chose output j counts when output j arrives. Each token counts
        once, however often a preemption made the program redo it."""
        total = 0
        for r, plen in zip(self.records, self.prompts):
            at = plen                   # next forward's position + 1
            for k, (t, n) in enumerate(r["events"]):
                start, stop = (0, plen + n - 1) if k == 0 else (at, at + n)
                if self.t0 <= t <= self.t1:
                    total += flops_lib.span_flops(self.shape, start, stop)
                at = stop
        return total

    def mfu_percent(self) -> float | None:
        f = self.window_flops()
        if f <= 0:
            return None
        return 100.0 * f / (self.window_s * self.chips * self.peak_flops)

    # -------------------------------------------------------------- trace
    def trace_value(self, key: str):
        return None if self.trace is None else self.trace.get(key)
