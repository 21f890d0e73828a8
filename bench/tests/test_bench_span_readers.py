"""The readers of the served path's span and counter metrics, on
synthetic window deltas: scan fill and host time per tick. Each reads
nothing where its denominator did not move, or where the program has no
such counter."""
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness  # noqa: E402
from bench.measure import Run  # noqa: E402

BENCH = harness.benchmark()
NAMES = ["scan_fill.chat", "tick_host_ms.chat"]


def _run(c0: dict, c1: dict) -> Run:
    return Run(records=[], t0=0.0, t1=50.0, mode="closed", setup_s=1.0,
               shape=None, chips=1, peak_flops=1.0, prompts=[],
               counters0=c0, counters1=c1)


C0 = {"scan_slot_steps": 1000, "scan_slot_capacity": 2000,
      "engine_tick_s": 30.0, "engine_readback_s": 25.0, "dispatches": 60}
C1 = {"scan_slot_steps": 1900, "scan_slot_capacity": 3280,
      "engine_tick_s": 80.0, "engine_readback_s": 74.5, "dispatches": 160}


@pytest.mark.parametrize("name,want", [
    ("scan_fill.chat", 100.0 * 900 / 1280),
    ("tick_host_ms.chat", 1000.0 * (50.0 - 49.5) / 100),
])
def test_reader_value_from_window_deltas(name, want):
    got = harness.metric_reader(name).read(_run(C0, C1))
    assert got == pytest.approx(want)


@pytest.mark.parametrize("name,denominator", [
    ("scan_fill.chat", "scan_slot_capacity"),
    ("tick_host_ms.chat", "dispatches"),
])
def test_reader_is_none_where_the_denominator_did_not_move(name,
                                                           denominator):
    c1 = {**C1, denominator: C0[denominator]}
    assert harness.metric_reader(name).read(_run(C0, c1)) is None


@pytest.mark.parametrize("name", NAMES)
def test_reader_is_none_without_the_counters(name):
    """A program without the spans and counters (an older one) has none
    of these keys in ``GET /v1/metrics``: the reader finds nothing."""
    old = {"dispatches": 60, "mixed_dispatches": 50}
    new = {"dispatches": 160, "mixed_dispatches": 140}
    assert harness.metric_reader(name).read(_run(old, new)) is None


@pytest.mark.parametrize("name", NAMES)
def test_metric_is_declared_for_the_chat_cell(name):
    m = next(m for m in BENCH["per_layer"] if m["name"] == name)
    assert m["source"] == "program_counter"
    assert m["workloads"] == ["phi3-mini-3.8b.chat"]
    assert m["moves"] == "output_tokens_per_s"
    assert harness.cell_metrics(BENCH, "phi3-mini-3.8b.chat", True).count(m)
