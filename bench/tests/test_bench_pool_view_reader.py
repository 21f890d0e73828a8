"""The reader of the whole-pool attention share, on synthetic window
deltas: it reads nothing where no dispatch ran, or where the program has
no such counter (an older program)."""
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness  # noqa: E402
from bench.measure import Run  # noqa: E402

NAME = "attn_pool_view_share.chat"
C0 = {"attn_pool_view_dispatches": 40, "dispatches": 60}


def _read(c1: dict):
    run = Run(records=[], t0=0.0, t1=50.0, mode="closed", setup_s=1.0,
              shape=None, chips=1, peak_flops=1.0, prompts=[],
              counters0={k: v for k, v in C0.items() if k in c1},
              counters1=c1)
    return harness.metric_reader(NAME).read(run)


@pytest.mark.parametrize("c1,want", [
    ({"attn_pool_view_dispatches": 118, "dispatches": 140}, 97.5),
    ({"attn_pool_view_dispatches": 40, "dispatches": 140}, 0.0),
    ({"attn_pool_view_dispatches": 40, "dispatches": 60}, None),
    ({"dispatches": 140}, None),
])
def test_reader_from_window_deltas(c1, want):
    got = _read(c1)
    assert got == (None if want is None else pytest.approx(want))


def test_metric_is_declared_for_the_chat_cell():
    bench = harness.benchmark()
    m = next(m for m in bench["per_layer"] if m["name"] == NAME)
    assert m["source"] == "program_counter"
    assert m["layer"] == "megatick programs"
    assert m["workloads"] == ["phi3-mini-3.8b.chat"]
    assert m["moves"] == "output_tokens_per_s"
    assert m in harness.cell_metrics(bench, "phi3-mini-3.8b.chat", True)
