"""A whole run of the harness, on the CPU at the smoke size with the
look for a chip skipped, comes out ``correct`` when the served path is
sound and not correct when the timed path is broken underneath it: a
step that leaves its state unchanged, a token altered where it is
produced, and on four devices the exchange between chips left out; and
the float8 control, put in the program's place, is judged not correct
by the same checks."""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness, smoke  # noqa: E402

SEED = 2**31 + 101


def _run(config="phi3-mini-3.8b", traffic="chat", control=False,
         **engine):
    import jax
    bench, cell = smoke.bench_with(config, traffic, 1)
    line = harness.run_cell(cell, SEED, 3.0, False, bench=bench,
                            conf=smoke.config(config, **engine),
                            traffic_conf=smoke.traffic(traffic),
                            devices=jax.devices()[:1],
                            require_chip=False, drain_s=30,
                            control=control, log=lambda *a, **k: None)
    assert line["attempted"] > 0
    return line


@pytest.mark.parametrize("config,traffic", [("phi3-mini-3.8b", "chat"),
                                            ("glm4-9b-tp4", "reasoning")])
def test_a_sound_run_is_correct(config, traffic):
    line = _run(config, traffic, tp=1)
    assert line["correct"], line["checks"]
    assert line["failed"] == 0


def test_the_float8_control_in_the_programs_place_is_not_correct():
    line = _run(control=True)
    assert line["correct"], line["checks"]
    ctl = line["control"]
    assert ctl["correct"] is False
    assert ctl["program"]["control"]["gap_max"] \
        > line["checks"]["gap_max"]["limit"]


def test_a_step_that_leaves_the_kv_state_unchanged_is_not_correct(
        monkeypatch):
    from repro.core import flash_decode as fd
    monkeypatch.setattr(fd, "paged_write",
                        lambda pool, *a, **k: pool)
    line = _run()
    assert not line["correct"]
    assert line["checks"]["gap_max"]["value"] \
        > line["checks"]["gap_max"]["limit"]


def test_a_token_altered_where_it_is_sampled_is_not_correct(monkeypatch):
    import jax.numpy as jnp

    from repro.serving import sampler
    monkeypatch.setattr(
        sampler, "greedy",
        lambda lg: ((jnp.argmax(lg[:, -1], -1) + 1) % lg.shape[-1])[
            :, None].astype(jnp.int32))
    line = _run()
    assert not line["correct"]


_TP4 = """
import json, sys
sys.path[:0] = [{root!r}, {src!r}]
from bench import harness, smoke
if {broken}:
    from repro.core import flash_decode as fd
    fd.combine_rs_ag = lambda partial, *, axis: partial
import jax
bench, cell = smoke.bench_with("glm4-9b-tp4", "reasoning", 4)
line = harness.run_cell(cell, {seed}, 3.0, False, bench=bench,
                        conf=smoke.config("glm4-9b-tp4", tp=4),
                        traffic_conf=smoke.traffic("reasoning"),
                        devices=jax.devices()[:4], require_chip=False,
                        drain_s=30, log=lambda *a, **k: None)
print(json.dumps(line))
"""


@pytest.mark.parametrize("broken", [False, True])
def test_four_devices_need_the_exchange_between_them(broken):
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4"}
    code = _TP4.format(root=str(ROOT), src=str(ROOT / "src"), seed=SEED,
                       broken=broken)
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["device"]["count"] == 4
    assert line["correct"] is (not broken), line["checks"]
