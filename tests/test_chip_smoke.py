"""Rehearse ``chip_smoke.py`` on the CPU at the smoke size.

The phases run as on the chip, through the same entry points, with the
server's ``--smoke`` config in place of phi3-mini's full widths. The
four-chip phase runs in a child process with four fake CPU devices.
``main()`` itself must refuse a machine without a TPU.
"""
import json
import os
import shutil
import subprocess
import sys

import jax

ROOT = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")
sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402

SMOKE_ARGV = list(chip_smoke.SERVER_ARGV) + ["--smoke"]


def test_serve_phase_smoke(capsys):
    outs = chip_smoke.serve_phase(SMOKE_ARGV, jax.devices()[:1])
    assert len(outs) == chip_smoke.N_REQUESTS
    assert {c.finish_reason for c in outs} == {"length"}
    printed = capsys.readouterr().out
    assert "solo and batched ids identical" in printed
    assert "layers 4, d_model 128" in printed


def test_tp_phase_smoke_four_fake_devices():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    code = ("import sys, jax; sys.path.insert(0, '.'); import chip_smoke; "
            f"chip_smoke.tp_phase({SMOKE_ARGV!r}, jax.devices()[:4]); "
            "print('TP_OK')")
    proc = subprocess.run([sys.executable, "-c", code], env=env, cwd=ROOT,
                          capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0 and "TP_OK" in proc.stdout, \
        f"stdout: {proc.stdout[-3000:]}\nstderr: {proc.stderr[-3000:]}"
    for mode in ("auto", "ring"):
        assert f"TP=4 {mode}: 4 requests finished 'length'" in proc.stdout


def test_main_refuses_cpu(capsys):
    prev = jax.config.jax_compilation_cache_dir
    try:
        rc = chip_smoke.main([])
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)
    out = capsys.readouterr().out
    assert rc != 0
    assert '"ok": true' not in out


def test_script_alone_fails(tmp_path):
    """In a directory holding chip_smoke.py and nothing else of the
    repo, the script fails and prints no result."""
    shutil.copy(os.path.join(ROOT, "chip_smoke.py"), tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                          env=env, capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode != 0
    lines = proc.stdout.strip().splitlines()
    assert not lines or not lines[-1].startswith("{") \
        or json.loads(lines[-1]).get("ok") is not True
