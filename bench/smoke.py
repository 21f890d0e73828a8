"""Smoke-sized stand-ins for a cell's configuration and traffic, for
running the whole harness on the CPU in tests. The model is the
program's own smoke config of the same architecture (``--smoke``); the
engine and the traffic keep the cell's shape at a few tokens."""
from __future__ import annotations

import copy

from bench import harness

# the limit on ``gap_max`` at the smoke size, set between the program's
# readings there (largest 0.0259 over a dozen seeds of the chat stand-in,
# 0.0383 over a dozen of the reasoning one) and the float8 control's
# (smallest 0.1646 and 0.2297 over the same seeds)
GAP_MAX = 0.085

SMOKE_SIZES = {"hidden_size": 128, "intermediate_size": 256,
               "num_hidden_layers": 4, "num_attention_heads": 4,
               "vocab_size": 512}


def config(name: str, **engine) -> dict:
    """``bench/configs/<name>.json`` at the smoke size."""
    conf = copy.deepcopy(harness.load_json(
        harness.BENCH / "configs" / f"{name}.json"))
    kv = max(1, min(conf["num_key_value_heads"] * 4
                    // conf["num_attention_heads"], 4))
    conf.update(SMOKE_SIZES, num_key_value_heads=kv, smoke=True,
                checks={**conf["checks"], "gap_max": GAP_MAX})
    conf["engine"] = {**conf["engine"], "batch": 4, "max_len": 64,
                      "kv_blocks": None, **engine}
    return conf


def traffic(name: str) -> dict:
    """``bench/traffic/<name>.json`` with a few tokens a request."""
    t = copy.deepcopy(harness.traffic(name))
    p = t["params"]
    p["prompt"] = {"median": 20, "sigma": 0.5, "min": 17, "max": 40}
    p["output"] = {"median": 10, "sigma": 0.5, "min": 6, "max": 16}
    if "rate" in p:
        p["rate"] = 4.0
    if "requests_per_client" in p:
        p["requests_per_client"] = 64
    return t


def bench_with(config_name: str, traffic_name: str, chips: int) -> tuple:
    """``BENCHMARK.json`` with a cell of this configuration and traffic
    in it (added where it is not there), and the cell's name."""
    bench = copy.deepcopy(harness.benchmark())
    name = f"{config_name}.{traffic_name}"
    if all(c["name"] != name for c in bench["workloads"]):
        bench["workloads"].append({"name": name, "config": config_name,
                                   "traffic": traffic_name,
                                   "chips": chips})
    if all(c["name"] != config_name for c in bench["configs"]):
        bench["configs"].append({
            "name": config_name,
            "file": f"bench/configs/{config_name}.json"})
    return bench, name
