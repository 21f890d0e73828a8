"""Open loop: requests arrive as a Poisson process at a fixed rate.

Parameters (the traffic file's ``params``): ``rate`` (requests/s),
``prompt`` and ``output``, each ``{"median", "sigma", "min", "max"}``
of a lognormal in tokens. The window holds ``round(rate * seconds)``
requests; each is sent when it is due, whatever has finished.
"""
from __future__ import annotations

from bench.traffic import sampling


def limits(params: dict) -> dict:
    """The longest prompt and output, and the shortest prompt, this
    traffic can send; and whether a request can run alone near its end,
    so that a megatick is shorter than K (here it can)."""
    return {"prompt": params["prompt"]["max"],
            "output": params["output"]["max"],
            "min_prompt": params["prompt"]["min"], "short_pure": True}


def schedule(params: dict, seed: int, seconds: float, vocab: int,
             concurrency: int) -> dict:
    rate = float(params["rate"])
    n = max(1, round(rate * seconds))
    p, o = params["prompt"], params["output"]
    plens = sampling.lognormal(n, p["median"], p["sigma"], p["min"],
                               p["max"], sampling.rng_for(seed, "prompt"))
    olens = sampling.lognormal(n, o["median"], o["sigma"], o["min"],
                               o["max"], sampling.rng_for(seed, "output"))
    gaps = sampling.exponential_gaps(n, rate,
                                     sampling.rng_for(seed, "arrival"))
    # every due time inside the window: the gaps are scaled to fill
    # n / (n + 1) of it, as the arrivals of n events in [0, seconds)
    scale = seconds * n / (n + 1) / sum(gaps)
    ids = sampling.rng_for(seed, "ids")
    due, reqs = 0.0, []
    for k in range(n):
        due += gaps[k] * scale
        reqs.append({"due": due,
                     "prompt": sampling.token_ids(plens[k], vocab, ids),
                     "max_new": olens[k]})
    return {"mode": "open", "requests": reqs}
