"""Closed loop: a fixed number of clients, each sending its next
request when its last one has finished.

Parameters: ``clients`` (a number, or ``"batch"`` for as many as the
engine has slots), ``requests_per_client``, ``layout_seed``, and
``prompt``/``output`` lognormals as in ``poisson_open``. The k-th
requests of all clients are one stratified draw. In a closed loop the
order of the sizes is part of the work: it decides which requests share
a dispatch and which finish inside the window. So the sizes, and which
client sends each in which round, come from ``layout_seed`` and are the
same for every run; the run's seed draws the token ids.
"""
from __future__ import annotations

from bench.traffic import sampling


def limits(params: dict) -> dict:
    """As ``poisson_open.limits``. With more than one client a pure
    megatick is shorter than K only if every running request is within
    K tokens of its end at once, which outputs longer than the batch
    never are."""
    c = params["clients"]
    alone = c != "batch" and int(c) < 2
    return {"prompt": params["prompt"]["max"],
            "output": params["output"]["max"],
            "min_prompt": params["prompt"]["min"], "short_pure": alone}


def schedule(params: dict, seed: int, seconds: float, vocab: int,
             concurrency: int) -> dict:
    c = params["clients"]
    n_clients = concurrency if c == "batch" else int(c)
    rounds = int(params["requests_per_client"])
    layout = int(params["layout_seed"])
    p, o = params["prompt"], params["output"]
    ids = sampling.rng_for(seed, "ids")
    clients = [[] for _ in range(n_clients)]
    for k in range(rounds):
        plens = sampling.lognormal(
            n_clients, p["median"], p["sigma"], p["min"], p["max"],
            sampling.rng_for(layout, f"prompt:{k}"))
        olens = sampling.lognormal(
            n_clients, o["median"], o["sigma"], o["min"], o["max"],
            sampling.rng_for(layout, f"output:{k}"))
        for i in range(n_clients):
            clients[i].append({
                "prompt": sampling.token_ids(plens[i], vocab, ids),
                "max_new": olens[i]})
    return {"mode": "closed", "clients": clients}
