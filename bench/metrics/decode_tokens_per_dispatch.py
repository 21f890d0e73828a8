"""Scheduler: output tokens per megatick dispatch, pure and mixed, from
the window's deltas of the engine's counters (``GET /v1/metrics``)."""


def read(run):
    parts = [run.counter(n) for n in ("decode_tokens", "mixed_decode_tokens",
                                      "decode_dispatches", "mixed_dispatches")]
    if any(p is None for p in parts) or not (parts[2] + parts[3]):
        return None
    return (parts[0] + parts[1]) / (parts[2] + parts[3])
