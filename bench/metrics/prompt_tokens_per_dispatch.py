"""Scheduler: prompt tokens carried per mixed megatick dispatch, from
the window's deltas of the engine's ``mixed_prompt_tokens`` and
``mixed_dispatches`` counters (``GET /v1/metrics``)."""


def read(run):
    tokens = run.counter("mixed_prompt_tokens")
    dispatches = run.counter("mixed_dispatches")
    if not dispatches:
        return None
    return tokens / dispatches
