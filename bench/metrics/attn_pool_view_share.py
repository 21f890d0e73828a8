"""Megatick programs: share of the dispatches whose single-device paged
attention read the whole KV pool once rather than a per-slot gather,
from the window's deltas of the engine's ``attn_pool_view_dispatches``
and ``dispatches`` counters (``GET /v1/metrics``)."""


def read(run):
    whole = run.counter("attn_pool_view_dispatches")
    dispatches = run.counter("dispatches")
    if whole is None or not dispatches:
        return None
    return 100.0 * whole / dispatches
