"""Megatick programs: share of the slot-steps the dispatched scans were
compiled for (scan length x batch) in which a slot did work, from the
window's deltas of the engine's ``scan_slot_steps`` and
``scan_slot_capacity`` counters (``GET /v1/metrics``)."""


def read(run):
    steps = run.counter("scan_slot_steps")
    capacity = run.counter("scan_slot_capacity")
    if steps is None or not capacity:
        return None
    return 100.0 * steps / capacity
