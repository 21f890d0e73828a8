"""Scheduler, engine host: host time of a tick outside its one wait for
the device (``engine.tick`` span seconds less ``engine.readback``), per
dispatch, from the window's deltas of the engine's counters
(``GET /v1/metrics``)."""


def read(run):
    tick = run.counter("engine_tick_s")
    readback = run.counter("engine_readback_s")
    dispatches = run.counter("dispatches")
    if tick is None or readback is None or not dispatches:
        return None
    return 1000.0 * (tick - readback) / dispatches
