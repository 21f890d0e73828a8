"""Device: share of the traced window in which no operation ran on the
device, averaged over the chips used (profiler trace)."""


def read(run):
    idle = run.trace_value("idle_share")
    return None if idle is None else 100.0 * idle
