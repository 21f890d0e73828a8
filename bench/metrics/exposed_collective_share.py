"""Collectives: time in collective operations during which no other
operation ran on that device, over the traced window, averaged over
the chips used. Nothing to read where the trace holds no collective."""


def read(run):
    if not run.trace_value("has_collectives"):
        return None
    return 100.0 * run.trace_value("exposed_collective_share")
