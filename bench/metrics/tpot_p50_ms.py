"""Median over requests of (last token time - first token
time) / (tokens - 1), on the client's clock."""


def read(run):
    from bench.measure import percentile
    xs = run.tpot_ms()
    return percentile(xs, 50) if xs else None
