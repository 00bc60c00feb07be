"""Driver / data: host milliseconds per step spent waiting for the next
device batch from the benchmark's prefetch thread, the mean over the
measured window."""


def read(ctx):
    spans = ctx["window"]["spans"]["batch_wait"]
    return 1e3 * sum(spans) / len(spans) if spans else None
