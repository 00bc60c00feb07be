"""Driver: host milliseconds per step spent in the call that dispatches the
jitted step (``benchmarks/chip/harness.py`` loop -> jit dispatch), the
mean over the measured window."""


def read(ctx):
    spans = ctx["window"]["spans"]["dispatch"]
    return 1e3 * sum(spans) / len(spans) if spans else None
