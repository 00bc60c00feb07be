"""Step: share of the traced window in which no op ran on the device
(1 - union of op intervals / window), averaged over the chips."""


def read(ctx):
    trace = ctx["trace"]
    return None if trace is None else trace["idle_pct"]
