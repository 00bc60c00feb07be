"""Gradient sync: device milliseconds per step in which a collective op
(all-reduce, reduce-scatter, all-gather, collective-permute, all-to-all)
was in flight, from the trace by HLO opcode, averaged over the chips."""


def read(ctx):
    trace = ctx["trace"]
    if trace is None or not trace["has_collectives"]:
        return None
    return 1e3 * trace["collective_s_per_step"]
