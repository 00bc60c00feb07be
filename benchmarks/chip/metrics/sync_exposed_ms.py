"""Gradient sync: the part of the collective time per step in which no
other op ran on the device (milliseconds, averaged over the chips)."""


def read(ctx):
    trace = ctx["trace"]
    if trace is None or not trace["has_collectives"]:
        return None
    return 1e3 * trace["exposed_s_per_step"]
