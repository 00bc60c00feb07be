"""Optimizer: device milliseconds a step of the ops under the program's
``optimizer`` scope (global norm, clip, the Adam update, the finite
gate), from the traced window, averaged over the chips."""


def read(ctx):
    phases = ctx["phases"]
    if phases is None or "optimizer" not in phases:
        return None
    return 1e3 * phases["optimizer"]
