"""Model: device milliseconds a step of the ops under the program's
``backward`` scope (the transposed forward, its remat recompute
included), from the traced window, averaged over the chips."""


def read(ctx):
    phases = ctx["phases"]
    if phases is None or "backward" not in phases:
        return None
    return 1e3 * phases["backward"]
