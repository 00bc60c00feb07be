"""Model: device milliseconds a step of the ops under the program's
``forward`` scope (the forward pass inside the loss), from the traced
window, averaged over the chips."""


def read(ctx):
    phases = ctx["phases"]
    if phases is None or "forward" not in phases:
        return None
    return 1e3 * phases["forward"]
