"""Gradient sync: device milliseconds a step of the ops under
``sync/Pack`` and ``sync/Unpack`` (the gradient packed into one buffer
per wire dtype and read back out), from the traced window, averaged over
the chips.  The two are added, which is their union where the gradient
is synced as whole packed buffers (every packed mode but the bucketed
``hier_overlap``): a step's unpack reads what the sync made of the whole
packed buffer, and the next step's pack waits on the parameters that
gradient updated, so their ops never run at once."""

SCOPES = ("sync/Pack", "sync/Unpack")


def read(ctx):
    phases = ctx["phases"]
    if phases is None or not any(s in phases for s in SCOPES):
        return None
    return 1e3 * sum(phases.get(s, 0.0) for s in SCOPES)
