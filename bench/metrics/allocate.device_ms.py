"""Device time of the ``allocate`` executable per controller solve in the
traced window."""
from benchlib import tracing


def read(ctx):
    tr = ctx.get("trace")
    if not tr or not ctx.get("n_solves"):
        return None
    secs, runs = tracing.module_seconds(tr, r"^jit_allocate$")
    if not runs:
        return None
    return secs / runs * 1e3
