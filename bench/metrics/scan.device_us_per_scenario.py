"""Device time of the campaign's bucket executables (``jit_impl``: the
vmapped scan of each bucket), summed over the chips, per scenario of the
traced campaign."""
from benchlib import tracing


def read(ctx):
    tr, n = ctx.get("trace"), ctx.get("n_traced")
    if not tr or not n:
        return None
    secs, runs = tracing.module_seconds(tr, r"^jit_impl$")
    if not runs:
        return None
    return secs / n * 1e6
