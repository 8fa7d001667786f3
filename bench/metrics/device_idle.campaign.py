"""Share of the traced window in which no operation ran on the chip,
averaged over the chips (campaign cells)."""


def read(ctx):
    tr = ctx.get("trace")
    if not tr or tr["window_s"] <= 0 or ctx.get("n_scenarios") is None:
        return None
    return (1.0 - tr["busy_s"] / tr["window_s"]) * 100.0
