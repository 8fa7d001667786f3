"""Host packing (stage) time per chunk over the window's campaigns, from
the campaign pipeline's own span (``last_stats["stage_s"]``)."""


def read(ctx):
    p = ctx.get("pipeline")
    if not p or not p["n_chunks"]:
        return None
    return p["stage_s"] / p["n_chunks"] * 1e3
