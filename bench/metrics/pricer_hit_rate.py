"""Share of the window's ``StepPricer`` lookups that hit its signature
cache, from the program's own counters (``pricer_stats``)."""

LAYER = "analytic pricing"
MOVES = "sim_steps_per_s"


def read(ctx):
    a, b = ctx.pricer_open, ctx.pricer_last
    if a is None or b is None:
        return None
    hits = b["hits"] - a["hits"]
    lookups = hits + b["misses"] - a["misses"]
    if lookups <= 0:
        return None
    return hits / lookups
