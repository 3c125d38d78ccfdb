"""Host self time of the extent census per simulated step: the stripe
census of analytically priced steps (``core/address_map.py``), the
columnar view it reads, and the per-transaction decomposition of
cycle-priced steps (``SystemSim.decompose``)."""

LAYER = "extent census"
MOVES = "sim_steps_per_s"
FUNCTIONS = (
    "repro.core.address_map.extent_census",
    "repro.core.system_sim.SystemSim.decompose",
    "repro.workloads.stream.ExtentStream.arrays",
)


def read(ctx):
    t = ctx.self_s.get(LAYER)
    if t is None or not ctx.steps:
        return None
    return 1e3 * t / ctx.steps
