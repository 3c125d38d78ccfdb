"""Share of the traced span in which no operation ran on the device:
1 - (union of device-op intervals / span), from the profiler trace,
reduced by ``bench/harness/profile.py``."""

LAYER = "device"
MOVES = "sim_steps_per_s"


def read(ctx):
    p = ctx.profile
    if p is None or p["n_device_planes"] == 0 or p["busy_s"] <= 0:
        return None
    return p["idle_share"]
