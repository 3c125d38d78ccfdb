"""Host self time of the cycle engine per transaction it simulated in
the window's cycle-priced steps: the lockstep channel drivers
(``core/sched/``) and the cycle path of ``SystemSim``."""

LAYER = "cycle engine"
MOVES = "sim_steps_per_s"
FUNCTIONS = (
    "repro.core.system_sim.SystemSim._run_cycle",
    "repro.core.sched.vectorized.run_channels",
)


def read(ctx):
    t = ctx.self_s.get(LAYER)
    if t is None or not ctx.cycle_txns:
        return None
    return 1e9 * t / ctx.cycle_txns
