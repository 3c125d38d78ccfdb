"""Host self time of analytic pricing per analytically priced step:
step dispatch, queue-window features, the ``StepPricer`` signature cache
and the price itself (``core/queue_model.py``, ``SystemSim`` run and
pricing methods)."""

LAYER = "analytic pricing"
MOVES = "sim_steps_per_s"
FUNCTIONS = (
    "repro.core.system_sim.SystemSim.run",
    "repro.core.system_sim.SystemSim.run_steps",
    "repro.core.system_sim.SystemSim._features_many",
    "repro.core.system_sim.SystemSim._pressure",
    "repro.core.system_sim.SystemSim._analytic_result",
    "repro.core.queue_model.StepPricer.features_many",
    "repro.core.queue_model.StepPricer.signature",
    "repro.core.queue_model._features_batch",
)


def read(ctx):
    t = ctx.self_s.get(LAYER)
    if t is None or not ctx.analytic_steps:
        return None
    return 1e3 * t / ctx.analytic_steps
