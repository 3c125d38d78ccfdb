"""Host self time of the recurrent-state pool per simulated step: the
per-slot state read and in-place write streams of a workload with
linear-attention layers (``serve/state_pool.py``)."""

LAYER = "recurrent state"
MOVES = "sim_steps_per_s"
FUNCTIONS = (
    "repro.serve.state_pool.RecurrentStatePool.step_streams",
)


def read(ctx):
    t = ctx.self_s.get(LAYER)
    if t is None or not ctx.steps:
        return None
    return 1e3 * t / ctx.steps
