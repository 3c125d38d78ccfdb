"""Host self time of the recorder and batcher per simulated step: the
continuous batcher's scheduling, the KV cache's page accounting and the
step's extent stream (``serve/replay/recorder.py``, ``serve/batching.py``,
``serve/kv_cache.py``)."""

LAYER = "recorder + batcher"
MOVES = "sim_steps_per_s"
FUNCTIONS = (
    "repro.serve.replay.recorder.ServeTraceRecorder.step",
    "repro.serve.replay.recorder.ServeTraceRecorder.submit_due",
)


def read(ctx):
    t = ctx.self_s.get(LAYER)
    if t is None or not ctx.steps:
        return None
    return 1e3 * t / ctx.steps
