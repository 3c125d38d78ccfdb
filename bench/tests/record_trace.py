"""Record the small profiler trace that ``test_profile.py`` reduces:
``python3 bench/tests/record_trace.py <out.json>`` on a TPU host.

It traces a few device operations between host layer annotations, as a
traced benchmark run does, and keeps the events of the device planes and
of the harness's own annotations.
"""
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from harness import profile  # noqa: E402


def main(out: str) -> None:
    import jax
    import jax.numpy as jnp
    d = Path(out).with_suffix(".trace")
    jax.profiler.start_trace(str(d), profiler_options=profile.options())
    with jax.profiler.TraceAnnotation(profile.SPAN):
        with jax.profiler.TraceAnnotation("recorder + batcher"):
            x = jnp.zeros((512, 16, 2, 64), jnp.bfloat16)
            x.block_until_ready()
            time.sleep(0.02)
        with jax.profiler.TraceAnnotation("cycle engine"):
            y = (x.astype(jnp.float32) + 1.0).sum()
            y.block_until_ready()
            time.sleep(0.05)
    jax.profiler.stop_trace()
    keep = [e for e in profile.events_of(str(d))
            if profile.is_device(e[0])
            or e[2] in (profile.SPAN, "recorder + batcher", "cycle engine")]
    Path(out).write_text(json.dumps(keep, indent=0) + "\n")


if __name__ == "__main__":
    main(sys.argv[1])
