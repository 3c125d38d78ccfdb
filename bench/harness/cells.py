"""Wire one cell into the program's own entry points.

``replay`` traffic drives :meth:`ReplayEngine.run`, built by the
program's ``build_replay``; ``fleet`` traffic drives
:meth:`ClusterSim.run`. Either way the generated request stream replaces
the program's own arrival process, so the program receives only the
benchmark's inputs.
"""
from __future__ import annotations

from contextlib import contextmanager
from functools import partial

from .traffic import Feed, draw


@contextmanager
def kv_geometry(geometry: dict):
    """Build the program's KV page pools at the configuration's geometry
    (its model module's ``pool_geometry``): the builders take the pool
    factory's defaults, so the factory is bound to these arguments while
    they run."""
    import repro.serve.cluster.sim as cluster
    import repro.serve.replay.recorder as recorder
    orig = recorder.make_kv_cache
    bound = partial(orig, **geometry)
    recorder.make_kv_cache = cluster.make_kv_cache = bound
    try:
        yield
    finally:
        recorder.make_kv_cache = cluster.make_kv_cache = orig


def build(config: dict, traffic: dict, seed: int, equations):
    """``(sim, feed, system)``: the object whose ``run()`` the window
    drives, the arrival feed handed to it, and its pricing
    ``SystemSim``. ``equations`` is the configuration's model module."""
    from repro.configs.paper_workloads import ServingMix
    from repro.serve.replay.arrivals import RequestSpec

    feed = Feed(draw(traffic, seed), RequestSpec)
    mix = ServingMix(**traffic["mix"])
    common = dict(workload=config["workload"], policy=config["policy"],
                  n_channels=int(config["n_channels"]), mix=mix,
                  length_scale=float(traffic["length_scale"]),
                  n_slots=int(traffic["n_slots"]),
                  n_ops=int(traffic["n_ops"]),
                  scale=float(traffic["step_scale"]),
                  sim_mode=traffic["sim_mode"], n_requests=1)
    engine = traffic["engine"]
    with kv_geometry(equations.pool_geometry(config)):
        sim, system = _construct(engine, config, traffic, feed, common)
    if engine == "replay" and int(config["workers"]) != 1:
        raise ValueError("ReplayEngine prices serially; workers must be 1")
    return sim, feed, system


def _construct(engine: str, config: dict, traffic: dict, feed, common):
    from repro.serve.replay import build_replay
    if engine == "replay":
        sim, _ = build_replay(warm=bool(traffic["warm"]),
                              prefill_chunk_tokens=traffic[
                                  "prefill_chunk_tokens"], **common)
        sim.recorder.arrivals = feed
        system = sim.system
    elif engine == "fleet":
        from repro.serve.cluster.sim import ClusterSim
        sim = ClusterSim(n_replicas=int(traffic["n_replicas"]),
                         router=traffic["router"],
                         attach_pricer=bool(traffic["attach_pricer"]),
                         workers=int(config["workers"]), **common)
        sim.arrivals = feed
        system = sim.system
    else:
        raise ValueError(f"traffic engine {engine!r} is not replay|fleet")
    return sim, system
