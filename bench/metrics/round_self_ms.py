"""Host self time of the fleet loop per fleet round: ``ClusterSim``'s
round loop, replica step bookkeeping and the router
(``serve/cluster/``)."""

LAYER = "router / fleet"
MOVES = "sim_steps_per_s"
FUNCTIONS = (
    "repro.serve.cluster.sim.ClusterSim.run",
    "repro.serve.cluster.sim.Replica.begin_step",
    "repro.serve.cluster.sim.Replica.finish_step",
    "repro.serve.cluster.router.RoundRobinRouter.place",
    "repro.serve.cluster.router.LeastKVRouter.place",
    "repro.serve.cluster.router.SessionAffinityRouter.place",
    "repro.serve.cluster.router.SLOAwareRouter.place",
)


def read(ctx):
    t = ctx.self_s.get(LAYER)
    if t is None or not ctx.rounds:
        return None
    return 1e3 * t / ctx.rounds
