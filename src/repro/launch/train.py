"""Training driver: real, runnable end-to-end (CPU-scale configs), with the
full production feature set — mesh + named shardings, microbatched grad
accumulation, remat, checkpoint/restart (atomic, resumable), async saves,
and deterministic restart-safe data.

    PYTHONPATH=src python -m repro.launch.train --arch qwen2-7b --reduced \
        --steps 50 --mesh 1x1 --ckpt-dir /tmp/ckpt

On a real TPU pod the same driver runs with --mesh 16x16; nothing in the
loop is CPU-specific. Straggler/fault posture: the step is synchronous SPMD
(stragglers surface as step-time tail, mitigated by the checkpoint/restart
path and the elastic re-mesh in repro.distributed.elastic); node failure =>
restart from latest complete checkpoint on the surviving divisor mesh.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ..configs.base import reduced
from ..configs.registry_configs import ALL_ARCHS
from ..data.pipeline import make_pipeline
from ..distributed import checkpoint as ckpt
from ..models.registry import get_adapter
from ..train.train_step import TrainState, make_train_step, train_state_init
from .compile_cache import setup_compile_cache
from .mesh import make_mesh
from ..compat import set_mesh, tree_map


def build(arch: str, use_reduced: bool, mesh_shape: tuple, seq_len: int,
          global_batch: int, microbatches: int, lr: float):
    cfg = ALL_ARCHS[arch]
    if use_reduced:
        cfg = reduced(cfg)
    adapter = get_adapter(cfg)
    tp = mesh_shape[-1]
    mesh = make_mesh(mesh_shape, ("data", "model")[-len(mesh_shape):]
                     if len(mesh_shape) == 2 else ("data",))

    def loss_fn(params, batch):
        return adapter.loss(params, batch, remat=True)

    step = make_train_step(loss_fn, microbatches=microbatches, lr=lr)
    return cfg, adapter, mesh, step, tp


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-7b", choices=sorted(ALL_ARCHS))
    ap.add_argument("--reduced", action="store_true",
                    help="tiny same-family config (CPU-runnable)")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--microbatches", type=int, default=2)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--mesh", default="1x1",
                    help="DATAxMODEL, e.g. 16x16 on a pod, 1x1 on CPU")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=10)
    ap.add_argument("--async-ckpt", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    setup_compile_cache()

    mesh_shape = tuple(int(x) for x in args.mesh.split("x"))
    cfg, adapter, mesh, step, tp = build(
        args.arch, args.reduced, mesh_shape, args.seq_len,
        args.global_batch, args.microbatches, args.lr)

    pipe = make_pipeline(cfg.vocab, args.seq_len, args.global_batch,
                         seed=args.seed)

    with set_mesh(mesh):
        params = adapter.init(jax.random.PRNGKey(args.seed), tp=tp)
        state = train_state_init(params)

        start_step = 0
        if args.ckpt_dir:
            latest = ckpt.latest_step(args.ckpt_dir)
            if latest is not None:
                state = ckpt.restore(args.ckpt_dir, latest, state)
                start_step = latest + 1
                print(f"[train] resumed from step {latest}")

        jstep = jax.jit(step, donate_argnums=(0,))
        saver = ckpt.AsyncCheckpointer() if args.async_ckpt else None

        losses = []
        t0 = time.time()
        for i in range(start_step, start_step + args.steps):
            batch = tree_map(jnp.asarray, pipe.batch_at(i))
            state, metrics = jstep(state, batch)
            loss = float(metrics["loss"])
            losses.append(loss)
            if i % 5 == 0 or i == start_step + args.steps - 1:
                print(f"[train] step {i} loss {loss:.4f} "
                      f"({(time.time()-t0):.1f}s)", flush=True)
            if args.ckpt_dir and (i + 1) % args.ckpt_every == 0:
                if saver:
                    saver.save(args.ckpt_dir, i, state)
                else:
                    ckpt.save(args.ckpt_dir, i, state)
        if saver:
            saver.close()

    if len(losses) >= 10:
        first = np.mean(losses[:3])
        last = np.mean(losses[-3:])
        print(f"[train] loss {first:.3f} -> {last:.3f} "
              f"({'improved' if last < first else 'NOT improved'})")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
