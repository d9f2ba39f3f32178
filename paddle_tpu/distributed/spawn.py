"""dist.spawn (reference: distributed/spawn.py:317).

On TPU, multi-*device* work is single-process SPMD (pjit over the mesh), so
spawn only forks processes for multi-host simulation / CPU testing.

One process per chip: each child is bound to chip ``rank`` (the
launcher's ``chip_binding_env``), and a parent that already HOLDS the
TPU — it has touched jax — is refused: its children could only fail or
hang waiting for a chip that is never released.
"""
from __future__ import annotations

import multiprocessing
import os

from .env import chip_binding_env


def _worker(func, rank, nprocs, args, env):
    os.environ.update(env)
    os.environ["PADDLE_TRAINER_ID"] = str(rank)
    os.environ["PADDLE_TRAINERS_NUM"] = str(nprocs)
    func(*args)


def spawn(func, args=(), nprocs=-1, join=True, daemon=False, **options):
    if nprocs == -1:
        nprocs = int(os.environ.get("PADDLE_TRAINERS_NUM", "1"))
    if nprocs <= 1:
        func(*args)
        return None
    from jax._src import xla_bridge

    from ..framework.errors import PreconditionNotMetError

    if "tpu" in xla_bridge._backends:
        raise PreconditionNotMetError(
            "dist.spawn: this process has already initialised jax on the "
            "TPU and holds the chip(s); a chip belongs to one process, so "
            f"{nprocs} children cannot start on it.  Call spawn before "
            "the first device use, or start the job with `python -m "
            "paddle_tpu.distributed.launch` (its parent stays off jax)")
    ctx = multiprocessing.get_context("spawn")
    procs = []
    base_port = int(options.get("started_port", 36789))
    endpoints = ",".join(f"127.0.0.1:{base_port + i}" for i in range(nprocs))
    for rank in range(nprocs):
        env = {
            "PADDLE_TRAINER_ENDPOINTS": endpoints,
            "PADDLE_CURRENT_ENDPOINT": f"127.0.0.1:{base_port + rank}",
            **chip_binding_env(rank),
        }
        p = ctx.Process(target=_worker, args=(func, rank, nprocs, args, env),
                        daemon=daemon)
        p.start()
        procs.append(p)
    if join:
        for p in procs:
            p.join()
        for p in procs:
            if p.exitcode != 0:
                raise RuntimeError(f"spawned process exited with {p.exitcode}")
    return procs
