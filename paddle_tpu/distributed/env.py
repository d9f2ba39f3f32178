"""Process / cluster environment.

Reference analog: the PADDLE_TRAINER_ID / PADDLE_TRAINER_ENDPOINTS env
contract (fleet/launch_utils.py:57) + NCCL TCP bootstrap
(gen_comm_id_helper.cc:286).  TPU-native: jax.distributed.initialize is the
coordination service (coordinator address ↔ the reference's root endpoint);
within a process, devices are chips; ranks are processes × local devices.
"""
from __future__ import annotations

import os
from typing import List, Optional

import jax

_initialized = False


def init_parallel_env(strategy=None):
    """reference: paddle.distributed.init_parallel_env (parallel.py:57)."""
    global _initialized
    if _initialized:
        return
    trainers = int(os.environ.get("PADDLE_TRAINERS_NUM", "1"))
    trainer_id = int(os.environ.get("PADDLE_TRAINER_ID", "0"))
    endpoints = os.environ.get("PADDLE_TRAINER_ENDPOINTS", "")
    backend = os.environ.get("PADDLE_DIST_BACKEND", "auto")
    if trainers > 1 and backend == "gloo" \
            and not os.environ.get("PADDLE_GLOO_ENDPOINT"):
        raise ValueError(
            "PADDLE_DIST_BACKEND=gloo requires PADDLE_GLOO_ENDPOINT "
            "(host:port of the rank-0 rendezvous)")
    if trainers > 1 and os.environ.get("PADDLE_GLOO_ENDPOINT"):
        # host-side eager collectives (GlooWrapper analog) — always useful
        # alongside the compiled path, required for backend="gloo"
        from . import gloo

        gloo.init_gloo(rank=trainer_id, world_size=trainers)
    if trainers > 1 and endpoints and backend != "gloo":
        coordinator = endpoints.split(",")[0]
        jax.distributed.initialize(
            coordinator_address=coordinator,
            num_processes=trainers,
            process_id=trainer_id,
        )
    _initialized = True


def get_rank() -> int:
    if os.environ.get("PADDLE_TRAINER_ID") is not None:
        return int(os.environ["PADDLE_TRAINER_ID"])
    try:
        return jax.process_index()
    except Exception:
        return 0


def get_world_size() -> int:
    if os.environ.get("PADDLE_TRAINERS_NUM") is not None:
        return int(os.environ["PADDLE_TRAINERS_NUM"])
    try:
        return jax.process_count()
    except Exception:
        return 1


def chip_binding_env(local_rank: int) -> dict:
    """Environment that binds a child process to ONE chip of the host:
    ``FLAGS_selected_tpus`` (what ``ParallelEnv.device_id`` reads) plus
    the libtpu variables that make the selection real — the child's jax
    sees chip ``local_rank`` as its single device, so siblings never
    contend for a chip.  Inert on a host without a TPU."""
    return {
        "FLAGS_selected_tpus": str(local_rank),
        "TPU_VISIBLE_CHIPS": str(local_rank),
        "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
        "TPU_PROCESS_BOUNDS": "1,1,1",
        "ALLOW_MULTIPLE_LIBTPU_LOAD": "1",
    }


def device_count() -> int:
    return len(jax.devices())


class ParallelEnv:
    """reference: fluid/dygraph/parallel.py ParallelEnv."""

    @property
    def rank(self):
        return get_rank()

    @property
    def world_size(self):
        return get_world_size()

    @property
    def device_id(self):
        return int(os.environ.get("FLAGS_selected_tpus", "0").split(",")[0])

    @property
    def current_endpoint(self):
        return os.environ.get("PADDLE_CURRENT_ENDPOINT", "")

    @property
    def trainer_endpoints(self):
        eps = os.environ.get("PADDLE_TRAINER_ENDPOINTS", "")
        return eps.split(",") if eps else []

    local_rank = rank
    nranks = world_size
