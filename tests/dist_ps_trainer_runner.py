"""Trainer process for the cross-host PS service tests: geo-async CTR
training through RemoteSparseTable shards on real server processes
(reference test_dist_fleet_base.py trainer side)."""
import json
import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
import jax  # noqa: E402  (platform forced above, before first use)
jax.config.update("jax_platforms", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402

import paddle_tpu as paddle  # noqa: E402
import paddle_tpu.nn.functional as F  # noqa: E402
from paddle_tpu import nn, optimizer  # noqa: E402
from paddle_tpu import distributed as dist  # noqa: E402
from paddle_tpu.distributed import fleet  # noqa: E402
from paddle_tpu.distributed.ps import runtime as ps_runtime  # noqa: E402

VOCAB = 400
EMB_DIM = 8


def main():
    rank = int(os.environ["PADDLE_TRAINER_ID"])
    mode = os.environ.get("PS_MODE", "geo")

    role = fleet.PaddleCloudRoleMaker()
    strategy = fleet.DistributedStrategy()
    if mode == "geo":
        strategy.a_sync = True
        strategy.a_sync_configs.k_steps = 4
    elif mode == "async":
        strategy.a_sync = True
        strategy.a_sync_configs.k_steps = 0
    fleet.init(role, strategy=strategy)
    assert fleet.is_worker()
    dist.init_parallel_env()          # gloo for trainer barriers
    fleet.init_worker()

    emb = ps_runtime.sparse_embedding("ctr", EMB_DIM, rule="sgd", lr=0.5,
                                      strategy=strategy)
    head = nn.Linear(EMB_DIM, 1)
    opt = optimizer.SGD(learning_rate=0.2, parameters=head.parameters())

    # disjoint id ranges per trainer -> cross-process delta propagation is
    # provable: rank 0 later pulls rank 1's rows from the servers
    rng = np.random.RandomState(100 + rank)
    half = VOCAB // 4          # small per-trainer vocab: ids recur enough
    base = rank * (VOCAB // 2)
    losses = []
    paddle.seed(7 + rank)
    for step in range(60):
        ids = base + rng.randint(0, half, size=(16, 3))
        # learnable bag-of-ids rule: "contains a low id" — per-id embeddings
        # can encode it directly, so the loss must actually drop
        label = (ids.min(axis=1, keepdims=True) < base + half // 4) \
            .astype(np.float32)
        e = emb(paddle.to_tensor(ids))
        pooled = e.sum(axis=1)
        loss = F.binary_cross_entropy_with_logits(head(pooled),
                                                  paddle.to_tensor(label))
        loss.backward()
        opt.step()
        opt.clear_grad()
        emb.step()
        losses.append(float(loss._value))

    fleet.stop_worker()               # flush async queue / geo deltas
    dist.collective.barrier()         # both trainers fully flushed

    other_rows_nonzero = None
    table_size = None
    if rank == 0:
        client = ps_runtime.get_client()
        table_size = client.table_size("ctr")
        other_base = (1 - rank) * (VOCAB // 2)
        probe = np.arange(other_base, other_base + VOCAB // 2)
        rows = client.pull_sparse("ctr", probe, create=False)
        other_rows_nonzero = bool(np.abs(rows).sum() > 0)

    dist.collective.barrier()
    if rank == 0:
        ps_runtime.shutdown_servers()

    print("RESULT " + json.dumps({
        "rank": rank, "losses": losses, "table_size": table_size,
        "other_rows_nonzero": other_rows_nonzero,
    }), flush=True)
    dist.gloo.shutdown()


if __name__ == "__main__":
    sys.exit(main())
