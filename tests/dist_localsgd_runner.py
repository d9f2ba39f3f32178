"""Child process for the eager multi-process LocalSGD test.

Mirrors the reference's dist-test runner model (test_dist_base.py:671 —
trainer subprocesses with the env-var cluster contract, per-rank results
compared by the parent).  Each rank diverges its replica by training on
rank-specific data, then LocalSGD's sync_params must average the replicas
through the host gloo backend.
"""
import json
import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
import jax  # noqa: E402  (platform forced above, before first use)
jax.config.update("jax_platforms", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402

import paddle_tpu as paddle  # noqa: E402
from paddle_tpu import nn, optimizer  # noqa: E402
from paddle_tpu.nn import functional as F  # noqa: E402
from paddle_tpu import distributed as dist  # noqa: E402
from paddle_tpu.distributed.fleet.meta_optimizers import (  # noqa: E402
    LocalSGDOptimizer,
)


def main():
    rank = int(os.environ["PADDLE_TRAINER_ID"])
    dist.init_parallel_env()

    paddle.seed(7)  # identical init on every rank
    model = nn.Linear(4, 1)
    inner = optimizer.SGD(learning_rate=0.05,
                          parameters=model.parameters())
    opt = LocalSGDOptimizer(inner, k_steps=3)

    # rank-specific data → replicas diverge between syncs
    rng = np.random.RandomState(100 + rank)
    x = paddle.to_tensor(rng.randn(16, 4).astype(np.float32))
    y = paddle.to_tensor(rng.randn(16, 1).astype(np.float32))

    pre_sync_w = None
    for step in range(6):
        loss = F.mse_loss(model(x), y)
        loss.backward()
        if opt._count + 1 == 3:  # capture divergence right before 1st sync
            pre_sync_w = model.weight.numpy().copy()
        opt.step()
        opt.clear_grad()

    out = {
        "rank": rank,
        "pre_sync_w": np.asarray(pre_sync_w).tolist(),
        "final_w": model.weight.numpy().tolist(),
        "final_b": model.bias.numpy().tolist(),
    }
    print("RESULT " + json.dumps(out))
    dist.gloo.shutdown()


if __name__ == "__main__":
    sys.exit(main())
