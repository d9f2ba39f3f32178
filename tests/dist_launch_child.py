"""Child for the launch-CLI e2e test: proves the launcher's env contract
+ gloo rendezvous end-to-end (reference launch_utils.py:435
start_local_trainers env contract)."""
import json
import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
import jax  # noqa: E402  (platform forced above, before first use)
jax.config.update("jax_platforms", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from paddle_tpu import distributed as dist  # noqa: E402
from paddle_tpu.distributed import fleet  # noqa: E402


def main():
    os.environ["PADDLE_DIST_BACKEND"] = "gloo"   # CPU e2e: skip jax.dist
    dist.init_parallel_env()
    rank = dist.get_rank()
    world = dist.get_world_size()
    # host collective through the launcher-provided rendezvous
    total = int(fleet.fleet.util.all_reduce(rank + 1, mode="sum"))
    out = {"rank": rank, "world": world, "sum": total,
           "endpoint": os.environ.get("PADDLE_CURRENT_ENDPOINT"),
           "gloo": os.environ.get("PADDLE_GLOO_ENDPOINT")}
    with open(os.path.join(os.environ["LAUNCH_OUT_DIR"],
                           f"rank{rank}.json"), "w") as f:
        json.dump(out, f)
    dist.gloo.shutdown()


if __name__ == "__main__":
    main()
