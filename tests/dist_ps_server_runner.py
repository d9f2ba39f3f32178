"""PS server process for the cross-host service tests (reference
test_dist_fleet_base.py forks brpc pservers the same way)."""
import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
import jax  # noqa: E402  (platform forced above, before first use)
jax.config.update("jax_platforms", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from paddle_tpu.distributed import fleet  # noqa: E402


def main():
    role = fleet.PaddleCloudRoleMaker()
    fleet.init(role)
    assert fleet.is_server()
    fleet.init_server()
    print("SERVER READY", flush=True)
    fleet.run_server()     # blocks until a worker sends stop
    print("SERVER STOPPED", flush=True)


if __name__ == "__main__":
    sys.exit(main())
