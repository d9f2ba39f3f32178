"""Child process for the 2-process InMemoryDataset.global_shuffle test
(reference data_set.h:205 GlobalShuffle routes records across trainers)."""
import json
import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
import jax  # noqa: E402  (platform forced above, before first use)
jax.config.update("jax_platforms", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402

from paddle_tpu import distributed as dist  # noqa: E402
from paddle_tpu.distributed.fleet import InMemoryDataset  # noqa: E402
from paddle_tpu.io.multislot import Slot, write_multislot_file  # noqa: E402

SLOTS = [Slot("ids", dtype="int64")]


def main():
    rank = int(os.environ["PADDLE_TRAINER_ID"])
    workdir = os.environ["SHUFFLE_WORKDIR"]
    dist.init_parallel_env()

    # each rank owns a disjoint id range so provenance is checkable
    base = rank * 1000
    rows = [{"ids": [base + i]} for i in range(40)]
    path = os.path.join(workdir, f"rank{rank}.txt")
    write_multislot_file(path, rows, SLOTS)

    ds = InMemoryDataset()
    ds.set_slots(SLOTS)
    ds.set_filelist([path])
    ds.set_batch_size(1000)
    ds.load_into_memory()
    ds.set_shuffle_seed(42)
    ds.global_shuffle()

    ids = sorted(int(r.slots["ids"][0]) for r in ds._records)
    print("RESULT " + json.dumps({"rank": rank, "ids": ids}))
    dist.gloo.shutdown()


if __name__ == "__main__":
    sys.exit(main())
