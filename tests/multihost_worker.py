"""Worker process for the multi-host execution test (test_multihost_exec.py).

Joins a 2-process jax.distributed job over localhost DCN, builds a global
mesh spanning both processes' devices, stitches a per-process local batch
into one globally-sharded array, and runs a jitted reduction whose
all-reduce crosses the process boundary. Runs OUTSIDE pytest — each rank is
its own interpreter, like a real multi-host launch.

Usage: python multihost_worker.py <rank> <coordinator_port>
"""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402

import numpy as np  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import PartitionSpec  # noqa: E402

from gofr_tpu.config import MockConfig  # noqa: E402
from gofr_tpu.parallel.multihost import (global_mesh, initialize_from_config,  # noqa: E402
                                         process_local_batch)


def main() -> None:
    rank, port = int(sys.argv[1]), sys.argv[2]
    spec = initialize_from_config(MockConfig({
        "JAX_COORDINATOR_ADDR": f"127.0.0.1:{port}",
        "JAX_NUM_PROCESSES": "2",
        "JAX_PROCESS_ID": str(rank),
        "JAX_COORDINATOR_TIMEOUT_S": "150",
    }))
    assert spec is not None and spec.process_id == rank
    assert jax.process_count() == 2, jax.process_count()
    assert len(jax.devices()) == 4  # 2 virtual CPU devices per process

    mesh = global_mesh(dp=4)
    # each rank contributes ITS half of the global [4, 8] batch
    local = np.full((2, 8), float(rank + 1), dtype=np.float32)
    batch = process_local_batch(local, mesh, spec=PartitionSpec("dp"))
    assert batch.shape == (4, 8)

    @jax.jit
    def reduce_sum(x):
        return jnp.sum(x)  # all-reduce across both processes' shards

    total = float(reduce_sum(batch))
    expected = 2 * 8 * 1.0 + 2 * 8 * 2.0
    assert abs(total - expected) < 1e-5, (total, expected)
    print(f"RANK{rank}_OK total={total}", flush=True)


if __name__ == "__main__":
    main()
