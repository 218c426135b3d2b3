"""Subprocess worker for test_distributed.py: joins a 2-process CPU
"slice" via seldon_tpu.parallel.distributed and proves cross-host
collectives work. Prints one JSON line the test asserts on."""

import json
import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "")
    + " --xla_force_host_platform_device_count=2"
)

import jax
import numpy as np

from seldon_tpu.parallel import distributed


def main():
    coordinator = sys.argv[1]
    pid = int(sys.argv[2])
    nproc = int(sys.argv[3])

    cfg = distributed.SliceConfig(
        coordinator=coordinator, num_processes=nproc, process_id=pid
    )
    assert distributed.ensure_initialized(cfg)
    assert distributed.ensure_initialized(cfg)  # idempotent

    # Slice-aware readiness: all hosts joined -> check passes.
    distributed.SliceReadiness(expected_hosts=nproc).check()

    # Cross-host collective: allgather each process's id.
    from jax.experimental import multihost_utils

    gathered = multihost_utils.process_allgather(np.array([pid], np.int32))

    # Global mesh spanning both processes; one sharded computation.
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    devs = np.array(jax.devices()).reshape(-1)
    mesh = Mesh(devs, ("dp",))
    n = len(devs)
    y = jax.jit(
        lambda: jnp.sum(jnp.arange(n * 4, dtype=jnp.float32)),
        out_shardings=NamedSharding(mesh, P()),
    )()

    print(json.dumps({
        "process_id": pid,
        "process_count": jax.process_count(),
        "global_devices": len(jax.devices()),
        "local_devices": len(jax.local_devices()),
        "allgather": np.asarray(gathered).ravel().tolist(),
        "sharded_sum": float(y),
    }), flush=True)


if __name__ == "__main__":
    main()
