"""Node health-check payload: chip enumeration + matmul + collective.

Reference parity: ``dlrover/trainer/torch/node_check/nvidia_gpu.py:24-56``
(matmul + 16M-element allreduce timed rounds) and the agent entries
``node_health_check`` / ``comm_perf_check``
(``elastic_agent/torch/training.py:1115,1134``).  The TPU twist
(SURVEY.md §7 step 3): a "node" is a TPU-VM worker, and the payload is
chip enumeration plus a small ICI allreduce/matmul run under ``pmap``
across the node's local devices.

The payload runs in a throwaway subprocess so a wedged chip cannot hang
the agent; elapsed time goes back to the master's
``NetworkCheckRendezvousManager`` which shuffles pair groups across two
rounds to isolate the straggler / fault node.
"""

import os
import time

from dlrover_tpu.common.log import default_logger as logger

# Matches the reference's payload scale (matmul K x K, 16M-element
# allreduce) but sized to finish in ~1s on one TPU chip.
_MATMUL_DIM = 1024
_MATMUL_ROUNDS = 3
_ALLREDUCE_ELEMS = 1 << 24


def mock_error() -> bool:
    """Fault injection switch (reference ``node_check/utils.py:49``)."""
    return os.getenv("DLROVER_TPU_MOCK_NODE_ERROR", "") == "1"


def run_health_check() -> float:
    """Run the compute+collective payload on all local devices.

    Returns elapsed seconds; raises on failure (bad chip, injected
    fault).  Imports jax lazily so the agent process itself never
    touches the accelerator runtime.
    """
    if mock_error():
        raise RuntimeError("injected node-check failure")

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding
    from jax.sharding import PartitionSpec as P

    devices = jax.local_devices()
    if not devices:
        raise RuntimeError("no local accelerator devices visible")
    n = len(devices)
    logger.info("node check: %d local devices (%s)", n, devices[0].platform)

    start = time.time()

    mesh = Mesh(np.asarray(devices), ("i",))
    per_chip = NamedSharding(mesh, P("i"))

    def on_each_chip(body, out_specs):
        return jax.jit(
            jax.shard_map(
                body, mesh=mesh, in_specs=P("i"), out_specs=out_specs
            )
        )

    # Per-chip matmul (MXU) + ICI allreduce across local chips.
    def _payload(v):  # this chip's [1, D, D] slice
        y = v[0]
        for _ in range(_MATMUL_ROUNDS):
            y = jnp.tanh(y @ v[0])
        return y[None], jax.lax.psum(jnp.sum(y), axis_name="i")

    x = jax.device_put(
        jax.random.normal(
            jax.random.PRNGKey(0),
            (n, _MATMUL_DIM, _MATMUL_DIM),
            dtype=jnp.bfloat16,
        ),
        per_chip,
    )
    jax.block_until_ready(on_each_chip(_payload, (P("i"), P()))(x))

    # Bandwidth probe: 16M-element (64MB fp32) allreduce, reference
    # ``bm_allreduce`` (node_check/utils.py:88).
    big = jax.device_put(
        jnp.ones((n, _ALLREDUCE_ELEMS // n), dtype=jnp.float32), per_chip
    )
    jax.block_until_ready(
        on_each_chip(
            lambda v: jax.lax.psum(v, axis_name="i"), P()
        )(big)
    )

    elapsed = time.time() - start
    logger.info("node check passed in %.3fs", elapsed)
    return elapsed


def main() -> int:
    """Subprocess entry: ``python -m dlrover_tpu.agent.node_check``."""
    try:
        elapsed = run_health_check()
    except Exception as e:  # noqa: BLE001
        logger.error("node check failed: %s", e)
        return 1
    # Elapsed time goes to the parent via a result file; the agent
    # forwards it to the master (report_network_status).
    out = os.getenv("DLROVER_TPU_NODE_CHECK_RESULT_FILE", "")
    if out:
        with open(out, "w") as f:
            f.write(f"{elapsed:.6f}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
