"""Training-process bootstrap: ``jax.distributed`` from the agent's env.

Reference parity: the torch side reads MASTER_ADDR/MASTER_PORT that the
agent's ``MasterKVStore`` handed out (``elastic_agent/torch/training.py``);
here the agent exports ``DLROVER_TPU_COORDINATOR_ADDR`` /
``PROCESS_RANK`` / ``PROCESS_COUNT`` (see
``dlrover_tpu.agent.training._worker_env``) and the trainer calls
``jax.distributed.initialize`` with them — device discovery replaces
NCCL init (SURVEY.md §2.9).
"""

import os
from dataclasses import dataclass
from typing import Optional

from dlrover_tpu.common.constants import NodeEnv
from dlrover_tpu.common.log import default_logger as logger


def process_rank() -> int:
    return int(os.getenv(NodeEnv.PROCESS_RANK, "0"))


def process_count() -> int:
    return int(os.getenv(NodeEnv.PROCESS_COUNT, "1"))


def local_rank() -> int:
    return int(os.getenv(NodeEnv.LOCAL_RANK, "0"))


def node_rank() -> int:
    return int(os.getenv(NodeEnv.NODE_RANK, "0"))


def restart_count() -> int:
    return int(os.getenv("DLROVER_TPU_RESTART_COUNT", "0"))


@dataclass
class ElasticContext:
    rank: int
    world_size: int
    local_rank: int
    node_rank: int
    restart_count: int
    coordinator_addr: str
    master_addr: str


_context: Optional[ElasticContext] = None
_started = False  # this process's ``process`` stage is on the timeline


def _bring_up_jax(rank: int, world: int, coord: str):
    """JAX, the device world and the backend, each under its ``startup``
    stage (observability/events.py ``STARTUP_STAGES``): ``process``
    (once a process) is what lies behind — the interpreter's start and the
    worker script's imports up to here — ``imports`` brings JAX in,
    ``backend_init`` is the first device query, which is the runtime's
    initialisation and would else hide in whatever touches a device
    first.  The process's compile meter is installed before anything can
    compile: every program's trace, lowering and backend compile or
    cache load is a ``compile`` record from here on."""
    global _started
    from dlrover_tpu.observability.events import get_event_logger

    events = get_event_logger()
    if not _started:
        events.process_stage()
    _started = True
    with events.span("startup", stage="imports"):
        from dlrover_tpu.common.jax_env import install_compile_meter

        install_compile_meter(events)
        import jax
    if world > 1 and coord:
        # trainer-side rendezvous: connecting to the coordinator and
        # assembling the device world is restart overhead the goodput
        # ledger must see
        with events.span("rendezvous"):
            jax.distributed.initialize(
                coordinator_address=coord,
                num_processes=world,
                process_id=rank,
            )
        logger.info(
            "jax.distributed initialized: rank %d/%d via %s",
            rank,
            world,
            coord,
        )
    sid = events.begin("startup", stage="backend_init")
    kind = jax.devices()[0].device_kind
    events.end("startup", sid, device_kind=kind)


def init_distributed(initialize_jax: bool = True) -> ElasticContext:
    """Initialize multi-process JAX from the agent-provided env.

    Safe to call when launched standalone (single process, no
    coordinator): it becomes a world of size 1, whose backend is up
    when this returns (``initialize_jax=False`` leaves JAX alone).
    """
    global _context
    if _context is not None:
        return _context
    rank = process_rank()
    world = process_count()
    coord = os.getenv(NodeEnv.COORDINATOR_ADDR, "")
    if initialize_jax:
        _bring_up_jax(rank, world, coord)
    _context = ElasticContext(
        rank=rank,
        world_size=world,
        local_rank=local_rank(),
        node_rank=node_rank(),
        restart_count=restart_count(),
        coordinator_addr=coord,
        master_addr=os.getenv(NodeEnv.MASTER_ADDR, ""),
    )
    return _context


def get_context() -> Optional[ElasticContext]:
    return _context


def reset_context():
    global _context
    _context = None


def coordination_client():
    """The jax.distributed coordination-service client, or None when
    the process is not in a distributed world.  The service's KV store
    and barriers are CONTROL-PLANE primitives: they work on every
    backend, including CPU worlds where XLA multiprocess computations
    (and therefore every ``multihost_utils`` collective) are
    unavailable."""
    # jax.distributed exposes initialize/is_initialized/shutdown only;
    # the client itself still lives on the private global state
    from jax._src import distributed

    return distributed.global_state.client


def control_plane_barrier(
    name: str, timeout_s: float = 600.0
) -> bool:
    """Block at a named coordination-service barrier until every
    process arrives; returns False (no-op) outside a distributed
    world.  ``name`` must be unique per barrier instance (suffix a
    step/round counter).  Unlike ``sync_global_devices`` this never
    launches an XLA computation, so it also COUPLES processes on CPU
    CI exactly like a data-plane collective does on TPU: when a peer
    dies, the survivors stall here until the agent tears them down."""
    client = coordination_client()
    if client is None:
        return False
    client.wait_at_barrier(name, int(timeout_s * 1000))
    return True
