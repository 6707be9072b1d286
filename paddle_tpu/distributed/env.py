"""Distributed environment bootstrap.

Reference analog: `paddle.distributed.init_parallel_env`
(python/paddle/distributed/parallel.py:943) which builds a TCPStore +
ProcessGroupNCCL per rank. TPU-native: one *controller process per host*
drives all local chips through PJRT; multi-host jobs bootstrap through
jax.distributed's coordination service (the TCPStore equivalent) and then
every collective is compiled into XLA programs over ICI/DCN — there are no
explicit process groups to create.

Rank/world-size semantics: `get_rank`/`get_world_size` report *process*
(host) coordinates, matching the launcher's view; device-level parallelism
coordinates live on the hybrid topology (topology.py) over the global device
mesh.
"""
from __future__ import annotations

import os

import jax

_initialized = False
_global_store = None


class ParallelEnv:
    """Reference: paddle.distributed.ParallelEnv (parallel.py)."""

    @property
    def rank(self):
        return get_rank()

    @property
    def world_size(self):
        return get_world_size()

    @property
    def local_rank(self):
        return get_rank()

    @property
    def device_count(self):
        return jax.device_count()

    @property
    def nranks(self):
        return get_world_size()

    @property
    def device_id(self):
        return 0


def init_parallel_env():
    """Initialize multi-host coordination if launcher env is present.

    The launcher (paddle_tpu.distributed.launch) sets
    PADDLE_TPU_COORDINATOR / PADDLE_TPU_NUM_PROCESSES / PADDLE_TPU_PROCESS_ID
    (≈ reference PADDLE_TRAINER_* env, parallel.py:943). Single-host runs
    need no bootstrap: all chips are already addressable via PJRT.
    """
    global _initialized
    if _initialized:
        return ParallelEnv()
    # Check env BEFORE any jax call: jax.distributed.initialize must run
    # before the XLA backend initializes (probing process_count() would
    # initialize it and make multi-host bootstrap impossible).
    coord = os.environ.get("PADDLE_TPU_COORDINATOR")
    if coord:
        jax.distributed.initialize(
            coordinator_address=coord,
            num_processes=int(os.environ["PADDLE_TPU_NUM_PROCESSES"]),
            process_id=int(os.environ["PADDLE_TPU_PROCESS_ID"]),
        )
    # Framework control plane (native TCPStore): rendezvous KV + barriers +
    # liveness heartbeats, orthogonal to the XLA data plane. The launcher
    # sets PADDLE_TPU_MASTER to the rank-0-hosted store (reference:
    # create_or_get_global_tcp_store, parallel.py:1099).
    master = os.environ.get("PADDLE_TPU_MASTER")
    if master:
        from .store import TCPStore

        global _global_store
        host, _, port = master.rpartition(":")
        rank = int(os.environ.get("PADDLE_TPU_PROCESS_ID", "0"))
        world = int(os.environ.get("PADDLE_TPU_NUM_PROCESSES", "1"))
        # the launcher controller on node 0 hosts the daemon; every worker
        # (rank 0 included) is a client
        _global_store = TCPStore(host or "127.0.0.1", int(port),
                                 world_size=world)
        _global_store.start_heartbeat(f"rank{rank}")
        # collective-schedule verifier (PADDLE_TPU_COMMCHECK=1): arm the
        # cross-host rendezvous over this store so every entrypoint's
        # schedule fingerprint is compared BEFORE its first dispatch.
        # Epoch-namespaced by the launcher's restart epoch, so an
        # elastic relaunch re-verifies the whole cohort under fresh
        # /commcheck/<epoch>/ keys.
        from ..analysis import commcheck as _cc

        if _cc.enabled() and world > 1:
            _cc.attach_store(
                _global_store, host=f"rank{rank}", world_size=world,
                epoch=int(os.environ.get("PADDLE_RESTART_EPOCH", "0")
                          or 0))
    # declarative mesh from the launcher (--mesh): AFTER the
    # jax.distributed bootstrap above, so the config resolves against the
    # job-global device set and every host installs the identical hybrid
    # ICI×DCN topology before any engine asks for placement
    _apply_mesh_env()
    _initialized = True
    return ParallelEnv()


def _apply_mesh_env():
    """`PADDLE_TPU_MESH` (serialized by the launcher's ``--mesh``) ->
    build the declarative mesh and install it as the global topology.
    Returns the mesh, or None when the env is unset. Deterministic per
    config + device set, so N hosts of a rendezvous — and the SAME hosts
    after an elastic relaunch — always agree on placement with zero
    per-host code (docs/sharding.md)."""
    from ..sharding import MeshConfig

    cfg = MeshConfig.from_env()
    if cfg is None:
        return None
    from . import topology as topo_mod

    mesh = cfg.build()
    topo_mod.set_hybrid_communicate_group(
        topo_mod.HybridCommunicateGroup(mesh=mesh))
    return mesh


def get_store():
    """The job-global coordination store, or None outside launched jobs."""
    return _global_store


def is_initialized():
    return _initialized


def get_rank(group=None):
    if group is not None:
        return group.rank
    # Launcher/spawn contract first (reference: PADDLE_TRAINER_ID): spawned
    # children without jax.distributed all report process_index()==0.
    env_rank = os.environ.get("PADDLE_TPU_PROCESS_ID")
    if env_rank is not None:
        return int(env_rank)
    return jax.process_index()


def get_world_size(group=None):
    if group is not None:
        return group.nranks
    env_world = os.environ.get("PADDLE_TPU_NUM_PROCESSES")
    if env_world is not None:
        return int(env_world)
    return jax.process_count()
