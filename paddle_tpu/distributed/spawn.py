"""paddle.distributed.spawn — multi-process launch from inside python.

Reference: python/paddle/distributed/spawn.py:536 `spawn(func, args,
nprocs, join, daemon, **options)` — forks nprocs workers, wires the
TCPStore rendezvous env, runs func in each, propagates the first child
error with its traceback.

TPU-native: child processes are full controller processes. The parent
hosts the native coordination store (native/coord_store.cc) and exports
the same PADDLE_TPU_* env contract as the launch CLI
(launch/controller.py:137), so `init_parallel_env` / `get_store` /
eager p2p work identically under spawn and under `-m ...launch`.
Children default to the CPU platform: a host's chips belong to ONE
process, so N children cannot each open them (and a parent that has
touched JAX already holds them). Multi-host TPU jobs use the launch CLI
with one controller process per host instead.
"""
from __future__ import annotations

import multiprocessing as mp
import os
import traceback


def _worker(func, args, rank, nprocs, master, error_queue, env_extra):
    os.environ["PADDLE_TPU_PROCESS_ID"] = str(rank)
    os.environ["PADDLE_TPU_NUM_PROCESSES"] = str(nprocs)
    os.environ["PADDLE_TPU_MASTER"] = master
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    for k, v in env_extra.items():
        os.environ[k] = v
    # a forked child inherits the parent's imported jax, whose config read
    # JAX_PLATFORMS at import: pin the child's platform in config too
    import jax

    jax.config.update("jax_platforms", os.environ["JAX_PLATFORMS"])
    try:
        func(*args)
    except KeyboardInterrupt:
        pass
    except Exception:
        error_queue.put((rank, traceback.format_exc()))
        raise SystemExit(1)


class SpawnContext:
    def __init__(self, processes, error_queue, store):
        self.processes = processes
        self._error_queue = error_queue
        self._store = store

    def join(self, timeout=None):
        """Wait for all workers, polling so one failed child terminates
        its siblings instead of deadlocking ranks blocked on its store
        keys (reference: spawn.py MultiprocessContext.join polls the
        error queue the same way)."""
        import time as _time

        deadline = (_time.monotonic() + timeout) if timeout else None
        while True:
            failed = [p for p in self.processes
                      if p.exitcode not in (0, None)]
            if failed:
                for p in self.processes:
                    if p.is_alive():
                        p.terminate()
                try:
                    rank, tb = self._error_queue.get(timeout=1.0)
                    raise RuntimeError(f"spawned rank {rank} failed:\n{tb}")
                except mp.queues.Empty:
                    raise RuntimeError(
                        f"spawned process {failed[0].pid} exited with "
                        f"code {failed[0].exitcode}")
            if all(p.exitcode == 0 for p in self.processes):
                break
            if deadline is not None and _time.monotonic() > deadline:
                raise TimeoutError("spawned processes did not finish")
            for p in self.processes:
                p.join(timeout=0.2)
        if self._store is not None:
            self._store.close()
        return True


def spawn(func, args=(), nprocs=-1, join=True, daemon=False, **options):
    """Launch `func(*args)` in `nprocs` coordinated worker processes."""
    if nprocs == -1:
        nprocs = int(os.environ.get("PADDLE_TPU_SPAWN_NPROCS", "2"))
    from .store import create_master_store
    store = create_master_store(world_size=nprocs)
    master = f"127.0.0.1:{store.port}"

    ctx = mp.get_context(options.pop("start_method", "spawn"))
    error_queue = ctx.Queue()
    env_extra = {str(k): str(v) for k, v in
                 options.pop("env", {}).items()}
    procs = []
    for rank in range(nprocs):
        p = ctx.Process(target=_worker,
                        args=(func, args, rank, nprocs, master, error_queue,
                              env_extra),
                        daemon=daemon)
        p.start()
        procs.append(p)
    context = SpawnContext(procs, error_queue, store)
    if join:
        context.join()
        return None
    return context
