"""Launch controller: rendezvous, process spawn, log watch, elastic loop.

Reference analog: controllers/collective.py (CollectiveController.build_pod
+ _get_entrypoint spawning per-rank procs with PADDLE_TRAINER_* env),
controllers/master.py rendezvous, watcher.py log aggregation, and
fleet/elastic/manager.py's relaunch-on-failure loop.
"""
from __future__ import annotations

import os
import signal
import subprocess
import sys
import threading
import time

from .context import Context, free_port


class Proc:
    def __init__(self, rank, popen, log_path=None):
        self.rank = rank
        self.popen = popen
        self.log_path = log_path


class Controller:
    def __init__(self, ctx: Context):
        self.ctx = ctx
        self.args = ctx.args
        self.procs: list[Proc] = []
        self._store = None
        self._shutdown = threading.Event()

    # -- rendezvous --------------------------------------------------------
    @staticmethod
    def _is_local_host(host):
        import socket

        if host in ("", "localhost", "127.0.0.1", "0.0.0.0"):
            return True
        try:
            addrs = {ai[4][0] for ai in socket.getaddrinfo(host, None)}
        except socket.gaierror:
            return False
        local = {"127.0.0.1", "::1"}
        try:
            local |= {ai[4][0] for ai in socket.getaddrinfo(
                socket.gethostname(), None)}
        except socket.gaierror:
            pass
        return bool(addrs & local)

    def rendezvous(self):
        """Determine (node_rank, master addr); the controller on the master
        host also hosts the store daemon.

        Single-node default: host a store on a free port locally.
        Multi-node: --master required; explicitly ranked nodes claim their
        rank, auto-rank (-1) nodes draw from an atomic counter skipping
        claimed ranks (reference: master.py sync_peers)."""
        from ..store import TCPStore

        args = self.args
        if args.master is None:
            if args.nnodes != 1:
                raise SystemExit("--master host:port is required for "
                                 "multi-node jobs")
            port = free_port()
            self.master = f"127.0.0.1:{port}"
            self._store = TCPStore("127.0.0.1", port, is_master=True,
                                   world_size=args.nnodes)
            self.node_rank = 0
            return
        host, _, port = args.master.rpartition(":")
        port = int(port)
        self.master = args.master
        # the node running on the master address hosts the daemon (works
        # with auto-rank too); everyone else is a client
        if args.rank == 0 or (args.rank == -1 and self._is_local_host(host)):
            try:
                self._store = TCPStore(host, port, is_master=True,
                                       world_size=args.nnodes)
            except RuntimeError:
                # lost the local bind race to a peer controller
                self._store = TCPStore(host, port, world_size=args.nnodes)
        else:
            self._store = TCPStore(host, port, world_size=args.nnodes)
        job = args.job_id
        # claims are atomic: the first add() on a rank's claim key wins,
        # so explicit and auto assignment cannot race into the same rank.
        # A restarted node may RE-claim its explicit rank when the previous
        # holder's controller heartbeat has gone stale (elastic rejoin).
        if args.rank >= 0:
            gen = self._store.add(f"/rdzv/{job}/claim/{args.rank}", 1)
            if gen != 1:
                # conflict. Give the current holder a grace window to prove
                # liveness (its heartbeat starts right after its claim);
                # then only the LATEST claimant (per the atomic counter)
                # may take over — so concurrent rejoiners can't both win.
                ttl = float(os.environ.get("PADDLE_RDZV_TTL", "5"))
                deadline = time.monotonic() + ttl
                while time.monotonic() < deadline:
                    age = self._store.heartbeat_age(f"ctl/{job}/{args.rank}")
                    if age is not None and age < ttl:
                        raise SystemExit(
                            f"node rank {args.rank} already claimed by a "
                            "live node")
                    time.sleep(min(0.25, ttl / 4))
                cur = self._store.get_nowait(f"/rdzv/{job}/claim/{args.rank}")
                if cur is not None and int(cur) != gen:
                    raise SystemExit(
                        f"node rank {args.rank} superseded by a newer "
                        "claimant")
            self.node_rank = args.rank
        else:
            while True:
                n = self._store.add(f"/rdzv/{job}/next", 1) - 1
                if self._store.add(f"/rdzv/{job}/claim/{n}", 1) == 1:
                    self.node_rank = n
                    break
        # liveness lease backing the re-claim rule above; beat well inside
        # the TTL so a live holder is never mistaken for stale by a
        # rejoiner sampling with the same TTL
        ttl = float(os.environ.get("PADDLE_RDZV_TTL", "5"))
        self._store.start_heartbeat(f"ctl/{job}/{self.node_rank}",
                                    interval=min(1.0, ttl / 4))

    # -- spawn -------------------------------------------------------------
    def _env_for(self, local_rank, restart_epoch=0):
        args = self.args
        world = args.nnodes * args.nproc_per_node
        rank = self.node_rank * args.nproc_per_node + local_rank
        env = dict(os.environ)
        env.update({
            # framework env (consumed by init_parallel_env, env.py)
            "PADDLE_TPU_MASTER": self.master,
            "PADDLE_TPU_PROCESS_ID": str(rank),
            "PADDLE_TPU_NUM_PROCESSES": str(world),
            # reference-parity env (PADDLE_TRAINER_*, parallel.py:943)
            "PADDLE_TRAINER_ID": str(rank),
            "PADDLE_TRAINERS_NUM": str(world),
            "PADDLE_LOCAL_RANK": str(local_rank),
            "PADDLE_RESTART_EPOCH": str(restart_epoch),
            "PADDLE_JOB_ID": args.job_id,
        })
        if getattr(args, "ckpt_dir", None):
            env["PADDLE_TPU_CKPT_DIR"] = args.ckpt_dir
        if getattr(args, "mesh", None):
            # canonical serialized MeshConfig: parse-validate HERE so a
            # bad --mesh fails the launch on the controller, not worker N
            # mid-rendezvous; the SAME payload survives elastic relaunches
            # (spawn() re-runs this), so a restarted world rebuilds the
            # identical mesh and auto-resume proceeds unchanged
            from ...sharding import MeshConfig

            env["PADDLE_TPU_MESH"] = MeshConfig.parse(args.mesh).to_env()
        if world > 1:
            # jax.distributed coordinator (data plane) on master host,
            # distinct port from the KV store
            mhost, _, mport = self.master.rpartition(":")
            env["PADDLE_TPU_COORDINATOR"] = \
                f"{mhost}:{int(mport) + 1}"
        if args.devices:
            env["CUDA_VISIBLE_DEVICES"] = args.devices
            env["TPU_VISIBLE_DEVICES"] = args.devices
        if args.nproc_per_node > 1:
            # several ranks per node are CPU ranks (Context refused an
            # accelerator platform): one process owns a host's chips
            env["JAX_PLATFORMS"] = env.get("JAX_PLATFORMS") or "cpu"
        return env

    def spawn(self, restart_epoch=0):
        args = self.args
        self.procs = []
        if args.log_dir:
            os.makedirs(args.log_dir, exist_ok=True)
        for lr in range(args.nproc_per_node):
            if getattr(args, "module", False):
                cmd = [sys.executable, "-m", args.training_script,
                       *args.training_script_args]
            else:
                cmd = [sys.executable, args.training_script,
                       *args.training_script_args]
            log_path = None
            stdout = stderr = None
            f = None
            if args.log_dir:
                rank = self.node_rank * args.nproc_per_node + lr
                log_path = os.path.join(args.log_dir,
                                        f"worker.{rank}.log")
                f = open(log_path, "ab")
                stdout, stderr = f, subprocess.STDOUT
            p = subprocess.Popen(cmd, env=self._env_for(lr, restart_epoch),
                                 stdout=stdout, stderr=stderr)
            if f is not None:
                f.close()  # Popen dup'd the fd; don't leak per relaunch
            self.procs.append(Proc(lr, p, log_path))

    def terminate(self, sig=signal.SIGTERM, grace=10.0):
        for pr in self.procs:
            if pr.popen.poll() is None:
                try:
                    pr.popen.send_signal(sig)
                except ProcessLookupError:
                    pass
        deadline = time.monotonic() + grace
        for pr in self.procs:
            left = max(0.1, deadline - time.monotonic())
            try:
                pr.popen.wait(timeout=left)
            except subprocess.TimeoutExpired:
                pr.popen.kill()

    # -- supervision -------------------------------------------------------
    def watch(self):
        """Block until all workers exit; fail fast on the first nonzero
        exit (reference: watcher/pod watch loop). Returns exit code."""
        while True:
            alive = 0
            for pr in self.procs:
                rc = pr.popen.poll()
                if rc is None:
                    alive += 1
                elif rc != 0:
                    from ..preemption import is_clean_preempt

                    if is_clean_preempt(rc):
                        print(f"worker rank {pr.rank} exited on clean "
                              f"preemption (code {rc})", file=sys.stderr)
                    else:
                        print(f"worker rank {pr.rank} failed with code {rc}",
                              file=sys.stderr)
                    self.terminate()
                    return rc
            if alive == 0:
                return 0
            time.sleep(0.2)

    def run(self):
        from ..preemption import is_clean_preempt

        self.rendezvous()
        args = self.args
        restarts = 0   # FAILURE relaunches — the budget args.max_restarts caps
        spawns = 0     # all incarnations, incl. free clean-preempt relaunches
        while True:
            self.spawn(restart_epoch=spawns)
            spawns += 1
            rc = self.watch()
            if rc == 0:
                return 0
            if not args.elastic:
                return rc
            preempted = is_clean_preempt(rc)
            if preempted:
                # the worker checkpointed inside its grace window and
                # exited PREEMPT_EXIT_CODE on purpose — relaunching costs
                # nothing from the retry budget (a preemption storm must
                # not exhaust the failure allowance)
                print("elastic: clean preemption (workers checkpointed "
                      "and exited within the grace window); relaunching "
                      f"without spending a retry "
                      f"({restarts}/{args.max_restarts} used)",
                      file=sys.stderr)
            elif restarts >= args.max_restarts:
                return rc
            else:
                restarts += 1
            # all workers are dead here (watch() tears down on first
            # failure), so sweeping torn checkpoints is race-free; the
            # relaunched workers then auto-resume from the newest
            # COMMITTED checkpoint (fleet/elastic resume path)
            if getattr(args, "ckpt_dir", None):
                from ..checkpoint.manager import clean_uncommitted

                try:
                    removed = clean_uncommitted(args.ckpt_dir)
                except OSError as e:
                    print(f"elastic: checkpoint sweep failed: {e}",
                          file=sys.stderr)
                else:
                    if removed:
                        print("elastic: swept torn checkpoints "
                              f"{sorted(removed)}", file=sys.stderr)
            if not preempted:
                print(f"elastic: relaunching workers after failure "
                      f"(attempt {restarts}/{args.max_restarts})",
                      file=sys.stderr)

    def close(self):
        if self._store is not None:
            self._store.close()
            self._store = None


def main(argv=None):
    from .context import parse_args

    args = parse_args(argv)
    ctl = Controller(Context(args))
    try:
        return ctl.run()
    except KeyboardInterrupt:
        ctl.terminate()
        return 130
    finally:
        ctl.close()
