"""Launcher + elastic tests (reference: launch tests and
fleet/elastic tests; single-host multi-process per SURVEY §4)."""
import os
import subprocess
import sys
import textwrap
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_launch(script, tmp_path, *extra, procs=4, env=None, timeout=120):
    sp = tmp_path / "worker.py"
    sp.write_text(textwrap.dedent(script))
    cmd = [sys.executable, "-m", "paddle_tpu.distributed.launch",
           "--nproc_per_node", str(procs), *extra, str(sp)]
    e = dict(os.environ, PYTHONPATH=REPO)
    e.update(env or {})
    return subprocess.run(cmd, cwd=REPO, env=e, capture_output=True,
                          text=True, timeout=timeout)


def test_launch_spawns_ranked_workers(tmp_path):
    """Workers see rank env + the shared store, and rendezvous through it."""
    out = tmp_path / "out"
    out.mkdir()
    r = _run_launch(f"""
        import os
        from paddle_tpu.distributed.store import TCPStore
        rank = int(os.environ["PADDLE_TPU_PROCESS_ID"])
        world = int(os.environ["PADDLE_TPU_NUM_PROCESSES"])
        assert os.environ["PADDLE_TRAINER_ID"] == str(rank)
        host, _, port = os.environ["PADDLE_TPU_MASTER"].rpartition(":")
        s = TCPStore(host, int(port), world_size=world, timeout=20)
        s.set(f"/r/{{rank}}", str(rank))
        s.barrier("test")
        peers = sorted(int(s.get(f"/r/{{i}}")) for i in range(world))
        assert peers == list(range(world)), peers
        open(r"{out}" + f"/rank{{rank}}", "w").write("ok")
        s.close()
    """, tmp_path, procs=4)
    assert r.returncode == 0, r.stderr
    assert sorted(os.listdir(out)) == [f"rank{i}" for i in range(4)]


def test_launch_exports_canonical_mesh_env(tmp_path):
    """--mesh is parse-validated on the controller and every worker gets
    the CANONICAL serialized MeshConfig in PADDLE_TPU_MESH (so N hosts —
    and elastic relaunches — build the identical mesh); a bad spec fails
    at launch, not on worker N mid-rendezvous."""
    from paddle_tpu.distributed.launch.context import Context, parse_args
    from paddle_tpu.distributed.launch.controller import Controller
    from paddle_tpu.sharding import MeshConfig

    args = parse_args(["--mesh", "fsdp=8,dcn_dp=2", "train.py"])
    c = Controller(Context(args))
    c.master, c.node_rank = "127.0.0.1:1", 0
    env = c._env_for(0)
    assert env["PADDLE_TPU_MESH"] == "dp=1,fsdp=8,tp=1,dcn_dp=2"
    assert MeshConfig.parse(env["PADDLE_TPU_MESH"]) == \
        MeshConfig(fsdp=8, dcn_dp=2)
    # unchanged across an elastic relaunch epoch
    assert c._env_for(0, restart_epoch=2)["PADDLE_TPU_MESH"] == \
        env["PADDLE_TPU_MESH"]

    bad = Controller(Context(parse_args(["--mesh", "fsdp=x", "t.py"])))
    bad.master, bad.node_rank = "127.0.0.1:1", 0
    with pytest.raises(ValueError):
        bad._env_for(0)
    # no --mesh: the env key is absent entirely (workers fall back to
    # their own topology setup)
    plain = Controller(Context(parse_args(["t.py"])))
    plain.master, plain.node_rank = "127.0.0.1:1", 0
    assert "PADDLE_TPU_MESH" not in plain._env_for(0)


def test_launch_refuses_several_accelerator_ranks_per_node(monkeypatch):
    """A host's chips belong to one process: --nproc_per_node > 1 is
    refused at launch when JAX_PLATFORMS names an accelerator, and the
    ranks are pinned to CPU otherwise."""
    from paddle_tpu.distributed.launch.context import Context, parse_args
    from paddle_tpu.distributed.launch.controller import Controller

    args = parse_args(["--nproc_per_node", "2", "t.py"])
    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    with pytest.raises(SystemExit, match="one process owns"):
        Context(args)
    Context(parse_args(["--nproc_per_node", "1", "t.py"]))  # 1 is fine
    monkeypatch.delenv("JAX_PLATFORMS")
    c = Controller(Context(args))
    c.master, c.node_rank = "127.0.0.1:1", 0
    assert c._env_for(1)["JAX_PLATFORMS"] == "cpu"


def test_launch_fail_fast_propagates_exit_code(tmp_path):
    r = _run_launch("""
        import os, sys, time
        if os.environ["PADDLE_TPU_PROCESS_ID"] == "1":
            sys.exit(7)
        time.sleep(30)  # must be torn down by the controller
    """, tmp_path, procs=3, timeout=60)
    assert r.returncode == 7
    assert "rank" in r.stderr and "failed" in r.stderr


def test_launch_elastic_relaunches(tmp_path):
    """First attempt fails; elastic relaunch (restart epoch 1) succeeds."""
    r = _run_launch(f"""
        import os, sys
        epoch = int(os.environ["PADDLE_RESTART_EPOCH"])
        rank = os.environ["PADDLE_TPU_PROCESS_ID"]
        if epoch == 0 and rank == "0":
            sys.exit(1)  # simulated failure on the first attempt
        if epoch >= 1:
            open(r"{tmp_path}" + f"/ok{{rank}}", "w").write(str(epoch))
    """, tmp_path, "--elastic", "--max_restarts", "2", procs=2, timeout=60)
    assert r.returncode == 0, r.stderr
    assert "relaunching" in r.stderr
    assert sorted(f for f in os.listdir(tmp_path) if f.startswith("ok")) \
        == ["ok0", "ok1"]


def test_launch_clean_preempt_does_not_burn_retry_budget(tmp_path):
    """Workers exiting PREEMPT_EXIT_CODE (checkpointed inside the grace
    window) are relaunched WITHOUT spending an elastic retry: two
    consecutive preemptions converge even with --max_restarts 1, and the
    relaunch log names the clean preemption instead of a failure."""
    from paddle_tpu.distributed.preemption import PREEMPT_EXIT_CODE

    r = _run_launch(f"""
        import os, sys
        epoch = int(os.environ["PADDLE_RESTART_EPOCH"])
        rank = os.environ["PADDLE_TPU_PROCESS_ID"]
        if epoch < 2:
            sys.exit({PREEMPT_EXIT_CODE})  # clean preemption, twice
        open(r"{tmp_path}" + f"/done{{rank}}", "w").write(str(epoch))
    """, tmp_path, "--elastic", "--max_restarts", "1", procs=2, timeout=60)
    assert r.returncode == 0, r.stderr
    assert "clean preemption" in r.stderr
    assert "without spending a retry" in r.stderr
    assert "failed" not in r.stderr
    assert sorted(f for f in os.listdir(tmp_path)
                  if f.startswith("done")) == ["done0", "done1"]


def test_launch_log_dir(tmp_path):
    logs = tmp_path / "logs"
    r = _run_launch("""
        import os
        print("hello from", os.environ["PADDLE_TPU_PROCESS_ID"])
    """, tmp_path, "--log_dir", str(logs), procs=2)
    assert r.returncode == 0, r.stderr
    files = sorted(os.listdir(logs))
    assert files == ["worker.0.log", "worker.1.log"]
    assert "hello from 0" in (logs / "worker.0.log").read_text()


def test_elastic_manager_membership():
    from paddle_tpu.distributed.store import create_master_store, TCPStore
    from paddle_tpu.distributed.fleet.elastic import (ElasticManager,
                                                      ElasticStatus)

    master = create_master_store()
    nodes = [TCPStore(port=master.port) for _ in range(2)]
    mgrs = [ElasticManager(nodes[i], job_id="j", rank=i, np_target=2,
                           ttl=0.6, interval=0.1) for i in range(2)]
    for m in mgrs:
        m.register()
    assert mgrs[0].wait_for_world(timeout=10)
    assert mgrs[0].check() == ElasticStatus.HOLD

    events = []
    mgrs[0].watch(on_change=lambda st, alive: events.append((st, alive)))
    # node 1 dies (stops heartbeating)
    mgrs[1].deregister()
    deadline = time.time() + 10
    while not events and time.time() < deadline:
        time.sleep(0.05)
    mgrs[0].exit()
    assert events and events[0][0] == ElasticStatus.RESTART
    assert events[0][1] == ["j/node0"]
    for s in nodes:
        s.close()
    master.close()


def test_launch_module_mode(tmp_path):
    """-m module launch (regression: argparse rejected -m entirely)."""
    pkg = tmp_path / "mymod.py"
    pkg.write_text("import os; print('mod rank', "
                   "os.environ['PADDLE_TPU_PROCESS_ID'])")
    cmd = [sys.executable, "-m", "paddle_tpu.distributed.launch",
           "--nproc_per_node", "2", "--log_dir", str(tmp_path / "logs"),
           "-m", "mymod"]
    env = dict(os.environ, PYTHONPATH=f"{REPO}:{tmp_path}")
    r = subprocess.run(cmd, cwd=REPO, env=env, capture_output=True,
                       text=True, timeout=60)
    assert r.returncode == 0, r.stderr
    logs = (tmp_path / "logs")
    assert "mod rank 0" in (logs / "worker.0.log").read_text()


def test_rendezvous_mixed_explicit_and_auto_ranks():
    """Auto-assigned node ranks must skip explicitly claimed ones, and the
    node on the master address self-elects as store host under --rank -1."""
    from paddle_tpu.distributed.launch.context import (Context, parse_args,
                                                       free_port)
    from paddle_tpu.distributed.launch.controller import Controller

    port = free_port()
    master = f"127.0.0.1:{port}"

    def ctl(*extra):
        args = parse_args(["--nnodes", "3", "--master", master, *extra,
                           "x.py"])
        c = Controller(Context(args))
        c.rendezvous()
        return c

    c_host = ctl()              # auto rank; local master address -> hosts
    assert c_host._store._server is not None
    assert c_host.node_rank == 0
    c_explicit = ctl("--rank", "1")
    assert c_explicit.node_rank == 1
    c_auto = ctl()              # must skip claimed ranks 0 and 1
    assert c_auto.node_rank == 2
    for c in (c_auto, c_explicit, c_host):
        c.close()


def test_explicit_rank_reclaim_after_crash():
    """A relaunched node with the same rank may re-claim once the previous
    holder's heartbeat is stale; a LIVE holder blocks the claim."""
    from paddle_tpu.distributed.launch.context import (Context, parse_args,
                                                       free_port)
    from paddle_tpu.distributed.launch.controller import Controller

    port = free_port()
    master = f"127.0.0.1:{port}"

    def ctl(*extra):
        args = parse_args(["--nnodes", "2", "--master", master, *extra,
                           "x.py"])
        c = Controller(Context(args))
        c.rendezvous()
        return c

    os.environ["PADDLE_RDZV_TTL"] = "1"
    try:
        host = ctl()                 # hosts the store, rank 0
        worker = ctl("--rank", "1")  # live holder of rank 1
        with pytest.raises(SystemExit, match="live node"):
            ctl("--rank", "1")       # duplicate while holder is alive
        # holder dies (heartbeat stops)
        worker._store.stop_heartbeat()
        worker._store.close()
        time.sleep(1.5)              # let the heartbeat go stale (> ttl)
        rejoin = ctl("--rank", "1")  # stale heartbeat -> re-claim succeeds
        assert rejoin.node_rank == 1
        rejoin.close()
        host.close()
    finally:
        del os.environ["PADDLE_RDZV_TTL"]


def test_launch_elastic_sweeps_torn_checkpoints(tmp_path):
    """--ckpt_dir exports PADDLE_TPU_CKPT_DIR to workers and the elastic
    relaunch path sweeps torn (uncommitted) checkpoint dirs left by the
    crash before respawning, so resumed workers only ever see committed
    state."""
    ck = tmp_path / "ck"
    ck.mkdir()
    r = _run_launch("""
        import os, sys
        root = os.environ["PADDLE_TPU_CKPT_DIR"]
        torn = os.path.join(root, "step_00000005")
        if int(os.environ["PADDLE_RESTART_EPOCH"]) == 0:
            os.makedirs(torn)
            open(os.path.join(torn, "data_0.npz"), "wb").write(b"torn")
            sys.exit(1)   # crash mid-job, torn dir left behind
        assert not os.path.exists(torn), "torn checkpoint not swept"
    """, tmp_path, "--elastic", "--max_restarts", "1",
        "--ckpt_dir", str(ck), procs=1, timeout=60)
    assert r.returncode == 0, r.stderr
    assert "swept torn checkpoints" in r.stderr
