"""Launch context: CLI args + environment (reference:
launch/context/__init__.py Context and args parsing in main.py)."""
from __future__ import annotations

import argparse
import os
import socket


def parse_args(argv=None):
    p = argparse.ArgumentParser(
        prog="python -m paddle_tpu.distributed.launch",
        description="Launch a (multi-process) paddle_tpu job")
    p.add_argument("--nnodes", type=int,
                   default=int(os.environ.get("PADDLE_NNODES", "1")),
                   help="number of nodes (hosts) in the job")
    p.add_argument("--nproc_per_node", type=int,
                   default=int(os.environ.get("PADDLE_NPROC_PER_NODE", "1")),
                   help="processes per node. Keep 1 on a TPU host: one "
                        "controller process owns all local chips; more than "
                        "one runs the ranks on CPU and is refused when "
                        "JAX_PLATFORMS names an accelerator")
    p.add_argument("--master", default=os.environ.get("PADDLE_MASTER"),
                   help="host:port of the rendezvous store "
                        "(auto-hosted locally when omitted)")
    p.add_argument("--rank", type=int,
                   default=int(os.environ.get("PADDLE_NODE_RANK", "-1")),
                   help="node rank; -1 = assign via the store")
    p.add_argument("--job_id", default=os.environ.get("PADDLE_JOB_ID",
                                                      "default"))
    p.add_argument("--log_dir", default=os.environ.get("PADDLE_LOG_DIR"),
                   help="write per-rank logs under this dir")
    p.add_argument("--elastic", action="store_true",
                   help="relaunch failed workers (elastic mode)")
    p.add_argument("--ckpt_dir", default=os.environ.get("PADDLE_CKPT_DIR"),
                   help="fault-tolerant checkpoint root: exported to "
                        "workers as PADDLE_TPU_CKPT_DIR (consumed by "
                        "hapi ModelCheckpoint auto-resume / "
                        "CheckpointManager); on elastic relaunch the "
                        "controller sweeps torn checkpoints left by the "
                        "crash before respawning")
    p.add_argument("--max_restarts", type=int, default=3,
                   help="elastic: maximum relaunch attempts")
    p.add_argument("--mesh", default=os.environ.get("PADDLE_MESH"),
                   help="declarative mesh for the whole job, e.g. "
                        "'dp=2,fsdp=4' or 'fsdp=8,dcn_dp=2': exported to "
                        "every worker as PADDLE_TPU_MESH so each host of "
                        "the rendezvous builds the IDENTICAL hybrid "
                        "ICI*DCN mesh (consumed by init_parallel_env; "
                        "MeshConfig(fsdp=N) selects fsdp-by-default "
                        "training, docs/sharding.md)")
    p.add_argument("--devices", default=os.environ.get("PADDLE_DEVICES"),
                   help="visible device ids for this node (comma-separated)")
    p.add_argument("-m", "--module", action="store_true",
                   help="treat training_script as a module name "
                        "(python -m semantics)")
    p.add_argument("training_script",
                   help="the script (or, with -m, module name) to run")
    p.add_argument("training_script_args", nargs=argparse.REMAINDER)
    return p.parse_args(argv)


def free_port():
    s = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
    s.bind(("", 0))
    port = s.getsockname()[1]
    s.close()
    return port


class Context:
    def __init__(self, args):
        self.args = args
        self.node_ip = os.environ.get("POD_IP", "127.0.0.1")
        self.world_size = args.nnodes * args.nproc_per_node
        plat = (os.environ.get("JAX_PLATFORMS") or "cpu").lower()
        if args.nproc_per_node > 1 and "cpu" not in plat:
            # every local rank would get the same TPU_VISIBLE_DEVICES and
            # fight for chips that belong to ONE process
            raise SystemExit(
                f"launch: --nproc_per_node={args.nproc_per_node} with "
                f"JAX_PLATFORMS={plat!r}: one process owns a host's "
                f"accelerator chips; use --nproc_per_node 1 (one controller "
                f"per host drives all local chips)")
