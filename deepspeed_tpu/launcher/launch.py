"""Per-node process launcher.

TPU-native analogue of ``deepspeed/launcher/launch.py:133-254``: decode the
world map, compute this node's ranks, and ``Popen`` the user script once per
local rank with the distributed env contract:

    RANK, LOCAL_RANK, WORLD_SIZE, LOCAL_SIZE, CROSS_RANK, CROSS_SIZE,
    MASTER_ADDR, MASTER_PORT

TPU default is **one process per host** (all local chips belong to that
process; ``jax.distributed.initialize`` handles chip discovery), which is
``--proc_per_chip`` off.  With ``--proc_per_chip`` one process per slot is
spawned — the mode used by the CPU virtual-mesh CI.  On a host that holds
TPU chips the flag is REFUSED for more than one slot: a chip belongs to one
process at a time and nothing here hands each child its own chip, so the
first child would claim every chip of the host and the rest would fail or
hang.  This launcher never touches JAX itself (a parent that did would hold
the chips its children need).

Child exit codes propagate (reference launch.py:319); SIGTERM fans out to
the process group on interrupt.
"""

from __future__ import annotations

import argparse
import glob
import os
import signal
import subprocess
import sys
import time
from typing import Dict, List

from .multinode_runner import decode_world_info
from ..utils.logging import logger


def parse_args(argv=None):
    p = argparse.ArgumentParser(description="deepspeed_tpu per-node launcher")
    p.add_argument("--world_info", required=True,
                   help="base64 JSON {host: slots}")
    p.add_argument("--node_rank", default="0",
                   help="this node's rank, or 'env' to read TPU_WORKER_ID")
    p.add_argument("--master_addr", default="127.0.0.1")
    p.add_argument("--master_port", default="29500")
    p.add_argument("--proc_per_chip", action="store_true",
                   help="spawn one process per slot instead of per host")
    p.add_argument("user_script")
    p.add_argument("user_args", nargs=argparse.REMAINDER)
    return p.parse_args(argv)


def tpu_chips_on_host() -> int:
    """TPU chips of this host, counted from their device nodes — without
    importing JAX (v4 and older expose ``/dev/accel*``, v5e and newer
    VFIO groups)."""
    return len(glob.glob("/dev/accel[0-9]*")
               or glob.glob("/dev/vfio/[0-9]*"))


def build_rank_envs(world: Dict[str, int], node_rank: int,
                    master_addr: str, master_port: str,
                    proc_per_chip: bool) -> List[Dict[str, str]]:
    """Environment dicts, one per local process to spawn on this node."""
    hosts = list(world.keys())
    if not 0 <= node_rank < len(hosts):
        raise ValueError(f"node_rank {node_rank} out of range for {hosts}")
    if proc_per_chip:
        local_size = world[hosts[node_rank]]
        world_size = sum(world.values())
        rank_offset = sum(world[h] for h in hosts[:node_rank])
    else:
        local_size = 1
        world_size = len(hosts)
        rank_offset = node_rank

    envs = []
    for local_rank in range(local_size):
        env = {
            "RANK": str(rank_offset + local_rank),
            "LOCAL_RANK": str(local_rank),
            "WORLD_SIZE": str(world_size),
            "LOCAL_SIZE": str(local_size),
            "CROSS_RANK": str(node_rank),
            "CROSS_SIZE": str(len(hosts)),
            "MASTER_ADDR": master_addr,
            "MASTER_PORT": str(master_port),
        }
        if proc_per_chip:
            # CPU virtual-mesh CI: each process sees its own 1-device world
            # unless the test overrides XLA_FLAGS itself.
            env["DS_TPU_PROC_PER_CHIP"] = "1"
        envs.append(env)
    return envs


def main(argv=None) -> int:
    args = parse_args(argv)
    world = decode_world_info(args.world_info)
    if args.node_rank == "env":
        node_rank = int(os.environ.get("TPU_WORKER_ID", "0"))
    else:
        node_rank = int(args.node_rank)

    rank_envs = build_rank_envs(world, node_rank, args.master_addr,
                                args.master_port, args.proc_per_chip)
    chips = tpu_chips_on_host()
    if args.proc_per_chip and len(rank_envs) > 1 and chips:
        logger.error(
            "--proc_per_chip asks for %d processes on a host with %d TPU "
            "chip(s): a chip belongs to one process at a time and this "
            "launcher does not give each child its own chip.  Drop the "
            "flag — one process per host drives every local chip "
            "(jax.devices()) — or run the CPU virtual mesh on a host "
            "without chips.", len(rank_envs), chips)
        return 2
    logger.info("node %d launching %d process(es) for %s",
                node_rank, len(rank_envs), args.user_script)

    procs: List[subprocess.Popen] = []

    # Handlers installed BEFORE the spawn loop: a SIGINT/SIGTERM arriving
    # while children are still being spawned must terminate the ones
    # already started (the closure sees each Popen as it is appended).
    def _terminate(signum, frame):
        for p in procs:
            if p.poll() is None:
                p.terminate()
    signal.signal(signal.SIGINT, _terminate)
    signal.signal(signal.SIGTERM, _terminate)

    user_args = list(args.user_args)
    if user_args and user_args[0] == "--":
        user_args = user_args[1:]
    for env_delta in rank_envs:
        env = {**os.environ, **env_delta}
        cmd = [sys.executable, "-u", args.user_script,
               f"--local_rank={env_delta['LOCAL_RANK']}"] + user_args
        procs.append(subprocess.Popen(cmd, env=env))

    # Wait; on any child failure, kill the rest and propagate its code.
    exit_code = 0
    alive = list(procs)
    while alive:
        for p in list(alive):
            rc = p.poll()
            if rc is None:
                continue
            alive.remove(p)
            if rc != 0 and exit_code == 0:
                exit_code = rc
                logger.error("child %d exited with %d; terminating peers",
                             p.pid, rc)
                for q in alive:
                    q.terminate()
        time.sleep(0.1)
    return exit_code


if __name__ == "__main__":
    sys.exit(main())
