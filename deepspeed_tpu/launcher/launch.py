"""Per-node process spawner — reference ``launcher/launch.py:133 main``.

Reference behavior: decode base64 world-info, set CUDA_VISIBLE_DEVICES-like
env via the accelerator (:166), export RANK/LOCAL_RANK/MASTER_*, fork one
subprocess per local device, fan out signals, write pid files.

TPU-native: JAX wants **one process per host** that owns every local chip
(SPMD), so the default is a single child per node with
``JAX_PROCESS_COUNT = num_nodes`` and ``COORDINATOR_ADDRESS`` rendezvous.
``--one_proc_per_device`` restores the reference's process-per-device layout
for tools that need it; on a TPU host each child is told which chip is its
own (:func:`tpu_one_chip_env`), or the chips would all go to the first child
to start.  Both MASTER_* and COORDINATOR_ADDRESS spellings are exported.

This process never initialises a JAX backend — the chips belong to the
workers it starts.
"""

import argparse
import os
import signal
import subprocess
import sys
import time

from ..utils.logging import logger
from .runner import decode_world_info, local_chip_count

PID_FILE_BASEPATH = "/tmp"

#: libtpu's grid of one-chip processes on one host, by the host's chip
#: count.  Only layouts that have been run are listed: one chip, and the
#: four chips (2x2) of a v5e host.
TPU_PROCESS_BOUNDS = {1: "1,1,1", 4: "2,2,1"}
TPU_PROCESS_PORT_BASE = 8476


def tpu_one_chip_env(n, local_rank):
    """What libtpu needs, beside ``TPU_VISIBLE_DEVICES``, to give this child
    exactly one chip of a host whose ``n`` chips are shared out one per
    process, while the processes still form one slice (they rendezvous with each other on
    ``TPU_PROCESS_ADDRESSES``; jax.distributed sits on top as usual).
    Run on a four-chip v5e host: each child sees one local and four global
    devices.  ``jax.process_index()`` there follows the chip's position in
    the slice, not RANK (ranks 0,1,2,3 came up as processes 0,2,3,1)."""
    if n not in TPU_PROCESS_BOUNDS:
        raise ValueError(
            f"--one_proc_per_device on a TPU host is set up for "
            f"{sorted(TPU_PROCESS_BOUNDS)} chips per host, not {n}: the "
            "process grid libtpu needs for that layout has not been "
            "established — run the default one-process-per-host layout")
    return {
        "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
        "TPU_PROCESS_BOUNDS": TPU_PROCESS_BOUNDS[n],
        "TPU_PROCESS_ADDRESSES": ",".join(
            f"localhost:{TPU_PROCESS_PORT_BASE + i}" for i in range(n)),
        "TPU_PROCESS_PORT": str(TPU_PROCESS_PORT_BASE + local_rank),
        "CLOUD_TPU_TASK_ID": str(local_rank),
    }


def parse_args(args=None):
    parser = argparse.ArgumentParser()
    parser.add_argument("--world_info", type=str, required=True)
    parser.add_argument("--node_rank", type=int,
                        default=int(os.environ.get(
                            "NODE_RANK",
                            os.environ.get(
                                "OMPI_COMM_WORLD_RANK",
                                os.environ.get(
                                    "SLURM_PROCID",
                                    # MPICH/IMPI Hydra + MVAPICH mpirun_rsh
                                    os.environ.get(
                                        "PMI_RANK",
                                        os.environ.get(
                                            "MV2_COMM_WORLD_RANK", 0)))))))
    parser.add_argument("--master_addr", type=str, default="127.0.0.1")
    parser.add_argument("--master_port", type=int, default=29500)
    parser.add_argument("--one_proc_per_device", action="store_true")
    parser.add_argument("--bind_cores_to_rank", action="store_true",
                        help="numactl-bind each local process to its core "
                        "slice (reference utils/numa.py get_numactl_cmd).")
    parser.add_argument("--bind_core_list", type=str, default=None,
                        help="Restrict binding to these cores, e.g. "
                        "'0-27,32-59'.")
    parser.add_argument("--no_python", action="store_true")
    parser.add_argument("--module", action="store_true")
    parser.add_argument("--enable_elastic_training", action="store_true")
    parser.add_argument("--min_elastic_nodes", type=int, default=-1)
    parser.add_argument("--max_elastic_nodes", type=int, default=-1)
    parser.add_argument("--stall_timeout", type=float, default=0.0,
                        help="Elastic watchdog: kill+relaunch a worker "
                        "whose newest heartbeat is older than this many "
                        "seconds (0 disables hang detection; set well "
                        "above first-step compile time).")
    parser.add_argument("--heartbeat_dir", type=str, default=None,
                        help="Directory for worker heartbeat files "
                        "(exported to workers as DS_TPU_HEARTBEAT_DIR; "
                        "default: a per-agent tempdir).")
    parser.add_argument("--restart_backoff", type=float, default=1.0,
                        help="Base seconds of exponential backoff between "
                        "elastic restarts (doubles per restart, capped).")
    parser.add_argument("--save_pid", action="store_true")
    parser.add_argument("training_script", type=str)
    parser.add_argument("training_script_args", nargs=argparse.REMAINDER)
    return parser.parse_args(args=args)


def build_child_env(args, world_info, node_rank, local_rank, procs_per_node):
    """Environment for one child process."""
    hosts = list(world_info.keys())
    num_nodes = len(hosts)
    env = os.environ.copy()
    coordinator = f"{args.master_addr}:{args.master_port}"

    if procs_per_node == 1:
        # JAX SPMD: process == host
        world_size = num_nodes
        rank = node_rank
        env["JAX_PROCESS_COUNT"] = str(world_size)
        env["JAX_PROCESS_ID"] = str(rank)
    else:
        world_size = sum(len(s) for s in world_info.values())
        rank = sum(
            len(world_info[h]) for h in hosts[:node_rank]) + local_rank
        env["JAX_PROCESS_COUNT"] = str(world_size)
        env["JAX_PROCESS_ID"] = str(rank)
        slots = world_info[hosts[node_rank]]
        env["TPU_VISIBLE_DEVICES"] = str(slots[local_rank])
        if local_chip_count():
            if num_nodes > 1:
                raise ValueError(
                    "--one_proc_per_device on TPU hosts is set up for one "
                    "host: the cross-host process grid has not been "
                    "established — run one process per host")
            env.update(tpu_one_chip_env(len(slots), local_rank))

    if world_size > 1:
        env["COORDINATOR_ADDRESS"] = coordinator
    # torch-style spellings for user scripts that read them
    env["MASTER_ADDR"] = args.master_addr
    env["MASTER_PORT"] = str(args.master_port)
    env["WORLD_SIZE"] = str(world_size)
    env["RANK"] = str(rank)
    env["LOCAL_RANK"] = str(local_rank)
    env["CROSS_RANK"] = str(node_rank)
    env["CROSS_SIZE"] = str(num_nodes)
    env["LOCAL_SIZE"] = str(procs_per_node)
    return env


def main(args=None):
    args = parse_args(args)
    world_info = decode_world_info(args.world_info)
    hosts = list(world_info.keys())
    node_rank = args.node_rank
    assert 0 <= node_rank < len(hosts), \
        f"node_rank {node_rank} out of range for {len(hosts)} hosts"
    procs_per_node = (len(world_info[hosts[node_rank]])
                      if args.one_proc_per_device else 1)

    def child_cmd():
        cmd = []
        if not args.no_python:
            cmd = [sys.executable, "-u"]
            if args.module:
                cmd.append("-m")
        cmd.append(args.training_script)
        cmd.extend(args.training_script_args)
        return cmd

    if args.enable_elastic_training:
        # restart supervision (reference DSElasticAgent via torchelastic,
        # elasticity/elastic_agent.py:32): relaunch failed workers; state
        # recovery = checkpoint+resume in the training script
        from ..elasticity.elastic_agent import DSElasticAgent
        if procs_per_node != 1:
            logger.warning(
                "elastic training supervises one worker per node; "
                "--one_proc_per_device (%d local devices) is ignored — the "
                "worker owns all local chips",
                procs_per_node)
        env = build_child_env(args, world_info, node_rank, 0, 1)
        agent = DSElasticAgent(child_cmd(), env, ds_config=None,
                               min_nodes=args.min_elastic_nodes,
                               max_nodes=args.max_elastic_nodes,
                               heartbeat_dir=args.heartbeat_dir,
                               stall_timeout=args.stall_timeout,
                               restart_backoff=args.restart_backoff)
        sys.exit(agent.run(world_size=len(hosts)))

    processes = []
    for local_rank in range(procs_per_node):
        env = build_child_env(args, world_info, node_rank, local_rank,
                              procs_per_node)
        cmd = child_cmd()
        if args.bind_cores_to_rank:
            # keep the host-optimizer/aio threads NUMA-local per process
            from ..utils.numa import get_numactl_cmd
            prefix, per_rank = get_numactl_cmd(args.bind_core_list,
                                               procs_per_node, local_rank)
            env.setdefault("OMP_NUM_THREADS", str(per_rank))
            cmd = prefix + cmd
        logger.info("launching rank %s: %s", env["RANK"], " ".join(cmd))
        processes.append(subprocess.Popen(cmd, env=env))

    if args.save_pid:
        pid_path = os.path.join(PID_FILE_BASEPATH,
                                f"ds_launch_{os.getpid()}.pids")
        with open(pid_path, "w") as f:
            f.write(",".join(str(p.pid) for p in processes))

    def sigkill_handler(signum, frame):
        # reference launch.py:317 — fan the signal out and die
        for p in processes:
            if p.poll() is None:
                p.send_signal(signum)
        sys.exit(128 + signum)

    signal.signal(signal.SIGINT, sigkill_handler)
    signal.signal(signal.SIGTERM, sigkill_handler)

    # monitor: if any child fails, kill the rest (reference sigkill_handler)
    alive = list(processes)
    rc = 0
    while alive:
        for p in list(alive):
            ret = p.poll()
            if ret is None:
                continue
            alive.remove(p)
            if ret != 0:
                rc = ret
                logger.error("child %s exited with %s — terminating node",
                             p.pid, ret)
                for q in alive:
                    if q.poll() is None:
                        q.terminate()
                alive = []
                break
        time.sleep(0.5)
    sys.exit(rc)


if __name__ == "__main__":
    main()
