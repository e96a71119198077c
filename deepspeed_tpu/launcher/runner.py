"""``deepspeed`` CLI — multi-node launcher front-end.

Reference: ``launcher/runner.py`` (arg parsing :48, hostfile :213,
include/exclude filters :293, world-info encode :384, ``main`` :419 picks a
multinode backend and ``exec``s it).

TPU-native redesign: the unit of launch is a **host process driving all local
chips** (JAX SPMD convention), not one process per device.  A chip belongs
to one process at a time, so no launcher process (this runner, ``launch.py``,
the multinode runners, the elastic agent) ever initialises a JAX backend —
only the worker does.  Rendezvous is
``COORDINATOR_ADDRESS`` (``jax.distributed.initialize``) rather than
MASTER_ADDR/MASTER_PORT NCCL rendezvous — the launcher sets both spellings so
user scripts written against either work.  Single-node launches skip ssh and
exec ``launch.py`` directly.
"""

import argparse
import base64
import glob
import json
import os
import shlex
import subprocess
import sys
from collections import OrderedDict

from ..utils.logging import logger

DLTS_HOSTFILE = "/job/hostfile"
EXPORT_ENVS = ("PYTHONPATH", "PATH", "LD_LIBRARY_PATH", "JAX_PLATFORMS",
               "XLA_FLAGS", "LIBTPU_INIT_ARGS", "TPU_NAME", "DS_ACCELERATOR")


def parse_args(args=None):
    parser = argparse.ArgumentParser(
        description="deepspeed-tpu distributed launcher",
        formatter_class=argparse.ArgumentDefaultsHelpFormatter)
    parser.add_argument("-H", "--hostfile", type=str, default=DLTS_HOSTFILE,
                        help="Hostfile path: lines of '<host> slots=<n>'.")
    parser.add_argument("-i", "--include", type=str, default="",
                        help='Include filter, e.g. "worker-0@worker-1:0,2".')
    parser.add_argument("-e", "--exclude", type=str, default="",
                        help='Exclude filter, e.g. "worker-1:0".')
    parser.add_argument("--num_nodes", type=int, default=-1,
                        help="Limit to first N hosts of the resource pool.")
    parser.add_argument("--num_gpus", "--num_chips", dest="num_gpus",
                        type=int, default=-1,
                        help="Limit devices per node.")
    parser.add_argument("--master_port", type=int,
                        default=int(os.environ.get("DS_MASTER_PORT", 29500)),
                        help="Coordinator port.")
    parser.add_argument("--master_addr", type=str,
                        default=os.environ.get("DS_MASTER_ADDR", ""),
                        help="Coordinator address (default: first host).")
    parser.add_argument("--launcher", type=str, default="pdsh",
                        choices=("pdsh", "openmpi", "mpich", "impi",
                                 "mvapich", "slurm", "ssh", "local"),
                        help="Multinode backend.")
    parser.add_argument("--launcher_args", type=str, default="",
                        help="Extra args passed to the multinode backend.")
    parser.add_argument("--force_multi", action="store_true",
                        help="Treat as multi-node even for one host.")
    parser.add_argument("--autotuning", type=str, default="",
                        choices=("", "tune", "run"),
                        help="Run the autotuner to discover config.")
    parser.add_argument("--elastic_training", action="store_true",
                        help="Enable elastic batch/worker scheduling.")
    parser.add_argument("--one_proc_per_device", action="store_true",
                        help="Reference process-per-device layout instead "
                        "of the JAX one-process-per-host default "
                        "(forwarded to launch.py).")
    parser.add_argument("--no_python", action="store_true",
                        help="Run user_script directly (not via python).")
    parser.add_argument("--module", action="store_true",
                        help="Run user_script as a python module (-m).")
    parser.add_argument("--venv_script", type=str, default=None,
                        help="Activation script sourced before launch.")
    parser.add_argument("--bind_cores_to_rank", action="store_true",
                        help="numactl-bind each local process.")
    parser.add_argument("--bind_core_list", type=str, default=None,
                        help="Restrict binding to these cores, e.g. "
                        "'0-27,32-59'.")
    parser.add_argument("user_script", type=str,
                        help="User training script.")
    parser.add_argument("user_args", nargs=argparse.REMAINDER)
    return parser.parse_args(args=args)


def fetch_hostfile(hostfile_path):
    """'<host> slots=<n>' lines → OrderedDict host→slots (reference :213)."""
    if not os.path.isfile(hostfile_path):
        return None
    resource_pool = OrderedDict()
    with open(hostfile_path) as f:
        for line in f:
            line = line.split("#")[0].strip()
            if not line:
                continue
            try:
                hostname, slots = line.split()
                _, slot_count = slots.split("=")
                slot_count = int(slot_count)
            except ValueError:
                raise ValueError(f"Hostfile is not formatted correctly, "
                                 f"unable to parse line: {line!r}")
            if hostname in resource_pool:
                raise ValueError(f"Hostfile contains duplicate hosts: "
                                 f"{hostname}")
            resource_pool[hostname] = slot_count
    return resource_pool


def _parse_hostlist_entry(entry):
    if ":" in entry:
        host, slots = entry.split(":")
        return host, [int(x) for x in slots.split(",")]
    return entry, None


def parse_resource_filter(host_info, include_str="", exclude_str=""):
    """Apply '@'-separated host[:slot,slot] filters (reference :293)."""
    if include_str and exclude_str:
        raise ValueError("include_str and exclude_str are mutually exclusive")
    filtered = OrderedDict()
    if include_str:
        for entry in include_str.split("@"):
            host, slots = _parse_hostlist_entry(entry.strip())
            if host not in host_info:
                raise ValueError(f"Hostname '{host}' not found in hostfile")
            if slots is None:
                filtered[host] = host_info[host]
            else:
                for s in slots:
                    if s not in host_info[host]:
                        raise ValueError(
                            f"No slot '{s}' specified on host '{host}'")
                filtered[host] = sorted(slots)
        return filtered
    # exclude path: start from everything
    for host, slots in host_info.items():
        filtered[host] = slots
    if exclude_str:
        for entry in exclude_str.split("@"):
            host, slots = _parse_hostlist_entry(entry.strip())
            if host not in filtered:
                raise ValueError(f"Hostname '{host}' not found in hostfile")
            if slots is None:
                del filtered[host]
            else:
                remaining = [
                    s for s in host_info[host] if s not in slots
                ]
                if remaining:
                    filtered[host] = remaining
                else:
                    del filtered[host]
    return filtered


def parse_inclusion_exclusion(resource_pool, inclusion, exclusion):
    active_resources = OrderedDict()
    for hostname, slots in resource_pool.items():
        active_resources[hostname] = list(range(slots))
    return parse_resource_filter(active_resources, include_str=inclusion,
                                 exclude_str=exclusion)


def encode_world_info(world_info):
    """dict host→[slots] → base64 json (reference :384)."""
    return base64.urlsafe_b64encode(
        json.dumps(world_info).encode()).decode()


def decode_world_info(encoded):
    return json.loads(base64.urlsafe_b64decode(encoded).decode())


def local_chip_count():
    """TPU chips on this host, counted from the device nodes the driver
    exposes (``/dev/accel*``, or ``/dev/vfio/<n>`` on newer hosts) — never
    through JAX: a chip belongs to one process, and a launcher that asked
    JAX for its devices would hold the chip its own worker needs.
    0 on a host with no TPU."""
    return (len(glob.glob("/dev/accel[0-9]*"))
            or len(glob.glob("/dev/vfio/[0-9]*")))


def build_launch_command(args, active_resources):
    """Construct the per-node ``launch.py`` command (single-node path) or the
    multinode runner command."""
    from .multinode_runner import (IMPIRunner, MPICHRunner, MVAPICHRunner,
                                   OpenMPIRunner, PDSHRunner, SlurmRunner,
                                   SSHRunner)
    world_info = encode_world_info(active_resources)
    multi_node = args.force_multi or len(active_resources) > 1
    if not multi_node:
        cmd = [
            sys.executable, "-u", "-m", "deepspeed_tpu.launcher.launch",
            f"--world_info={world_info}",
            f"--master_addr={args.master_addr or 'localhost'}",
            f"--master_port={args.master_port}",
        ]
        if args.one_proc_per_device:
            cmd.append("--one_proc_per_device")
        if args.bind_cores_to_rank:
            cmd.append("--bind_cores_to_rank")
            if args.bind_core_list:
                cmd.append(f"--bind_core_list={args.bind_core_list}")
        if args.no_python:
            cmd.append("--no_python")
        if args.module:
            cmd.append("--module")
        if args.elastic_training:
            cmd.append("--enable_elastic_training")
        cmd.append(args.user_script)
        cmd.extend(args.user_args)
        return cmd

    runner_cls = {"pdsh": PDSHRunner, "openmpi": OpenMPIRunner,
                  "mpich": MPICHRunner, "impi": IMPIRunner,
                  "mvapich": MVAPICHRunner,
                  "slurm": SlurmRunner, "ssh": SSHRunner}[args.launcher]
    runner = runner_cls(args, world_info)
    if not runner.backend_exists():
        raise RuntimeError(f"launcher backend {args.launcher} not installed")
    env = {k: os.environ[k] for k in EXPORT_ENVS if k in os.environ}
    return runner.get_cmd(env, active_resources)


def main(args=None):
    args = parse_args(args)

    if args.bind_core_list and not args.bind_cores_to_rank:
        logger.warning("--bind_core_list has no effect without "
                       "--bind_cores_to_rank; processes run unbound")

    if args.autotuning:
        from ..autotuning.autotuner import run_autotuning
        return run_autotuning(args)

    resource_pool = fetch_hostfile(args.hostfile)
    if resource_pool is None:
        # slots matter only to --one_proc_per_device and the include/
        # exclude filters: the default worker owns every local chip
        n = args.num_gpus if args.num_gpus > 0 else max(local_chip_count(), 1)
        resource_pool = OrderedDict(localhost=n)
    active_resources = parse_inclusion_exclusion(resource_pool, args.include,
                                                 args.exclude)
    if args.num_nodes > 0:
        active_resources = OrderedDict(
            list(active_resources.items())[:args.num_nodes])
    if args.num_gpus > 0:
        active_resources = OrderedDict(
            (h, s[:args.num_gpus]) for h, s in active_resources.items())
    if not args.master_addr:
        args.master_addr = next(iter(active_resources))
        if args.master_addr == "localhost":
            args.master_addr = "127.0.0.1"

    cmd = build_launch_command(args, active_resources)
    logger.info("cmd = %s", " ".join(map(shlex.quote, cmd)))
    result = subprocess.Popen(cmd)
    result.wait()
    sys.exit(result.returncode)


if __name__ == "__main__":
    main()
