"""Cluster launcher.

Reference: ``deepspeed`` CLI (launcher/runner.py:436 -> per-node
launch.py:145): hostfile parsing, include/exclude filters, pdsh/mpirun
multi-node, per-device process spawn with RANK/WORLD_SIZE env.

TPU model: ONE process per host (JAX drives all local chips), rendezvous via
``jax.distributed`` — the launcher assigns DSTPU_COORDINATOR /
DSTPU_NUM_PROCESSES / DSTPU_PROCESS_ID and execs the training script on
every host (ssh for multi-host, plain subprocess for single).
"""

from __future__ import annotations

import argparse
import base64
import json
import os
import shlex
import subprocess
import sys
from collections import OrderedDict
from typing import Dict, List, Optional

from ..utils.logging import logger

DEFAULT_COORD_PORT = 29500


def parse_hostfile(path_or_text: str, is_text: bool = False) -> "OrderedDict[str, int]":
    """``host slots=N`` per line (reference fetch_hostfile, runner.py:230)."""
    text = path_or_text if is_text else open(path_or_text).read()
    hosts: "OrderedDict[str, int]" = OrderedDict()
    for lineno, line in enumerate(text.splitlines(), 1):
        line = line.split("#")[0].strip()
        if not line:
            continue
        parts = line.split()
        host = parts[0]
        slots = 1
        for p in parts[1:]:
            if p.startswith("slots="):
                slots = int(p.split("=", 1)[1])
        if host in hosts:
            raise ValueError(f"hostfile line {lineno}: duplicate host {host}")
        hosts[host] = slots
    if not hosts:
        raise ValueError("hostfile is empty")
    return hosts


def filter_hosts(hosts: "OrderedDict[str, int]", include: str = "",
                 exclude: str = "") -> "OrderedDict[str, int]":
    """``--include host1@host2`` / ``--exclude`` (reference parse_inclusion_exclusion,
    runner.py:310).  Slot-level filters (host:0,1) select chip subsets — on
    TPU chips aren't individually addressable per process, so only
    whole-host filtering is supported."""
    def parse(sel: str) -> List[str]:
        return [h.split(":")[0] for h in sel.split("@") if h]

    out = OrderedDict(hosts)
    if include:
        keep = parse(include)
        unknown = [h for h in keep if h not in hosts]
        if unknown:
            raise ValueError(f"--include hosts not in hostfile: {unknown}")
        out = OrderedDict((h, hosts[h]) for h in hosts if h in keep)
    if exclude:
        drop = parse(exclude)
        unknown = [h for h in drop if h not in hosts]
        if unknown:
            raise ValueError(f"--exclude hosts not in hostfile: {unknown}")
        out = OrderedDict((h, s) for h, s in out.items() if h not in drop)
    if not out:
        raise ValueError("no hosts remain after include/exclude filtering")
    return out


def build_launch_commands(hosts: "OrderedDict[str, int]", script: str,
                          script_args: List[str], master_addr: Optional[str] = None,
                          master_port: int = DEFAULT_COORD_PORT,
                          export_env: Optional[Dict[str, str]] = None,
                          ssh_port: int = 22) -> List[List[str]]:
    """One command per host (reference PDSHRunner.get_cmd equivalent).

    A host's TPU chips belong to ONE process (which drives all of them),
    and nothing here divides chips between ranks — so two ranks on this
    machine are refused unless the job is pinned to the CPU platform (the
    rendezvous tests)."""
    master_addr = master_addr or next(iter(hosts))
    n = len(hosts)
    n_local = sum(h in ("localhost", "127.0.0.1") for h in hosts)
    platforms = (export_env or {}).get(
        "JAX_PLATFORMS", os.environ.get("JAX_PLATFORMS", ""))
    if n_local > 1 and platforms != "cpu":
        raise ValueError(
            f"{n_local} ranks on this host would each claim every local TPU "
            "chip, and a chip belongs to one process: launch one rank per "
            "host (one process drives all local chips), or set "
            "JAX_PLATFORMS=cpu for a CPU-only rendezvous")
    cmds = []
    for pid, host in enumerate(hosts):
        env = {
            "DSTPU_COORDINATOR": f"{master_addr}:{master_port}",
            "DSTPU_NUM_PROCESSES": str(n),
            "DSTPU_PROCESS_ID": str(pid),
            "DSTPU_LOCAL_RANK": "0",
        }
        env.update(export_env or {})
        envstr = " ".join(f"{k}={shlex.quote(v)}" for k, v in env.items())
        inner = f"cd {shlex.quote(os.getcwd())} && {envstr} " \
                f"{shlex.quote(sys.executable)} -u {shlex.quote(script)} " + \
                " ".join(shlex.quote(a) for a in script_args)
        local = host in ("localhost", "127.0.0.1")
        if local and all(h in ("localhost", "127.0.0.1") for h in hosts):
            # ALL-local job (the reference's local num_gpus>1 launch):
            # spawn directly, no sshd needed.  Mixed local/remote jobs ssh
            # every rank so each gets the same clean login environment —
            # a bash-spawned local rank inheriting the launcher's shell
            # (XLA_FLAGS etc.) while remote ranks don't would desync the
            # rendezvous topology.
            cmds.append(["bash", "-c", inner])
        else:
            cmds.append(["ssh", "-p", str(ssh_port), host, inner])
    return cmds


def main(argv: Optional[List[str]] = None) -> int:
    parser = argparse.ArgumentParser("deepspeed_tpu.launcher")
    parser.add_argument("--hostfile", default=None)
    parser.add_argument("--include", default="")
    parser.add_argument("--exclude", default="")
    parser.add_argument("--master_addr", default=None)
    parser.add_argument("--master_port", type=int, default=DEFAULT_COORD_PORT)
    parser.add_argument("--ssh_port", type=int, default=22)
    parser.add_argument("--force_multi", action="store_true")
    parser.add_argument("--launcher", default="ssh",
                        choices=["ssh", "pdsh", "openmpi", "mpich", "impi",
                                 "slurm", "mvapich"],
                        help="multinode backend (reference multinode_runner)")
    parser.add_argument("--elastic_training", action="store_true",
                        help="watchdog relaunch on failure with per-attempt "
                             "host re-discovery (reference DSElasticAgent)")
    parser.add_argument("--max_elastic_restarts", type=int, default=3)
    parser.add_argument("script")
    parser.add_argument("script_args", nargs=argparse.REMAINDER)
    args = parser.parse_args(argv)

    if args.elastic_training:
        if args.launcher != "ssh":
            parser.error("--elastic_training currently relaunches over "
                         "ssh only; --launcher "
                         f"{args.launcher} is not supported with it")
        from ..elasticity.elastic_agent import ElasticAgent

        agent = ElasticAgent(hostfile=args.hostfile, include=args.include,
                             exclude=args.exclude,
                             max_restarts=args.max_elastic_restarts,
                             master_addr=args.master_addr,
                             master_port=args.master_port,
                             ssh_port=args.ssh_port)
        return agent.run(args.script, args.script_args)

    if args.hostfile:
        hosts = filter_hosts(parse_hostfile(args.hostfile), args.include, args.exclude)
    else:
        hosts = OrderedDict([("localhost", 1)])

    if args.launcher != "ssh":
        from .multinode_runner import get_runner

        runner = get_runner(args.launcher, hosts,
                            master_addr=args.master_addr,
                            master_port=args.master_port)
        cmd = runner.get_cmd(args.script, args.script_args)
        logger.info(f"launcher[{args.launcher}]: {' '.join(cmd)}")
        return subprocess.call(cmd)

    cmds = build_launch_commands(hosts, args.script, args.script_args,
                                 args.master_addr, args.master_port,
                                 ssh_port=args.ssh_port)
    procs = [subprocess.Popen(cmd) for cmd in cmds]
    rc = 0
    for p in procs:
        p.wait()
        rc = rc or p.returncode
    return rc


if __name__ == "__main__":
    sys.exit(main())
