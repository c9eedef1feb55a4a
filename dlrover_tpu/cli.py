"""``dlrover-tpu-run`` — the elastic launcher CLI.

Parity: reference ``trainer/torch/elastic_run.py`` (``dlrover-run``): a
torchrun-style launcher extended with ``--network-check`` /
``--node_unit`` / ``--exclude-straggler``; when no master address is given
and this is node rank 0, a local master subprocess is booted automatically
(reference ``elastic_run.py:185-210``).

Usage::

    dlrover-tpu-run --standalone --nproc_per_node=1 train.py [args...]
    dlrover-tpu-run --nnodes=2:4 --network-check train.py [args...]
"""

import argparse
import atexit
import os
import subprocess
import sys
import tempfile
import time
from typing import List, Optional, Tuple

from dlrover_tpu.agent import tpu_chips
from dlrover_tpu.agent.agent import ElasticLaunchConfig, launch_agent
from dlrover_tpu.agent.master_client import MasterClient
from dlrover_tpu.common.backoff import ExponentialBackoff
from dlrover_tpu.common.constants import NodeEnv
from dlrover_tpu.common.log import logger


def parse_nnodes(value: str) -> Tuple[int, int]:
    if ":" in value:
        lo, hi = value.split(":", 1)
        return int(lo), int(hi)
    n = int(value)
    return n, n


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        "dlrover-tpu-run", description="TPU-native elastic launcher"
    )
    p.add_argument("--standalone", action="store_true",
                   help="single-node mode with an auto-started local master")
    p.add_argument("--nnodes", type=str, default="1",
                   help="number of nodes or MIN:MAX range")
    p.add_argument("--nproc_per_node", type=int, default=1)
    p.add_argument("--node_rank", type=int,
                   default=int(os.getenv(NodeEnv.NODE_RANK, "0")))
    p.add_argument("--master_addr", type=str,
                   default=os.getenv(NodeEnv.MASTER_ADDR, ""))
    p.add_argument("--job_name", type=str,
                   default=os.getenv(NodeEnv.JOB_NAME, "local-job"))
    p.add_argument("--max_restarts", type=int, default=3)
    p.add_argument("--monitor_interval", type=float, default=1.0)
    p.add_argument("--rdzv_timeout", type=float, default=600.0)
    p.add_argument("--waiting_timeout", type=float, default=30.0)
    p.add_argument("--network-check", dest="network_check",
                   action="store_true",
                   help="run the pre-flight device/ICI check round")
    p.add_argument("--exclude-straggler", dest="exclude_straggler",
                   action="store_true")
    p.add_argument("--node_unit", type=int, default=1)
    p.add_argument("--log_dir", type=str, default="")
    p.add_argument("entrypoint", type=str, help="training script")
    p.add_argument("script_args", nargs=argparse.REMAINDER)
    return p


def _launch_local_master(job_name: str, node_num: int) -> Tuple[subprocess.Popen, str]:
    """Boot a master subprocess on this host and wait for its port."""
    port_file = tempfile.mktemp(prefix="dlrover_tpu_master_port_")
    proc = subprocess.Popen(
        [
            sys.executable, "-m", "dlrover_tpu.master.main",
            "--port", "0",
            "--node_num", str(node_num),
            "--job_name", job_name,
            "--port_file", port_file,
        ],
        start_new_session=True,
    )
    deadline = time.monotonic() + 30
    backoff = ExponentialBackoff(initial=0.02, max_delay=0.5)
    while time.monotonic() < deadline:
        try:
            with open(port_file) as f:
                content = f.read().strip()
        except FileNotFoundError:
            content = ""
        if content:
            os.unlink(port_file)
            return proc, f"127.0.0.1:{content}"
        if proc.poll() is not None:
            raise RuntimeError("local master exited during startup")
        backoff.sleep(deadline - time.monotonic())
    raise TimeoutError("local master did not report its port in 30s")


def run(args) -> int:
    min_nodes, max_nodes = parse_nnodes(args.nnodes)
    if args.standalone:
        min_nodes = max_nodes = 1
    try:
        tpu_chips.check_layout(
            args.nproc_per_node, max_nodes, tpu_chips.count_tpu_chips()
        )
    except ValueError as e:
        raise SystemExit(str(e))

    master_proc: Optional[subprocess.Popen] = None
    master_addr = args.master_addr
    if not master_addr:
        if args.node_rank == 0:
            master_proc, master_addr = _launch_local_master(
                args.job_name, max_nodes
            )
            logger.info("auto-started local master at %s", master_addr)
            atexit.register(master_proc.terminate)
        else:
            raise SystemExit(
                "--master_addr is required on non-zero node ranks"
            )

    os.environ[NodeEnv.MASTER_ADDR] = master_addr
    os.environ[NodeEnv.NODE_ID] = str(args.node_rank)
    os.environ[NodeEnv.NODE_RANK] = str(args.node_rank)
    os.environ[NodeEnv.JOB_NAME] = args.job_name
    MasterClient.reset()

    config = ElasticLaunchConfig(
        min_nodes=min_nodes,
        max_nodes=max_nodes,
        nproc_per_node=args.nproc_per_node,
        node_rank=args.node_rank,
        job_name=args.job_name,
        rdzv_timeout=args.rdzv_timeout,
        waiting_timeout=args.waiting_timeout,
        monitor_interval=args.monitor_interval,
        max_restarts=args.max_restarts,
        network_check=args.network_check,
        exclude_straggler=args.exclude_straggler,
        node_unit=args.node_unit,
        log_dir=args.log_dir,
    )
    script_args = [a for a in args.script_args if a != "--"]
    code = launch_agent(config, args.entrypoint, script_args)

    if master_proc is not None:
        # The agent's final node status lets a local master finish on its
        # own within a poll or two. Give it that: a job-exit report sent
        # to a master that has just gone retries for the whole outage
        # window (two minutes) before giving up.
        try:
            master_proc.wait(timeout=5)
        except subprocess.TimeoutExpired:
            pass
    if master_proc is None or master_proc.poll() is None:
        client = MasterClient.singleton_instance()
        try:
            client.report_job_exit(success=(code == 0))
        except Exception:
            logger.warning("job-exit report to master failed", exc_info=True)
    if master_proc is not None:
        try:
            master_proc.wait(timeout=10)
        except subprocess.TimeoutExpired:
            master_proc.terminate()
    return code


def main(argv=None) -> int:
    argv = list(sys.argv[1:]) if argv is None else list(argv)
    if argv and argv[0] == "timeline":
        # Subcommand, intercepted before the launcher parser (whose
        # required positional entrypoint would swallow it):
        #   dlrover-tpu-run timeline --state-dir DIR [--chrome-out F]
        from dlrover_tpu.observability.timeline import main as timeline_main

        return timeline_main(argv[1:])
    args = build_parser().parse_args(argv)
    return run(args)


if __name__ == "__main__":
    sys.exit(main())
