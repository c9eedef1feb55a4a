"""End-to-end elastic launch tests: standalone run, crash-restart, 2-node world.

Mirrors the reference's agent e2e strategy (SURVEY.md §4.1): a real master,
real agents, real worker processes — all on localhost with CPU JAX.
"""

import os
import subprocess
import sys
import time
import uuid

import pytest

from tests.conftest import cpu_subprocess_env as _env

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(REPO, "examples", "train_tiny.py")


def _run_cli(cli_args, extra_env=None, timeout=180):
    cmd = [sys.executable, "-m", "dlrover_tpu.cli", *cli_args]
    return subprocess.run(
        cmd, env=_env(extra_env), timeout=timeout,
        capture_output=True, text=True,
    )


@pytest.mark.e2e
class TestElasticRun:
    def test_standalone_run_succeeds(self, tmp_path):
        job = f"e2e-{uuid.uuid4().hex[:6]}"
        result = _run_cli(
            [
                "--standalone", "--nproc_per_node=1", f"--job_name={job}",
                "--monitor_interval=0.2", SCRIPT, "--", "--steps", "5",
            ],
        )
        assert result.returncode == 0, result.stderr[-2000:]

    def test_standalone_with_network_check(self):
        """--network-check runs the device-check round before training."""
        job = f"e2e-{uuid.uuid4().hex[:6]}"
        result = _run_cli(
            [
                "--standalone", "--nproc_per_node=1", f"--job_name={job}",
                "--monitor_interval=0.2", "--network-check",
                SCRIPT, "--", "--steps", "3",
            ],
            extra_env={"DLROVER_TPU_CHECK_MATMUL_SIZE": "128"},
        )
        assert result.returncode == 0, result.stderr[-2000:]

    # Promoted to slow: ~123s of subprocess churn, the single largest
    # tier-1 cost after the two-node drill; the crash→flash-restore
    # chain stays covered in-process (test_checkpoint, test_state_store)
    # and by the shm-restore unit drills.
    @pytest.mark.slow
    def test_crash_restart_resumes_from_flash_checkpoint(self, tmp_path):
        """The core goodput scenario: every-step MEMORY snapshots, DISK
        persist every 10 steps, crash at step 7. The agent flushes the step-7
        memory snapshot to storage; the restarted worker resumes model +
        optimizer state from step 7 — NOT from the last disk persist and not
        from scratch. The trainer itself asserts its step counter reached
        --steps through the restart."""
        job = f"e2e-{uuid.uuid4().hex[:6]}"
        sentinel = str(tmp_path / "crash.sentinel")
        ckpt_dir = str(tmp_path / "ckpts")
        marker = str(tmp_path / "resumed_from.txt")
        result = _run_cli(
            [
                "--standalone", "--nproc_per_node=1", f"--job_name={job}",
                "--monitor_interval=0.2", "--max_restarts=2",
                SCRIPT, "--",
                "--steps", "12", "--crash-at", "7",
                "--crash-sentinel", sentinel,
                "--ckpt-dir", ckpt_dir, "--persist-every", "10",
                "--resume-marker", marker,
            ],
        )
        assert result.returncode == 0, result.stderr[-2000:]
        assert os.path.exists(f"{sentinel}.7"), "crash was never injected"
        assert os.path.exists(marker), "worker never resumed from checkpoint"
        with open(marker) as f:
            resumed = int(f.read())
        assert resumed == 7, f"resumed from {resumed}, expected 7"
        # The step-7 dir on disk proves the crash-FLUSH path specifically:
        # no periodic DISK save could have created it (persist-every=10),
        # and the memory-restore path alone would not touch storage.
        assert os.path.isdir(os.path.join(ckpt_dir, "checkpoint-7")), (
            "agent crash flush never persisted the step-7 memory snapshot"
        )

    def test_crash_restart_with_dataloader(self, tmp_path):
        """Same goodput scenario driven through the elastic data layer:
        the worker consumes master-dispatched shards via ElasticDataLoader;
        the crash leaves a shard in `doing`; the agent's failure report
        recovers it, and the restarted worker trains to completion (a
        blocking fetch would hang here if recovery were broken)."""
        job = f"e2e-{uuid.uuid4().hex[:6]}"
        sentinel = str(tmp_path / "crash.sentinel")
        ckpt_dir = str(tmp_path / "ckpts")
        marker = str(tmp_path / "resumed_from.txt")
        result = _run_cli(
            [
                "--standalone", "--nproc_per_node=1", f"--job_name={job}",
                "--monitor_interval=0.2", "--max_restarts=2",
                SCRIPT, "--",
                "--steps", "12", "--use-dataloader", "--crash-at", "7",
                "--crash-sentinel", sentinel,
                "--ckpt-dir", ckpt_dir, "--persist-every", "10",
                "--resume-marker", marker,
            ],
        )
        assert result.returncode == 0, result.stderr[-2000:]
        assert os.path.exists(f"{sentinel}.7"), "crash was never injected"
        with open(marker) as f:
            assert int(f.read()) == 7

    # Promoted to slow: at ~75s this was the single largest tier-1 cost
    # and the eviction/re-form path stays covered by the faster
    # in-process drills (test_rescale, test_reshape).
    @pytest.mark.slow
    def test_permanent_node_loss_survivor_reforms(self, tmp_path):
        """Kill one of two agents (and its worker) with NO failure report:
        the master's heartbeat monitor evicts the node, invalidates the
        round, and the survivor re-forms a 1-node world from the flash
        checkpoint and finishes the job."""
        import signal
        import subprocess as sp

        job = f"e2e-{uuid.uuid4().hex[:6]}"
        port_file = str(tmp_path / "port")
        ckpt_dir = str(tmp_path / "ckpts")
        marker = str(tmp_path / "resumed.txt")
        env = _env({
            "DLROVER_TPU_HEARTBEAT_TIMEOUT": "2",
            "DLROVER_TPU_NODE_MONITOR_INTERVAL": "0.3",
        })
        master = subprocess.Popen(
            [
                sys.executable, "-m", "dlrover_tpu.master.main",
                "--node_num", "2", "--job_name", job,
                "--port_file", port_file,
            ],
            env=env,
        )
        agents = []
        try:
            deadline = time.monotonic() + 30
            while not os.path.exists(port_file):
                assert time.monotonic() < deadline, "master never started"
                time.sleep(0.05)
            with open(port_file) as f:
                addr = f"127.0.0.1:{f.read().strip()}"

            for rank in range(2):
                agents.append(
                    subprocess.Popen(
                        [
                            sys.executable, "-m", "dlrover_tpu.cli",
                            "--nnodes=1:2", "--nproc_per_node=1",
                            f"--node_rank={rank}", f"--master_addr={addr}",
                            f"--job_name={job}", "--monitor_interval=0.2",
                            "--waiting_timeout=2", "--max_restarts=3",
                            SCRIPT, "--", "--steps", "40",
                            "--step-sleep", "0.25",
                            "--ckpt-dir", ckpt_dir, "--persist-every", "50",
                            "--resume-marker", marker,
                        ],
                        env=_env(), stdout=subprocess.PIPE,
                        stderr=subprocess.STDOUT, text=True,
                    )
                )
            # Wait until BOTH workers are actually training (their flash
            # ckpt shm appears after the first memory save) — a fixed
            # sleep is load-sensitive when the suite saturates the CPU —
            # then hard-kill agent 1 and its worker children (simulated
            # host loss — no report).
            import glob

            deadline = time.monotonic() + 120
            while time.monotonic() < deadline:
                if len(glob.glob(f"/dev/shm/ckpt_{job}_n*_rank0")) >= 2:
                    break
                time.sleep(0.5)
            assert len(glob.glob(f"/dev/shm/ckpt_{job}_n*_rank0")) >= 2, (
                "workers never started saving memory snapshots"
            )
            time.sleep(2)  # a few steps past the first snapshot
            victim = agents[1]
            kids = sp.run(
                ["pgrep", "-P", str(victim.pid)], capture_output=True,
                text=True,
            ).stdout.split()
            victim.kill()
            for pid in kids:
                try:
                    os.kill(int(pid), signal.SIGKILL)
                except (ProcessLookupError, ValueError):
                    pass
            out, _ = agents[0].communicate(timeout=240)
            assert agents[0].returncode == 0, out[-4000:]
            assert "re-forming" in out or "membership changed" in out, (
                out[-4000:]
            )
            assert os.path.exists(marker), (
                "survivor never resumed from the flash checkpoint\n"
                + out[-4000:]
            )
            master.wait(timeout=30)
            assert master.returncode == 0, "master did not exit success"
        finally:
            for a in agents:
                if a.poll() is None:
                    a.kill()
            if master.poll() is None:
                master.terminate()
                master.wait(timeout=10)

    # Promoted to slow: ~122s of subprocess churn, the largest tier-1
    # cost by 7x; two-node rendezvous coverage continues in the slow
    # lane alongside the other multi-process drills in this file.
    @pytest.mark.slow
    def test_two_node_world(self, tmp_path):
        """Two agents rendezvous through one master; workers form a
        2-process JAX world via jax.distributed."""
        job = f"e2e-{uuid.uuid4().hex[:6]}"
        port_file = str(tmp_path / "port")
        master = subprocess.Popen(
            [
                sys.executable, "-m", "dlrover_tpu.master.main",
                "--node_num", "2", "--job_name", job,
                "--port_file", port_file,
            ],
            env=_env(),
        )
        try:
            deadline = time.monotonic() + 30
            while not os.path.exists(port_file):
                assert time.monotonic() < deadline, "master never started"
                time.sleep(0.05)
            with open(port_file) as f:
                addr = f"127.0.0.1:{f.read().strip()}"

            agents = []
            for rank in range(2):
                agents.append(
                    subprocess.Popen(
                        [
                            sys.executable, "-m", "dlrover_tpu.cli",
                            "--nnodes=2", "--nproc_per_node=1",
                            f"--node_rank={rank}", f"--master_addr={addr}",
                            f"--job_name={job}", "--monitor_interval=0.2",
                            SCRIPT, "--", "--steps", "3",
                            "--expect-world", "2",
                        ],
                        env=_env(), stdout=subprocess.PIPE,
                        stderr=subprocess.STDOUT, text=True,
                    )
                )
            for a in agents:
                out, _ = a.communicate(timeout=180)
                assert a.returncode == 0, out[-3000:]
        finally:
            master.terminate()
            master.wait(timeout=10)

    # Promoted to slow: ~130s, the largest tier-1 cost; two-node
    # crash/restore coverage continues in the slow lane and the same
    # failover machinery is exercised in-process by the WAL-replay and
    # rescale drills.
    @pytest.mark.slow
    def test_two_node_flash_checkpoint_crash(self, tmp_path):
        """Multi-node flash checkpoint: both nodes snapshot to their shm
        every step; a crash on node 0 flushes, both agents restart their
        workers, and BOTH resume from the same flushed step (the
        step-consistency vote across nodes picks it). The step-7 dir must
        hold done-files/shards from both nodes under one tracker."""
        job = f"e2e-{uuid.uuid4().hex[:6]}"
        port_file = str(tmp_path / "port")
        ckpt_dir = str(tmp_path / "ckpts")
        sentinel = str(tmp_path / "crash.sentinel")
        markers = [str(tmp_path / f"resumed{r}.txt") for r in range(2)]
        master = subprocess.Popen(
            [
                sys.executable, "-m", "dlrover_tpu.master.main",
                "--node_num", "2", "--job_name", job,
                "--port_file", port_file,
            ],
            env=_env(),
        )
        try:
            deadline = time.monotonic() + 30
            while not os.path.exists(port_file):
                assert time.monotonic() < deadline, "master never started"
                time.sleep(0.05)
            with open(port_file) as f:
                addr = f"127.0.0.1:{f.read().strip()}"

            agents = []
            for rank in range(2):
                crash_args = (
                    ["--crash-at", "7", "--crash-sentinel", sentinel]
                    if rank == 0 else []
                )
                agents.append(
                    subprocess.Popen(
                        [
                            sys.executable, "-m", "dlrover_tpu.cli",
                            "--nnodes=2", "--nproc_per_node=1",
                            f"--node_rank={rank}", f"--master_addr={addr}",
                            f"--job_name={job}", "--monitor_interval=0.2",
                            "--max_restarts=2",
                            SCRIPT, "--", "--steps", "12", "--lockstep",
                            "--step-sleep", "0.1",
                            "--ckpt-dir", ckpt_dir, "--persist-every", "50",
                            "--resume-marker", markers[rank],
                            *crash_args,
                        ],
                        env=_env(), stdout=subprocess.PIPE,
                        stderr=subprocess.STDOUT, text=True,
                    )
                )
            outs = []
            for a in agents:
                out, _ = a.communicate(timeout=240)
                outs.append(out)
                assert a.returncode == 0, out[-4000:]
            assert os.path.exists(f"{sentinel}.7"), "crash was never injected"
            for r in range(2):
                assert os.path.exists(markers[r]), (
                    f"rank {r} never resumed\n" + outs[r][-3000:]
                )
                with open(markers[r]) as f:
                    resumed = int(f.read())
                assert resumed == 7, (
                    f"rank {r} resumed from {resumed}, expected the "
                    "crash-flushed step 7"
                )
            # The committed step-7 dir must hold BOTH nodes' shards and
            # done-files under one tracker (2-node commit).
            step7 = os.path.join(ckpt_dir, "checkpoint-7")
            for f in ("done_0", "done_1", "shard_0.bin", "shard_1.bin"):
                assert os.path.exists(os.path.join(step7, f)), (
                    f"missing {f} in the 2-node commit"
                )
        finally:
            for a in agents:
                if a.poll() is None:
                    a.kill()
            master.terminate()
            master.wait(timeout=10)


class TestMasterFailover:
    # Promoted to slow for tier-1 headroom (~16s of subprocess churn);
    # master-restart recovery itself is exercised in-process by the
    # state-store/WAL replay tests.
    @pytest.mark.slow
    def test_master_killed_and_relaunched_job_completes(self, tmp_path):
        """The master is the one per-job singleton: kill it mid-run and
        relaunch it at the same address (the reference's operator
        relaunching the master pod). Workers ride out the outage via
        the RPC client's retry window — the job must complete and the
        RELAUNCHED master must see the success report and exit 0."""
        job = f"mfail-{uuid.uuid4().hex[:6]}"
        port_file = str(tmp_path / "port")

        def start_master(port=0):
            args = [
                sys.executable, "-m", "dlrover_tpu.master.main",
                "--node_num", "1", "--job_name", job,
            ]
            if port:
                args += ["--port", str(port)]
            else:
                args += ["--port_file", port_file]
            return subprocess.Popen(args, env=_env())

        master = start_master()
        agent = None
        master2 = None
        try:
            deadline = time.monotonic() + 30
            while not os.path.exists(port_file):
                assert time.monotonic() < deadline, "master never started"
                time.sleep(0.05)
            with open(port_file) as f:
                port = int(f.read().strip())
            addr = f"127.0.0.1:{port}"

            agent = subprocess.Popen(
                [
                    sys.executable, "-m", "dlrover_tpu.cli",
                    "--nnodes=1", "--nproc_per_node=1",
                    "--node_rank=0", f"--master_addr={addr}",
                    f"--job_name={job}", "--monitor_interval=0.2",
                    "--max_restarts=2",
                    SCRIPT, "--", "--steps", "40",
                    "--step-sleep", "0.25",
                    "--ckpt-dir", str(tmp_path / "ckpts"),
                    "--persist-every", "50",
                ],
                env=_env(), stdout=subprocess.PIPE,
                stderr=subprocess.STDOUT, text=True,
            )
            # Let the worker actually train (first flash snapshot lands),
            # then kill the master mid-job.
            import glob

            deadline = time.monotonic() + 120
            while time.monotonic() < deadline:
                if glob.glob(f"/dev/shm/ckpt_{job}_n*_rank0"):
                    break
                time.sleep(0.5)
            assert glob.glob(f"/dev/shm/ckpt_{job}_n*_rank0"), (
                "worker never started saving snapshots"
            )
            time.sleep(2)
            master.kill()
            master.wait(timeout=10)
            time.sleep(3)  # a real outage, not an instant flip
            master2 = start_master(port=port)

            out, _ = agent.communicate(timeout=240)
            assert agent.returncode == 0, out[-4000:]
            master2.wait(timeout=30)
            assert master2.returncode == 0, (
                "relaunched master did not exit success"
            )
        finally:
            for p in (agent, master, master2):
                if p is not None and p.poll() is None:
                    p.kill()
                    p.wait(timeout=10)
