"""Preloaded fork server: millisecond worker respawn.

The restart-latency breakdown (``train.bootstrap_timings``) shows a
relaunched worker spends ~2.2 s in ``spawn_s`` — CPython startup plus
importing jax/optax/numpy — dwarfing every other phase once the
persistent compile cache removes recompilation. The reference never
sees this because its unit of recovery is a pod; ours is a process, so
we can do what CPython's own ``multiprocessing`` forkserver does,
specialized for elastic training:

- the agent starts ONE template process per job
  (``python -m dlrover_tpu.agent.forkserver``) which imports the heavy
  modules and then blocks on a pipe — it never initializes a JAX
  backend, so forking it is safe (no XLA runtime threads to lose);
- each (re)start forks the template: the child gets the fully-imported
  interpreter for the price of a page-table copy (~10 ms), swaps in
  the worker env, redirects stdio, ``setsid()``s (the agent's
  process-group kill contract), and ``runpy``-executes the training
  script as ``__main__``;
- the template reaps its children and streams exit events back, so
  the agent-side :class:`ForkedWorker` handle offers the same
  ``poll``/``wait``/``pid`` surface as ``subprocess.Popen``.

Opt out with ``DLROVER_TPU_FORKSERVER=0`` (e.g. a worker whose
module-level imports must not run before env is set).
"""

import os
import pickle
import struct
import subprocess
import sys
import threading
import time
from typing import Any, Dict, List, Optional

from dlrover_tpu.common import env_utils
from dlrover_tpu.common.backoff import ExponentialBackoff
from dlrover_tpu.common.log import logger

_LEN = struct.Struct(">I")

#: Modules the template pre-imports. jax alone is ~1.5-2 s; the rest
#: round out the trainer stack's import closure.
PRELOAD = (
    "jax",
    "jax.numpy",
    "numpy",
    "optax",
    "dlrover_tpu.train",
    "dlrover_tpu.train.checkpoint",
    "dlrover_tpu.train.data",
    "dlrover_tpu.agent.master_client",
)


def _write_msg(f, obj: Any):
    data = pickle.dumps(obj)
    f.write(_LEN.pack(len(data)) + data)
    f.flush()


def _read_msg(f) -> Any:
    header = f.read(_LEN.size)
    if len(header) < _LEN.size:
        raise EOFError("fork server pipe closed")
    (n,) = _LEN.unpack(header)
    data = f.read(n)
    if len(data) < n:
        raise EOFError("fork server pipe closed mid-message")
    return pickle.loads(data)


# --------------------------------------------------------------------
# template-process side
# --------------------------------------------------------------------

def _child_main(req: Dict):
    """Runs in the forked child: become the worker process."""
    os.setsid()  # agent kills by process group
    log_path = req.get("log_path")
    if log_path:
        fd = os.open(log_path, os.O_WRONLY | os.O_CREAT | os.O_APPEND,
                     0o644)
        os.dup2(fd, 1)
        os.dup2(fd, 2)
        os.close(fd)
    os.environ.clear()
    os.environ.update(req["env"])
    # The template imported dlrover_tpu.train long ago; this process's
    # spawn phase starts NOW or the breakdown reports template age.
    try:
        import dlrover_tpu.train as _t

        _t._ENTRY_TS = time.time()
    except Exception:  # dtlint: disable=DT001 -- forked worker boot must never die on a metrics stamp
        pass
    import runpy

    sys.argv = [req["entrypoint"], *req["args"]]
    code = 0
    try:
        runpy.run_path(req["entrypoint"], run_name="__main__")
    except SystemExit as e:
        code = e.code if isinstance(e.code, int) else (
            0 if e.code is None else 1
        )
    except BaseException:
        import traceback

        traceback.print_exc()
        code = 1
    if code == 0:
        # A clean exit runs the worker's exit handlers the way a spawned
        # interpreter would. jax.distributed's shutdown barrier is one:
        # without it the first process to finish takes the coordination
        # service down under peers that are still closing, and they die.
        # A failed worker skips them — its peers may be gone, and the
        # barrier would wait for them.
        import atexit

        atexit._run_exitfuncs()
    os._exit(code)


def template_main():
    """Entry of ``python -m dlrover_tpu.agent.forkserver``."""
    for mod in PRELOAD:
        try:
            __import__(mod)
        except Exception as e:  # worker may not need it; keep going
            print(f"forkserver: preload {mod} failed: {e}",
                  file=sys.stderr, flush=True)
    # Move the agent protocol OFF fds 0/1: forked children inherit this
    # process's stdio, and a worker print into the protocol pipe would
    # corrupt it (and crash the worker once the pipe fd is gone). After
    # this, fd 0 is /dev/null and fd 1 aliases stderr, so a child with
    # no log_path still has sane, visible stdio.
    proto_in_fd = os.dup(0)
    proto_out_fd = os.dup(1)
    devnull = os.open(os.devnull, os.O_RDONLY)
    os.dup2(devnull, 0)
    os.close(devnull)
    os.dup2(2, 1)
    sys.stdout = os.fdopen(1, "w", buffering=1, closefd=False)
    inp = os.fdopen(proto_in_fd, "rb")
    out = os.fdopen(proto_out_fd, "wb")
    _write_msg(out, {"ready": True})
    children: List[int] = []
    import select

    while True:
        # Wake regularly to reap + report exits even with no requests.
        ready, _, _ = select.select([inp], [], [], 0.05)
        if ready:
            try:
                req = _read_msg(inp)
            except EOFError:
                break  # agent went away: exit (children are orphaned
                       # to init on purpose — the agent kills by pgid)
            if req.get("cmd") == "spawn":
                pid = os.fork()
                if pid == 0:
                    inp.close()   # protocol dups only — fds 0/1 are
                    out.close()   # already /dev/null + stderr alias
                    _child_main(req)  # never returns
                # Exits are keyed by the caller's unique token, not the
                # pid: pids recycle, tokens never do, and a token can't
                # collide with an exit event already in flight.
                children.append((pid, req.get("token")))
                _write_msg(out, {"pid": pid, "token": req.get("token")})
            elif req.get("cmd") == "stop":
                break
        for pid, token in list(children):
            done, status = os.waitpid(pid, os.WNOHANG)
            if done:
                children.remove((pid, token))
                code = (
                    os.waitstatus_to_exitcode(status)
                    if hasattr(os, "waitstatus_to_exitcode")
                    else (status >> 8)
                )
                _write_msg(out, {"exit": token, "code": code})


# --------------------------------------------------------------------
# agent side
# --------------------------------------------------------------------

class ForkedWorker:
    """Popen-shaped handle for a fork-server child."""

    def __init__(self, pid: int, token: int, server: "ForkServer"):
        self.pid = pid
        self.token = token
        self._server = server
        self.returncode: Optional[int] = None

    def poll(self) -> Optional[int]:
        if self.returncode is None:
            code = self._server.exit_code(self.token)
            if code is None and not self._server.alive():
                # Template gone: exit events can never arrive and the
                # child (reparented to init) cannot be waited from
                # here. If it is gone too, report an unknown-code
                # sentinel (-9): the agent then restarts the
                # incarnation from its checkpoint — conservative but
                # correct even if the worker actually exited 0, and
                # strictly better than hanging.
                try:
                    os.kill(self.pid, 0)
                except ProcessLookupError:
                    code = -9
                except PermissionError:
                    pass
            self.returncode = code
        return self.returncode

    def wait(self, timeout: Optional[float] = None) -> int:
        deadline = None if timeout is None else (
            time.monotonic() + timeout
        )
        backoff = ExponentialBackoff(initial=0.01, max_delay=0.2)
        while self.poll() is None:
            if deadline is not None and time.monotonic() > deadline:
                raise subprocess.TimeoutExpired(
                    f"forked-worker-{self.pid}", timeout
                )
            backoff.sleep(
                None if deadline is None else deadline - time.monotonic()
            )
        return self.returncode


class ForkServer:
    """Agent-side handle: one preloaded template, many fast forks."""

    def __init__(self):
        self._proc: Optional[subprocess.Popen] = None
        self._lock = threading.Lock()
        self._exits: Dict[int, int] = {}   # spawn token -> exit code
        self._reader: Optional[threading.Thread] = None
        self._next_token = 0

    @staticmethod
    def enabled() -> bool:
        return env_utils.FORKSERVER.get()

    def start(self, timeout: float = 120.0):
        import select

        if self._proc is not None and self._proc.poll() is None:
            return
        # _exits survives a template restart: tokens are unique across
        # templates, and clearing would drop codes of already-exited
        # workers nobody polled yet.
        self._proc = subprocess.Popen(
            [sys.executable, "-m", "dlrover_tpu.agent.forkserver"],
            stdin=subprocess.PIPE,
            stdout=subprocess.PIPE,
            start_new_session=True,
        )
        t0 = time.perf_counter()
        # Bounded handshake: a template wedged in preload (hung import,
        # driver lock) must not hang the agent — the caller falls back
        # to plain subprocess spawn.
        ready, _, _ = select.select(
            [self._proc.stdout], [], [], timeout
        )
        if not ready:
            self._proc.kill()
            self._proc.wait()
            raise TimeoutError(
                f"fork server preload exceeded {timeout:.0f}s"
            )
        msg = _read_msg(self._proc.stdout)
        assert msg.get("ready"), f"fork server bad handshake: {msg}"
        logger.info(
            "fork server preloaded in %.1f s (pid %s)",
            time.perf_counter() - t0, self._proc.pid,
        )
        self._reader = threading.Thread(
            target=self._read_loop, name="forkserver-reader",
            args=(self._proc.stdout,), daemon=True,
        )
        self._pending: List[Dict] = []
        self._reader.start()

    def _read_loop(self, stdout):
        # `stdout` is captured at thread creation: after a template
        # restart the stale reader EOFs on the OLD pipe and exits
        # instead of racing the new template's reader for frames.
        while True:
            try:
                msg = _read_msg(stdout)
            except (EOFError, ValueError, OSError):
                return
            with self._lock:
                if "exit" in msg:
                    self._exits[msg["exit"]] = msg["code"]
                else:
                    self._pending.append(msg)

    def _take_reply(self, token: int, timeout: float = 30.0) -> Dict:
        # Match by the echoed token, not FIFO order: two threads calling
        # spawn() concurrently would otherwise each pop whichever reply
        # landed first and hand back the OTHER spawn's pid.
        deadline = time.monotonic() + timeout
        backoff = ExponentialBackoff(initial=0.002, max_delay=0.05)
        while time.monotonic() < deadline:
            with self._lock:
                for i, msg in enumerate(self._pending):
                    if msg.get("token") == token:
                        return self._pending.pop(i)
            backoff.sleep(deadline - time.monotonic())
        raise TimeoutError("fork server did not answer")

    def spawn(self, entrypoint: str, args: List[str], env: Dict[str, str],
              log_path: str = "") -> ForkedWorker:
        with self._lock:
            alive = self._proc is not None and self._proc.poll() is None
            self._next_token += 1
            token = self._next_token
        if not alive:
            self.start()
        _write_msg(self._proc.stdin, {
            "cmd": "spawn", "entrypoint": entrypoint,
            "args": list(args), "env": dict(env),
            "log_path": log_path or None,
            "token": token,
        })
        reply = self._take_reply(token)
        return ForkedWorker(int(reply["pid"]), token, self)

    def exit_code(self, token: int) -> Optional[int]:
        with self._lock:
            return self._exits.get(token)

    def alive(self) -> bool:
        with self._lock:
            return self._proc is not None and self._proc.poll() is None

    def stop(self):
        if self._proc is None:
            return
        try:
            _write_msg(self._proc.stdin, {"cmd": "stop"})
        except (OSError, ValueError):
            pass
        try:
            self._proc.wait(timeout=5.0)
        except subprocess.TimeoutExpired:
            self._proc.kill()
            self._proc.wait()
        self._proc = None


if __name__ == "__main__":
    template_main()
