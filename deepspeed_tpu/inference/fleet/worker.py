"""Subprocess replica: the fleet protocol over stdin/stdout JSON lines.

``python -m deepspeed_tpu.inference.fleet.worker`` runs ONE replica — a
real ``ServingEngine`` (own jax runtime, own page pool, own watchdog)
wrapped in :class:`~.replica.LocalReplica` — and answers the protocol ops
as one JSON object per line:

    {"op": "init", "replica_id": ..., "model": {...GPTConfig kwargs...},
     "serving": {...ServingConfig kwargs...}, "seed": 0}
    {"op": "submit", "spec": {...}} | {"op": "pump", "steps": K}
    {"op": "load"} | {"op": "drain"} | {"op": "audit"} | {"op": "close"}
    {"op": "handoff_complete", "rid": N, "success": true}

:class:`SubprocessReplica` is the parent-side handle: it spawns the
worker, speaks the same dicts :class:`~.replica.LocalReplica` speaks
in-process, and — the point of the exercise — turns a SIGKILL'd or
wedged worker into :class:`~.replica.ReplicaDeadError` (pipe EOF, or no
response within ``call_timeout_s``), which the router answers with
re-route-to-survivors. ``scripts/serving_smoke.py --fleet`` SIGKILLs one
of two real-engine replicas mid-stream and proves the fleet heals.

Every response is read with a hard deadline (``select`` on the pipe fd):
a replica that stops answering is indistinguishable from a dead one on
purpose — that IS the failure model.
"""

from __future__ import annotations

import json
import os
import select
import signal
import subprocess
import sys
import time
from typing import Any, Dict, Optional

from .replica import ReplicaDeadError

#: generous init deadline: the worker imports jax and warms every serving
#: program shape before answering
DEFAULT_INIT_TIMEOUT_S = 300.0
DEFAULT_CALL_TIMEOUT_S = 60.0


class SubprocessReplica:
    """Parent-side handle for one worker process (module docstring)."""

    def __init__(self, replica_id: str, model: Dict[str, Any],
                 serving: Dict[str, Any], seed: int = 0,
                 call_timeout_s: float = DEFAULT_CALL_TIMEOUT_S,
                 init_timeout_s: float = DEFAULT_INIT_TIMEOUT_S,
                 env: Optional[Dict[str, str]] = None):
        self.replica_id = str(replica_id)
        # the replica runs on whatever platform the caller's environment
        # (plus ``env``, e.g. one TPU_VISIBLE_DEVICES per replica) selects
        penv = dict(os.environ)
        if env:
            penv.update(env)
        # -c instead of -m: the package __init__ already imports this
        # module, and runpy warns when re-executing an imported module
        self.proc = subprocess.Popen(
            [sys.executable, "-c",
             "from deepspeed_tpu.inference.fleet.worker import main; "
             "import sys; sys.exit(main())"],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, stderr=None,
            env=penv, cwd=os.path.dirname(os.path.dirname(os.path.dirname(
                os.path.dirname(os.path.abspath(__file__))))))
        self.call_timeout_s = float(call_timeout_s)
        # disaggregated role, read by the router's role-aware placement
        # (the worker's scheduler enforces the same role internally)
        self.role = str(serving.get("role", "both") or "both")
        self._alive = True
        self._buf = b""
        self._last_beat = time.monotonic()
        self._draining = False
        self._drained = False
        self._pending: Optional[str] = None  # op awaiting its response
        out = self._call({"op": "init", "replica_id": self.replica_id,
                          "model": model, "serving": serving,
                          "seed": int(seed)}, timeout=float(init_timeout_s))
        self.num_slots = int(out.get("num_slots", 0))

    @property
    def pid(self) -> int:
        return self.proc.pid

    @property
    def alive(self) -> bool:
        return self._alive

    @property
    def draining(self) -> bool:
        return self._alive and self._draining

    @property
    def drained(self) -> bool:
        return self._alive and self._drained

    def heartbeat_age(self) -> float:
        return time.monotonic() - self._last_beat

    # ------------------------------------------------------------- transport
    def _reap(self) -> None:
        """Reap the (already-signalled) child and close its pipes — a
        router that fails over replicas for a living must not accumulate
        zombies and leaked pipe fds."""
        try:
            self.proc.wait(timeout=5.0)
        except Exception:
            pass
        for pipe in (self.proc.stdin, self.proc.stdout):
            try:
                pipe.close()
            except Exception:
                pass

    def _mark_dead(self, why: str) -> None:
        self._alive = False
        try:
            self.proc.kill()
        except Exception:
            pass
        self._reap()
        raise ReplicaDeadError(f"replica {self.replica_id}: {why}")

    def _read_line(self, deadline: float) -> bytes:
        fd = self.proc.stdout.fileno()
        while b"\n" not in self._buf:
            remaining = deadline - time.monotonic()
            if remaining <= 0:
                self._mark_dead(
                    f"no response within {self.call_timeout_s}s "
                    f"(hung or wedged worker)")
            ready, _, _ = select.select([fd], [], [], min(remaining, 0.5))
            if not ready:
                if self.proc.poll() is not None:
                    self._mark_dead(
                        f"worker exited rc={self.proc.returncode}")
                continue
            chunk = os.read(fd, 1 << 16)
            if not chunk:
                self._mark_dead("worker pipe closed (killed or crashed)")
            self._buf += chunk
        line, self._buf = self._buf.split(b"\n", 1)
        return line

    def _send(self, obj: Dict[str, Any]) -> None:
        if not self._alive:
            raise ReplicaDeadError(f"replica {self.replica_id} is dead")
        if self._pending is not None:
            raise RuntimeError(
                f"replica {self.replica_id}: request while a "
                f"{self._pending!r} response is pending")
        try:
            self.proc.stdin.write((json.dumps(obj) + "\n").encode())
            self.proc.stdin.flush()
        except (BrokenPipeError, OSError):
            self._mark_dead("worker pipe broken on write")

    def _recv(self, timeout: Optional[float] = None) -> Dict[str, Any]:
        timeout = self.call_timeout_s if timeout is None else timeout
        line = self._read_line(time.monotonic() + timeout)
        try:
            out = json.loads(line)
        except ValueError:
            self._mark_dead(f"unparseable response: {line[:120]!r}")
        if out.get("error"):
            # a protocol-level error is a sick replica, not a router bug
            self._mark_dead(f"worker error: {out['error']}")
        self._last_beat = time.monotonic()
        return out

    def _call(self, obj: Dict[str, Any],
              timeout: Optional[float] = None) -> Dict[str, Any]:
        self._send(obj)
        return self._recv(timeout)

    # -------------------------------------------------------------- protocol
    def submit(self, spec: Dict[str, Any]) -> Dict[str, Any]:
        return self._call({"op": "submit", "spec": spec})

    def pump(self, max_steps: int = 1) -> Dict[str, Any]:
        self.pump_begin(max_steps)
        return self.pump_end()

    # two-phase pump: the router begins a pump on EVERY replica before
    # collecting any response, so N worker processes decode their steps
    # genuinely concurrently — the wall-clock fleet win of replicas owning
    # their own compute (separate chips; here, separate processes)
    def pump_begin(self, max_steps: int = 1) -> None:
        self._send({"op": "pump", "steps": int(max_steps)})
        self._pending = "pump"

    def pump_end(self) -> Dict[str, Any]:
        if self._pending != "pump":
            raise RuntimeError(f"replica {self.replica_id}: pump_end "
                               f"without pump_begin")
        try:
            out = self._recv()
        finally:
            self._pending = None
        self._draining = bool(out.get("draining"))
        self._drained = bool(out.get("drained"))
        return out

    def load(self) -> Dict[str, Any]:
        return self._call({"op": "load"})

    def handoff_complete(self, rid: int, success: bool = True) -> bool:
        out = self._call({"op": "handoff_complete", "rid": int(rid),
                          "success": bool(success)})
        return bool(out.get("ok"))

    def drain(self) -> None:
        out = self._call({"op": "drain"})
        self._draining = True
        self._drained = bool(out.get("drained"))

    def audit(self) -> Dict[str, Any]:
        return self._call({"op": "audit"})

    def close(self) -> None:
        if not self._alive:
            return
        try:
            self._call({"op": "close"}, timeout=10.0)
        except ReplicaDeadError:
            pass
        self._alive = False
        try:
            self.proc.wait(timeout=10.0)
        except Exception:
            self.proc.kill()
        self._reap()

    def kill(self) -> None:
        """The hard stop: SIGKILL, no goodbyes — what a preempted host or
        an OOM-killed container looks like from the router's side."""
        self._alive = False
        try:
            os.kill(self.proc.pid, signal.SIGKILL)
        except (ProcessLookupError, OSError):
            pass
        self._reap()


# ------------------------------------------------------------- worker main
def _build_replica(msg: Dict[str, Any]):
    """Import jax lazily (the parent handle must stay importable without
    acquiring a runtime) and assemble engine + LocalReplica."""
    import jax

    from ...models import gpt as gpt_mod
    from ..serving import ServingConfig, ServingEngine
    from .replica import LocalReplica

    cfg = gpt_mod.GPTConfig(**msg["model"])
    params = gpt_mod.init_params(cfg, jax.random.PRNGKey(
        int(msg.get("seed", 0))))
    eng = ServingEngine(cfg, params, ServingConfig(**msg["serving"]))
    eng.warmup()
    return LocalReplica(str(msg.get("replica_id", "worker")), engine=eng)


def main() -> int:
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    # the protocol owns fd 1: keep a private dup for responses and point
    # everything else (library prints, loggers bound to sys.stdout) at
    # stderr, so stray output can never tear the JSON framing
    out = os.fdopen(os.dup(1), "w")
    os.dup2(2, 1)
    sys.stdout = sys.stderr
    replica = None
    for raw in sys.stdin:
        raw = raw.strip()
        if not raw:
            continue
        try:
            msg = json.loads(raw)
            op = msg.get("op")
            if op == "init":
                replica = _build_replica(msg)
                resp = {"ok": True, "replica_id": replica.replica_id,
                        "num_slots": replica.sched.num_slots,
                        "pid": os.getpid()}
            elif replica is None:
                resp = {"error": f"op {op!r} before init"}
            elif op == "submit":
                resp = replica.submit(msg["spec"])
            elif op == "pump":
                resp = replica.pump(int(msg.get("steps", 1)))
            elif op == "load":
                resp = replica.load()
            elif op == "handoff_complete":
                resp = {"ok": replica.handoff_complete(
                    int(msg["rid"]), bool(msg.get("success", True)))}
            elif op == "drain":
                replica.drain()
                resp = {"ok": True, "drained": replica.drained}
            elif op == "audit":
                resp = replica.audit()
            elif op == "close":
                replica.close()
                print(json.dumps({"ok": True}), file=out, flush=True)
                return 0
            else:
                resp = {"error": f"unknown op {op!r}"}
        except Exception as e:  # report, let the parent decide
            resp = {"error": f"{type(e).__name__}: {e}"}
        print(json.dumps(resp), file=out, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
