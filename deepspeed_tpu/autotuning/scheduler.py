"""Autotuning experiment scheduler: queued trials over a host pool.

Capability parity with the reference's ``autotuning/scheduler.py:28``
(``ResourceManager`` + ``Node``): experiments are scheduled as SEPARATE jobs
onto free hosts, run concurrently, and report through metric files — the
multi-host tuning story the in-process :class:`~.autotuner.Autotuner` loop
does not cover (one controller per TPU host; trials that OOM or wedge a
backend must not take the tuner with them).

TPU-native mapping:

- a Node is one TPU host (all its chips belong to one process), not a GPU
  slot — ``slots`` defaults to 1 per host;
- the job command is ``python -m deepspeed_tpu.autotuning.run_exp exp.json``,
  executed locally (host ``None``/"localhost") or through the same ssh
  fan-out the launcher uses (``launcher/runner.py`` SSHRunner convention);
- each experiment directory gets ``exp.json`` (the trial's DeepSpeed config
  + model overrides), and the runner writes ``metrics.json``
  (``{"metric_value": tokens_per_sec}``) or ``error.log`` — the same
  file-based contract as the reference (``AUTOTUNING_METRIC_PATH``);
- :func:`profile_model_info` is the reference's model-info pass
  (``autotuner.py`` ``model_info_profile_run``): parameter count and
  per-micro-batch activation footprint from ``jax.eval_shape`` — zero device
  memory touched.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time
from dataclasses import dataclass
from typing import Any, Dict, List, Optional

import jax

from ..utils.logging import log_dist


@dataclass
class Node:
    """One schedulable host (parity: ``scheduler.py`` ``Node``)."""

    host: Optional[str] = None  # None/"localhost" = run locally
    slots: int = 1
    in_use: int = 0

    @property
    def free(self) -> bool:
        return self.in_use < self.slots

    @property
    def is_local(self) -> bool:
        return self.host in (None, "localhost", "127.0.0.1")


@dataclass
class ScheduledExperiment:
    exp_id: int
    name: str
    config: Dict[str, Any]
    exp_dir: str
    node: Optional[Node] = None
    proc: Optional[subprocess.Popen] = None
    metric_value: Optional[float] = None
    error: Optional[str] = None
    started: float = 0.0

    @property
    def ok(self) -> bool:
        return self.error is None and self.metric_value is not None


class ResourceManager:
    """Schedule tuning experiments onto a pool of hosts.

    ``hosts``: list of hostnames (empty/None => one local node). Experiments
    come from :meth:`schedule_experiments` (config dicts, e.g. from
    ``Autotuner.generate_experiments``); :meth:`run` drives the queue until
    done and returns the experiments with parsed metrics.
    """

    def __init__(self, hosts: Optional[List[str]] = None,
                 results_dir: str = "autotuning_exps",
                 runner_argv: Optional[List[str]] = None,
                 env: Optional[Dict[str, str]] = None,
                 timeout: float = 1800.0):
        self.nodes = ([Node(h) for h in hosts] if hosts else [Node(None)])
        self.results_dir = results_dir
        self.runner_argv = runner_argv or [
            sys.executable, "-m", "deepspeed_tpu.autotuning.run_exp"]
        self.env = env
        self.timeout = timeout
        self.experiment_count = 0
        self.queue: List[ScheduledExperiment] = []
        self.running: List[ScheduledExperiment] = []
        self.finished: List[ScheduledExperiment] = []

    # ------------------------------------------------------------------ queue
    def schedule_experiments(self, configs: List[Dict[str, Any]],
                             names: Optional[List[str]] = None) -> None:
        if names is not None and len(names) != len(configs):
            raise ValueError(
                f"{len(names)} names for {len(configs)} configs — a partial "
                "schedule would be indistinguishable from success")
        for i, cfg in enumerate(configs):
            name = (names[i] if names else None) or f"exp_{self.experiment_count}"
            exp_dir = os.path.join(self.results_dir, name)
            os.makedirs(exp_dir, exist_ok=True)
            with open(os.path.join(exp_dir, "exp.json"), "w") as f:
                json.dump(cfg, f, indent=2, default=str)
            self.queue.append(ScheduledExperiment(
                exp_id=self.experiment_count, name=name, config=cfg,
                exp_dir=exp_dir))
            self.experiment_count += 1

    # ------------------------------------------------------------------ dispatch
    def _command(self, exp: ScheduledExperiment, node: Node) -> List[str]:
        argv = self.runner_argv + [os.path.join(exp.exp_dir, "exp.json")]
        if node.is_local:
            return argv
        # ssh fan-out, same convention as launcher/runner.py SSHRunner
        remote = " ".join(argv)
        return ["ssh", "-o", "StrictHostKeyChecking=no", node.host,
                f"cd {os.getcwd()} && {remote}"]

    def _launch(self, exp: ScheduledExperiment, node: Node) -> None:
        cmd = self._command(exp, node)
        log_dist(f"autotuning scheduler: exp {exp.exp_id} ({exp.name}) "
                 f"-> {node.host or 'local'}")
        env = dict(self.env if self.env is not None else os.environ)
        # the job must import deepspeed_tpu no matter the caller's cwd
        pkg_root = os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))))
        env["PYTHONPATH"] = pkg_root + os.pathsep + env.get("PYTHONPATH", "")
        with open(os.path.join(exp.exp_dir, "stdout.log"), "w") as out, \
                open(os.path.join(exp.exp_dir, "stderr.log"), "w") as err:
            exp.proc = subprocess.Popen(cmd, stdout=out, stderr=err, env=env)
        exp.node = node
        exp.started = time.time()
        node.in_use += 1
        self.running.append(exp)

    def _reap(self, exp: ScheduledExperiment) -> None:
        metric_path = os.path.join(exp.exp_dir, "metrics.json")
        if exp.proc.returncode == 0 and os.path.exists(metric_path):
            try:
                with open(metric_path) as f:
                    exp.metric_value = float(json.load(f)["metric_value"])
            except (OSError, KeyError, ValueError, TypeError) as e:
                # TypeError: float(None) from a {"metric_value": null} file —
                # a bad job must not take the scheduler loop down
                exp.error = f"bad metrics.json: {e}"
        else:
            tail = ""
            try:
                with open(os.path.join(exp.exp_dir, "stderr.log")) as f:
                    tail = f.read()[-400:]
            except OSError:
                pass
            exp.error = f"rc={exp.proc.returncode}: {tail}"
        exp.node.in_use -= 1
        self.running.remove(exp)
        self.finished.append(exp)

    def run(self, poll_s: float = 1.0) -> List[ScheduledExperiment]:
        """Drive the queue to completion (parity: ``scheduler.py`` run loop:
        launch onto free nodes, poll, reap, repeat)."""
        while self.queue or self.running:
            for node in self.nodes:
                while node.free and self.queue:
                    self._launch(self.queue.pop(0), node)
            time.sleep(poll_s if self.running else 0)
            for exp in list(self.running):
                rc = exp.proc.poll()
                if rc is not None:
                    self._reap(exp)
                elif time.time() - exp.started > self.timeout:
                    exp.proc.kill()
                    exp.proc.wait()
                    self._reap(exp)
                    # a job that finished cleanly between poll and deadline
                    # keeps its metrics; only genuinely wedged jobs are marked
                    if not exp.ok:
                        exp.error = (f"timeout >{self.timeout}s "
                                     f"({exp.error or 'no metrics'})")
        ok = [e for e in self.finished if e.ok]
        log_dist(f"autotuning scheduler: {len(ok)}/{len(self.finished)} "
                 f"experiments succeeded")
        return self.finished

    def best(self, metric: str = "throughput") -> Optional[ScheduledExperiment]:
        ok = [e for e in self.finished if e.ok]
        if not ok:
            return None
        return (min if metric == "latency" else max)(
            ok, key=lambda e: e.metric_value)


# ---------------------------------------------------------------- model info
def profile_model_info(model, micro_batch_sizes: List[int],
                       seq_len: int, vocab_size: int,
                       dtype_bytes: int = 2) -> Dict[str, Any]:
    """Shape-only model profile (parity: the reference autotuner's
    ``model_info_profile_run`` — it runs a real job to count params; here
    ``jax.eval_shape`` gives the same numbers with no device memory)."""
    import numpy as np

    # the key is made INSIDE the abstract trace: a concrete PRNGKey would
    # initialize a backend, and this process (the scheduler's parent) must
    # leave the chip to the experiments it launches
    shapes = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
    n_params = sum(int(np.prod(l.shape))
                   for l in jax.tree_util.tree_leaves(shapes))
    info: Dict[str, Any] = {
        "num_params": n_params,
        "param_bytes_bf16": n_params * 2,
        "optimizer_state_bytes_fp32": n_params * 12,  # master + m + v
        "activation_bytes_per_micro_batch": {},
    }
    for mbs in micro_batch_sizes:
        # residual-stream proxy: ranks micro-batches correctly without
        # compiling anything (compiled_memory_analysis gives exact numbers
        # when a device is available — runtime/zero/mem_estimator.py)
        info["activation_bytes_per_micro_batch"][mbs] = (
            mbs * seq_len * dtype_bytes * _hidden_elems(shapes))
    return info


def _hidden_elems(param_shapes) -> int:
    """Per-token activation footprint proxy: layers x d_model (+ heads)."""
    leaves = jax.tree_util.tree_leaves(param_shapes)
    # the widest 2D+ leaf's trailing dim ~ d_model; depth from leading dims
    dims = [l.shape for l in leaves if len(l.shape) >= 2]
    if not dims:
        return 1
    d_model = max(min(s[-1], s[-2]) for s in dims)
    depth = max((s[0] for s in dims if len(s) == 3), default=1)
    return int(depth * d_model * 2)  # x2: attn + mlp residual contributions
