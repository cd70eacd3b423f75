"""ZeRO-Offload: host-CPU optimizer stepping with native SIMD.

Capability parity with the reference's ZeRO-Offload (``stage_1_and_2.py:129``
``cpu_offload``, ``ops/adam/cpu_adam.py`` stepping on host,
``offload_config.py``): gradients are produced on the accelerator, the optimizer
state (fp32 master params, moments) lives in host RAM, and the update runs on the
host CPU through :class:`deepspeed_tpu.ops.adam.DeepSpeedCPUAdam` (C++ AVX2+FMA,
OpenMP). Device HBM holds only bf16 params + transient grads — the memory
breakdown that lets a single chip train models several times larger than HBM.

TPU-native structure:
- the device program is grads-only (loss + grads in one jitted XLA program,
  ZeRO grad sharding intact);
- host<->device movement is explicit (``device_get`` of grads, ``device_put`` of
  the bf16 copy-back written by the C++ kernel in the same pass — parity with the
  reference's overlapped fp16 copy-back, ``csrc/adam/cpu_adam.cpp:216``);
- the step is the reference's semantics: clip by global norm, Adam/AdamW/Adagrad,
  LR schedule evaluated on host.
"""

from __future__ import annotations

import os
import tempfile
from typing import Any, Dict, Optional

import numpy as np

import jax
import jax.numpy as jnp
import ml_dtypes

from ...ops.adam import DeepSpeedCPUAdam, DeepSpeedCPUAdagrad
from ...utils.logging import log_dist
from ..topology import mesh_context


def _leaves(tree):
    return jax.tree_util.tree_flatten(tree)


class HostOffloadRunner:
    """Owns host-resident optimizer state + the grads-only device program."""

    def __init__(self, engine):
        self.engine = engine
        cfg = engine.config
        if engine.pc.loss_scaling:
            raise ValueError("ZeRO-Offload: use bf16 or fp32 (no dynamic loss scaling)")
        opt_cfg = cfg.optimizer
        opt_type = (opt_cfg.type if opt_cfg else "Adam").lower()
        params = dict(opt_cfg.params) if opt_cfg else {}
        self.base_lr = float(params.get("lr", 1e-3))
        if opt_type in ("adam", "adamw", "fusedadam"):
            self.cpu_opt = DeepSpeedCPUAdam(
                lr=self.base_lr,
                betas=tuple(params.get("betas", (0.9, 0.999))),
                eps=params.get("eps", 1e-8),
                weight_decay=params.get("weight_decay", 0.0),
                adamw_mode=(opt_type != "adam") or params.get("adam_w_mode", True),
                bias_correction=params.get("bias_correction", True))
            self._kind = "adam"
        elif opt_type == "adagrad":
            self.cpu_opt = DeepSpeedCPUAdagrad(
                lr=self.base_lr, eps=params.get("eps", 1e-10),
                weight_decay=params.get("weight_decay", 0.0))
            self._kind = "adagrad"
        else:
            raise ValueError(
                f"ZeRO-Offload supports Adam/AdamW/Adagrad on host (got {opt_type!r})")
        self.count = 0
        self._grads_jit = None
        self.master: Optional[list] = None  # flat leaf list, np.float32 (RAM mode)
        self.m: Optional[list] = None
        self.v: Optional[list] = None
        # NVMe mode (ZeRO-Infinity): state lives on local SSD, pipelined through
        # the native AIO pool (runtime/swap_tensor/optimizer_swapper.py)
        self.store = None
        oo = cfg.zero_optimization.offload_optimizer
        if oo is not None and oo.device.value == "nvme":
            from ..swap_tensor import NVMeLeafStore

            nvme_path = oo.nvme_path or os.path.join(
                tempfile.gettempdir(), "ds_tpu_nvme_swap")
            self.store = NVMeLeafStore(
                os.path.join(nvme_path, "optimizer"),
                aio_threads=max(1, int(oo.buffer_count)))
        log_dist(f"ZeRO-Offload: host {opt_type} "
                 f"({'native SIMD' if self.cpu_opt.is_native else 'numpy fallback'}"
                 f"{', NVMe swap' if self.store is not None else ''})")

    # ------------------------------------------------------------------ state
    def init_host_state(self, for_load: bool = False) -> None:
        """``for_load``: a checkpoint load follows immediately — only shapes are
        needed, skip writing fresh state that would be overwritten at once."""
        flat, self._treedef = _leaves(self.engine.state["params"])
        if self.store is not None:
            if for_load:
                self.store.shapes = [tuple(l.shape) for l in flat]
            else:
                self.store.write_init([
                    np.array(jax.device_get(l), np.float32, copy=True) for l in flat])
            self.master = "nvme"  # sentinel: state lives on disk
            return
        if for_load:
            # load_host_state_dict only needs the leaf count — skip the full
            # device->host transfer that it would immediately discard
            self.master = [None] * len(flat)
            self.m = self.v = [None] * len(flat)
            return
        self.master = [np.array(jax.device_get(l), np.float32, copy=True)
                       for l in flat]
        self.m = [np.zeros_like(x) for x in self.master]
        self.v = [np.zeros_like(x) for x in self.master]

    def host_state_dict(self) -> Dict[str, Any]:
        out = {"count": np.int64(self.count)}
        if self.store is not None:
            out.update(self.store.read_all())
            return out
        for i, (ms, mm, vv) in enumerate(zip(self.master, self.m, self.v)):
            out[f"master_{i}"] = ms
            out[f"m_{i}"] = mm
            out[f"v_{i}"] = vv
        return out

    def load_host_state_dict(self, d: Dict[str, Any]) -> None:
        self.count = int(d["count"])
        if self.store is not None:
            self.store.write_all(d)
            self._push_params_from([d[f"master_{i}"]
                                    for i in range(self.store.num_leaves)])
            return
        n = len(self.master)
        self.master = [np.ascontiguousarray(d[f"master_{i}"], np.float32) for i in range(n)]
        self.m = [np.ascontiguousarray(d[f"m_{i}"], np.float32) for i in range(n)]
        self.v = [np.ascontiguousarray(d[f"v_{i}"], np.float32) for i in range(n)]
        self._push_params()

    # ------------------------------------------------------------------ device program
    def _build_grads_jit(self):
        engine = self.engine

        def fused(params, batch, rng):
            if engine.gas == 1:
                loss, aux, grads = engine._loss_and_grads(
                    params, batch, jnp.float32(1.0), {"dropout": rng})
                return loss, grads
            rngs = jax.random.split(rng, engine.gas)

            def body(acc, xs):
                mb, r = xs
                loss, aux, grads = engine._loss_and_grads(
                    params, mb, jnp.float32(1.0), {"dropout": r})
                acc = jax.tree_util.tree_map(
                    lambda a, g: a + g / engine.gas, acc, grads)
                return acc, loss

            zero = jax.tree_util.tree_map(
                lambda x: jnp.zeros(x.shape, jnp.float32), params)
            grads, losses = jax.lax.scan(body, zero, (batch, rngs))
            return jnp.mean(losses), grads

        ps = jax.tree_util.tree_map(lambda x: x.sharding, engine.state["params"])
        batch_sharding = engine.batch_sharding
        if engine.gas > 1:
            from jax.sharding import NamedSharding, PartitionSpec as P

            batch_sharding = NamedSharding(
                engine.mesh, P(None, *engine.topo.batch_spec()))
        return jax.jit(fused, in_shardings=(ps, batch_sharding, None),
                       out_shardings=(None, engine.grad_shardings))

    # ------------------------------------------------------------------ step
    @staticmethod
    def _to_device_leaf(mst: np.ndarray, old, sharding):
        """Compute-dtype copy-back of one master leaf (bf16 round-to-nearest)."""
        if old.dtype == jnp.bfloat16:
            arr = np.ascontiguousarray(mst, np.float32).astype(
                ml_dtypes.bfloat16).reshape(old.shape)
        else:
            arr = mst.astype(old.dtype).reshape(old.shape)
        return jax.device_put(arr, sharding)

    def _push_params_from(self, masters) -> None:
        engine = self.engine
        flat_shard, _ = _leaves(engine.param_shardings)
        flat_params, treedef = _leaves(engine.state["params"])
        new_flat = [self._to_device_leaf(mst, old, shd)
                    for mst, old, shd in zip(masters, flat_params, flat_shard)]
        engine.state["params"] = jax.tree_util.tree_unflatten(treedef, new_flat)

    def _push_params(self) -> None:
        """bf16/compute-dtype copy-back to device with the engine's shardings."""
        self._push_params_from(self.master)

    def train_batch(self, batch, rng):
        engine = self.engine
        if self.master is None:
            self.init_host_state()
        if self._grads_jit is None:
            self._grads_jit = self._build_grads_jit()
        with mesh_context(engine.mesh):
            loss, grads = self._grads_jit(engine.state["params"], batch, rng)
        flat_g, _ = _leaves(grads)
        # copy=True: device_get can hand back read-only views, and both the
        # clip and the in-place C++ step need writable memory. The
        # blocking device->host fetch is a host<->HBM DMA wait — bracketed
        # under the offload_fetch watchdog deadline like the param stream's
        with engine._watch_phase("offload_fetch"):
            from .stream import fetch_fault_point

            fetch_fault_point()
            g_np = [np.array(jax.device_get(g), np.float32, copy=True)
                    for g in flat_g]

        # global grad norm + clip (parity: stage_1_and_2.py unscale_and_clip)
        gnorm = float(np.sqrt(sum(float((g ** 2).sum()) for g in g_np)))
        clip = float(engine.config.gradient_clipping or 0.0)
        if clip > 0.0 and gnorm > clip:
            scale = clip / (gnorm + 1e-6)
            for g in g_np:
                g *= scale

        self.count += 1
        lr = float(engine.lr_fn(engine.state["step"]))
        with engine._watch_phase("offload_flush"):
            self._host_step(engine, g_np, lr)
        engine.state["step"] = engine.state["step"] + 1

        metrics = {
            "loss": loss,
            "grad_norm": jnp.float32(gnorm),
            "lr": jnp.float32(lr),
            "loss_scale": jnp.float32(1.0),
            "overflow": jnp.bool_(False),
        }
        return engine.state, metrics

    def _host_step(self, engine, g_np, lr: float) -> None:
        """The host optimizer pass + compute-dtype copy-back (the
        ``offload_flush`` watchdog phase)."""
        if self.store is not None:
            # ZeRO-Infinity pipelined loop: while stepping leaf i, leaf i+1 is
            # being read and leaf i-1 written back, all on the AIO pool (parity:
            # pipelined_optimizer_swapper.py:32)
            flat_shard, _ = _leaves(engine.param_shardings)
            flat_params, treedef = _leaves(engine.state["params"])
            new_flat = []
            self.store.prefetch(0)
            for i, g in enumerate(g_np):
                if i + 1 < len(g_np):
                    self.store.prefetch(i + 1)
                mst, m, v = self.store.get(i)
                if self._kind == "adam":
                    self.cpu_opt.step(mst.ravel(), m.ravel(), v.ravel(),
                                      g.ravel(), self.count, lr=lr)
                else:
                    self.cpu_opt.step(mst.ravel(), v.ravel(), g.ravel(), lr=lr)
                new_flat.append(self._to_device_leaf(
                    mst, flat_params[i], flat_shard[i]))
                self.store.writeback(i, mst, m, v)
            self.store.drain()
            engine.state["params"] = jax.tree_util.tree_unflatten(treedef, new_flat)
        else:
            for i, g in enumerate(g_np):
                mst = self.master[i].ravel()
                if self._kind == "adam":
                    self.cpu_opt.step(mst, self.m[i].ravel(), self.v[i].ravel(),
                                      g.ravel(), self.count, lr=lr)
                else:
                    self.cpu_opt.step(mst, self.v[i].ravel(), g.ravel(), lr=lr)
            self._push_params()

    # ------------------------------------------------------------------ shards
    #: leaves per host shard file: small models stay one file, billion-scale
    #: masters flush in bounded atomic chunks a mid-flush kill cannot tear
    SHARD_LEAVES = 32

    def flush_host_shards(self, dir_path: str, writer=None) -> bool:
        """Crash-consistent host-state flush (docs/OFFLOAD.md): bounded
        groups of fp32 master/moment leaves per atomic ``shard_<k>.npz``,
        ``fault_point("host-shard", k)`` between shards, the PR 3 manifest/
        COMMIT covering all of them. Returns False in NVMe-swap mode."""
        from .stream import flush_host_shards as _flush

        if self.store is not None:
            return False

        def shards():
            n = len(self.master)
            for k0 in range(0, n, self.SHARD_LEAVES):
                arrays: Dict[str, Any] = {}
                for i in range(k0, min(n, k0 + self.SHARD_LEAVES)):
                    arrays[f"master_{i}"] = self.master[i]
                    arrays[f"m_{i}"] = self.m[i]
                    arrays[f"v_{i}"] = self.v[i]
                yield f"leaves_{k0}", arrays

        with self.engine._watch_phase("offload_flush"):
            _flush(dir_path, shards(),
                   meta={"count": int(self.count), "runner": "offload"},
                   writer=writer)
        return True

    def load_host_shards_dir(self, dir_path: str) -> None:
        from .stream import load_host_shards as _load

        d, meta = _load(dir_path)
        d["count"] = np.int64(meta.get("count", 0))
        self.load_host_state_dict(d)
