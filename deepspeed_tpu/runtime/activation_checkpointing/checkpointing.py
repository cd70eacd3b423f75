"""Activation checkpointing (rematerialization), TPU-native.

Capability parity with the reference's Megatron-derived module
(``runtime/activation_checkpointing/checkpointing.py``): the ``checkpoint(fn, *args)``
entry point (``:748``), global ``configure(...)`` from the DeepSpeed JSON block
(``:830``), activation *partitioning* across model-parallel ranks (``:372``),
CPU checkpointing (host offload of saved activations), and the RNG-state tracker
(``CudaRNGStatesTracker``, ``:122``).

TPU-native design — each reference mechanism maps to a compiler facility instead of
hand-managed buffers:

- recompute-in-backward  -> ``jax.checkpoint`` (XLA rematerialization). No custom
  autograd Function, no stashed tensors: the saved-residual set is a *policy*.
- ``partition_activations`` -> saved residuals are sharding-constrained over the
  model-parallel axes (tp, sp), so each rank stores ``1/mp`` of every checkpoint —
  the same memory math as the reference's scatter/gather, but the "gather" at
  recompute time is an XLA all-gather it schedules and overlaps itself.
- ``cpu_checkpointing`` -> ``jax.checkpoint`` offload policies: residuals are moved
  to ``pinned_host`` memory between fwd and bwd (``save_and_offload_only_these_names``
  machinery via ``jax.checkpoint_policies.offload_*``).
- ``contiguous_memory_optimization`` -> no-op by construction: XLA allocates saved
  residuals in one arena; there is no fragmentation to manage. Accepted, ignored.
- RNG tracker -> JAX PRNG keys are explicit values, so recompute determinism is
  automatic (the same key is an input to both executions). The tracker here exists
  for API parity and for deriving *model-parallel-unique* dropout keys the way the
  reference seeds each MP rank differently (``:122-258``).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Optional, Sequence

import jax
import jax.numpy as jnp

from ...utils.logging import logger

# must match the policy actually built in policy_from_config
_OFFLOAD_SUPPORTED = hasattr(jax.checkpoint_policies, "offload_dot_with_no_batch_dims")


@dataclasses.dataclass
class CheckpointConfig:
    """Resolved knobs. Parity: module-level globals set by ``configure`` (``:830``)."""

    partition_activations: bool = False
    cpu_checkpointing: bool = False
    contiguous_memory_optimization: bool = False
    number_checkpoints: Optional[int] = None
    synchronize_checkpoint_boundary: bool = False
    profile: bool = False
    # jax-side selection of what to save when NOT recomputing everything
    policy_name: str = "nothing_saveable"
    mp_axes: Sequence[str] = ("tp", "sp")


_config = CheckpointConfig()
_configured = False


def configure(mpu_=None, deepspeed_config=None, partition_activations=None,
              contiguous_checkpointing=None, num_checkpoints=None,
              checkpoint_in_cpu=None, synchronize=None, profile=None) -> None:
    """Parity: ``checkpointing.configure`` (``:830``) — same signature shape; accepts
    either the parsed DeepSpeed config or explicit overrides."""
    global _config, _configured
    cfg = CheckpointConfig()
    if deepspeed_config is not None:
        block = getattr(deepspeed_config, "activation_checkpointing", None)
        if block is not None:
            cfg.partition_activations = block.partition_activations
            cfg.cpu_checkpointing = block.cpu_checkpointing
            cfg.contiguous_memory_optimization = block.contiguous_memory_optimization
            cfg.number_checkpoints = block.number_checkpoints
            cfg.synchronize_checkpoint_boundary = block.synchronize_checkpoint_boundary
            cfg.profile = block.profile
    if partition_activations is not None:
        cfg.partition_activations = partition_activations
    if contiguous_checkpointing is not None:
        cfg.contiguous_memory_optimization = contiguous_checkpointing
    if num_checkpoints is not None:
        cfg.number_checkpoints = num_checkpoints
    if checkpoint_in_cpu is not None:
        cfg.cpu_checkpointing = checkpoint_in_cpu
    if synchronize is not None:
        cfg.synchronize_checkpoint_boundary = synchronize
    if profile is not None:
        cfg.profile = profile
    if cfg.cpu_checkpointing and not _OFFLOAD_SUPPORTED:
        logger.warning("cpu_checkpointing requested but this jax has no offload "
                       "checkpoint policies; falling back to plain remat")
        cfg.cpu_checkpointing = False
    _config = cfg
    _configured = True


def is_configured() -> bool:
    """Parity: ``checkpointing.is_configured`` (``:918``)."""
    return _configured


def reset() -> None:
    """Parity: ``checkpointing.reset`` (``:896``) — clears global state."""
    global _config, _configured
    _config = CheckpointConfig()
    _configured = False


# ----------------------------------------------------------------------- policies
def policy_from_config(cfg: Optional[CheckpointConfig] = None):
    """Map the config onto a ``jax.checkpoint`` policy (or None = save nothing)."""
    cfg = cfg or _config
    if cfg.cpu_checkpointing:
        # save dot outputs but park them in host memory between fwd and bwd —
        # the reference's checkpoint_in_cpu (":748" arg_cpu path), minus the
        # hand-rolled pinned-buffer management.
        if hasattr(jax.checkpoint_policies, "offload_dot_with_no_batch_dims"):
            return jax.checkpoint_policies.offload_dot_with_no_batch_dims(
                "device", "pinned_host")
    name = cfg.policy_name
    if name in (None, "nothing_saveable", "none"):
        return jax.checkpoint_policies.nothing_saveable
    pol = getattr(jax.checkpoint_policies, name, None)
    if pol is None:
        raise ValueError(f"unknown jax.checkpoint policy {name!r}")
    return pol


def _partition_saved(x, mp_axes: Sequence[str]):
    """Sharding-constrain a saved activation over the model-parallel axes.

    Parity: ``partition_activations`` (``checkpointing.py:372``) — each MP rank keeps
    1/mp of every saved tensor; XLA re-gathers at recompute time.
    """
    if not isinstance(x, jax.Array) and not isinstance(x, jnp.ndarray):
        return x
    if x.ndim == 0:
        return x
    from jax.sharding import PartitionSpec as P

    # shard the first dimension divisible by the mp extent; bare specs resolve
    # against the ambient mesh (engine runs under mesh_context)
    sizes = jax.sharding.get_abstract_mesh().shape
    live = [a for a in mp_axes if sizes.get(a, 1) > 1]
    if not live:
        return x
    extent = 1
    for a in live:
        extent *= sizes[a]
    # prefer trailing (feature/sequence) dims and never dim 0 of a batched
    # activation: dim 0 is the batch, already sharded over dp — constraining it
    # to the mp axes would force reshard collectives at every boundary instead
    # of reducing per-rank saved memory
    candidates = range(x.ndim - 1, 0, -1) if x.ndim >= 2 else range(x.ndim)
    for d in candidates:
        if x.shape[d] % extent == 0 and x.shape[d] >= extent:
            spec = [None] * x.ndim
            spec[d] = tuple(live) if len(live) > 1 else live[0]
            return jax.lax.with_sharding_constraint(x, P(*spec))
    return x


# ----------------------------------------------------------------------- API
def checkpoint(function: Callable, *args) -> Any:
    """Checkpoint ``function(*args)``: recompute its activations in backward.

    Parity: ``checkpointing.checkpoint`` (``:748``). Under the configured options
    this also partitions (shards) or host-offloads whatever the policy saves.
    """
    wrapped = checkpoint_wrapper(function)
    return wrapped(*args)


def checkpoint_wrapper(function: Callable,
                       cfg: Optional[CheckpointConfig] = None) -> Callable:
    """Return a rematerialized version of ``function``; composable with jit/scan."""
    cfg = cfg or _config
    policy = policy_from_config(cfg)

    if cfg.partition_activations:
        # wrap so that everything the policy saves is sharding-constrained over
        # the mp axes: apply constraint to the function outputs feeding residuals.
        inner = function

        def function(*a, **k):
            out = inner(*a, **k)
            return jax.tree_util.tree_map(
                lambda t: _partition_saved(t, cfg.mp_axes), out)

    remat = jax.checkpoint(function, policy=policy)

    if cfg.profile:
        @functools.wraps(function)
        def profiled(*a, **k):
            with jax.named_scope("activation_checkpoint"):
                return remat(*a, **k)

        return profiled
    return remat


# ----------------------------------------------------------------------- RNG tracker
class RNGStatesTracker:
    """Named PRNG-key tracker. Parity: ``CudaRNGStatesTracker`` (``:122``).

    In JAX, keys are values, so 'state save/restore around recompute' is automatic.
    What survives from the reference is the *naming* discipline: a
    ``model-parallel-rng`` stream derived per-MP-rank so dropout differs across tp
    ranks while data-parallel replicas agree (``:210-258``).
    """

    def __init__(self):
        self.states = {}

    def reset(self):
        self.states = {}

    def get_states(self):
        return dict(self.states)

    def set_states(self, states):
        self.states = dict(states)

    def add(self, name: str, seed: int):
        if name in self.states:
            raise Exception(f"RNG state {name} already exists")
        self.states[name] = jax.random.PRNGKey(seed)

    def fork(self, name: str = "model-parallel-rng"):
        """Split and return a fresh key from the named stream."""
        if name not in self.states:
            raise Exception(f"RNG state {name} not added")
        self.states[name], sub = jax.random.split(self.states[name])
        return sub


_tracker = RNGStatesTracker()


def get_rng_tracker() -> RNGStatesTracker:
    """Parity: ``get_cuda_rng_tracker`` (``:253``)."""
    return _tracker


def model_parallel_reseed(key: jax.Array, axis_name: str = "tp") -> jax.Array:
    """Fold the model-parallel coordinate into ``key`` (inside shard_map/pjit) so
    each tp rank draws distinct dropout. Parity:
    ``model_parallel_cuda_manual_seed`` (``:226``)."""
    try:
        idx = jax.lax.axis_index(axis_name)
    except NameError:
        return key
    return jax.random.fold_in(key, idx)
