"""The module contract between models and the engine.

The reference wraps ``torch.nn.Module``; the TPU-native contract is functional — a
model is (init, apply, partition rules):

- ``init(rng) -> params``: build the parameter pytree (fp32).
- ``apply(params, batch, rngs, train) -> (loss, aux)``: pure forward + loss.
- ``partition_specs(param_shapes) -> pytree of PartitionSpec``: the *model-parallel*
  (tp/sp) placement of each leaf. ZeRO sharding is layered on top by the engine's
  :class:`~deepspeed_tpu.runtime.zero.policy.ZeroShardingPolicy`; models never think
  about data parallelism.

``Module`` is a tiny carrier for those three functions so user code can also pass
plain callables.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional, Tuple

import jax
from jax.sharding import PartitionSpec as P

Params = Any
Batch = Any


def _spec_axes(spec) -> set:
    axes = set()
    for entry in tuple(spec):
        if entry is None:
            continue
        axes.update(entry if isinstance(entry, (tuple, list)) else (entry,))
    return axes


def maybe_shard(x, spec: P):
    """``with_sharding_constraint`` against the bound mesh (``mesh_context``).
    No-ops where the constraint has no meaning: no mesh is bound (tests, single
    device), the mesh lacks one of the spec's axes (an absent axis has extent
    1), or we are inside a ``shard_map`` body over one of them (the data is
    already device-local). Anything else the compiler refuses raises — a
    swallowed constraint would show up only as replicated memory."""
    am = jax.sharding.get_abstract_mesh()
    axes = _spec_axes(spec)
    if am.empty or not axes <= set(am.axis_names) or axes & set(am.manual_axes):
        return x
    return jax.lax.with_sharding_constraint(x, spec)


def replicated_specs(param_shapes) -> Any:
    """Default partitioning: every leaf replicated (pure data parallelism)."""
    return jax.tree_util.tree_map(lambda _: P(), param_shapes)


@dataclasses.dataclass(frozen=True)
class Module:
    """A trainable model: functional (init, apply, partition_specs).

    ``to_pipeline(num_stages, num_micro) -> Module`` (optional) rebuilds this
    model as its pipeline-parallel variant — layer stack sharded over the ``pp``
    mesh axis, micro-batches streamed by collective-permute pipelining. The
    engine calls it from ``initialize()`` when the mesh requests ``pp > 1``
    (parity: ``deepspeed.initialize`` returning a ``PipelineEngine`` for a
    ``PipelineModule``, ``deepspeed/__init__.py:124-148``)."""

    init: Callable[[jax.Array], Params]
    apply: Callable[..., Tuple[jax.Array, Dict[str, Any]]]
    partition_specs: Optional[Callable[[Any], Any]] = None
    to_pipeline: Optional[Callable[[int, int], "Module"]] = None
    pipelined: bool = False  # True: apply() already pipelines over the pp axis
    # optional random-LTD rebuild: (keep, layer_ids) -> Module whose listed
    # layers train on `keep`-token subsets (the engine calls it when the
    # data_efficiency random_ltd schedule moves to a new compile bucket)
    with_ltd_keep: Optional[Callable[[int, Tuple[int, ...]], "Module"]] = None
    # the GPTConfig this module was built from, when it is a build_gpt model —
    # checkpoint exporters need it (checkpoint/reference_export.py)
    gpt_config: Optional[Any] = None
    # params subtree (top-level key) whose layer stack runs through
    # zero3_layer_scan — the engine's quantized-gradient program buckets that
    # subtree's dp reduce-scatter per layer INSIDE the backward scan
    # (runtime/zero/gather.py grad_bucket_window) instead of folding it into
    # the monolithic post-backward exchange
    grad_bucket_key: Optional[str] = None
    # optional ZeRO-Infinity decomposition: () -> StreamSpec (models/gpt.py
    # make_stream). Exposes the model as embed / repeated-layer / head units so
    # the param-stream runner (runtime/zero/infinity.py) can keep master
    # weights on host and stream one unit at a time through HBM — the
    # offload_param capability (reference: deepspeed/runtime/zero/
    # partition_parameters.py remote-device "cpu"/"nvme")
    stream: Optional[Callable[[], Any]] = None

    def specs(self, param_shapes) -> Any:
        if self.partition_specs is None:
            return replicated_specs(param_shapes)
        return self.partition_specs(param_shapes)
