"""GPT-family decoder-only language models, TPU-first.

The reference keeps model definitions out-of-repo (Megatron-DeepSpeed / HF) and
ships fixtures (``tests/unit/simple_model.py``) plus fused transformer kernels.
This framework ships a first-class model family because the benchmarks
(BASELINE.json: GPT-2 350M, GPT-NeoX 6.7B/20B, BLOOM-7B1) need runnable flagships.

TPU-first design:
- parameters are one pytree; per-layer weights are *stacked* on a leading ``L`` axis
  and the block is applied with ``lax.scan`` — one compiled layer body regardless of
  depth (fast compiles, natural unit for pipeline stages later);
- Megatron-style tensor-parallel PartitionSpecs: column-parallel qkv/up projections,
  row-parallel out/down projections, vocab-parallel embedding — XLA inserts exactly
  the two all-reduces per block that Megatron does by hand;
- activations are sharding-constrained to batch x sequence axes so sequence
  parallelism ("sp") shards the residual stream;
- rotary or learned positions (NeoX vs GPT-2), pre-LN, optional remat
  (``jax.checkpoint``) = activation checkpointing parity
  (``runtime/activation_checkpointing/checkpointing.py``).
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Dict, NamedTuple, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import PartitionSpec as P

from ..ops.attention import multihead_attention
from . import ssm
from .api import Module, maybe_shard
from .ssm import SsmMixer

BATCH = ("dp", "ep")  # batch sharding axes (matches topology.BATCH_AXES)


def _laid_out(n: int) -> int:
    """A side of a held expert's matrices as the chip lays it out: ``n``
    rounded up to a multiple of 512 where it is wider than a lane row of 128
    (``GPTConfig.moe_width``, ``moe_rows``)."""
    return -(-n // 512) * 512 if n > 128 else n


@dataclasses.dataclass(frozen=True)
class GPTConfig:
    vocab_size: int = 50304
    n_layer: int = 12
    n_head: int = 12
    d_model: int = 768
    d_ff: Optional[int] = None  # default 4*d_model
    max_seq_len: int = 1024
    rotary: bool = False  # False: learned positions (GPT-2); True: RoPE (NeoX)
    rotary_pct: float = 1.0
    tie_embeddings: bool = True
    dropout: float = 0.0
    layer_norm_eps: float = 1e-5
    activation: str = "gelu"  # "gelu" (tanh approx), "gelu_exact", "relu" (OPT)
    parallel_residual: bool = False  # NeoX-style x + attn(ln1 x) + mlp(ln2 x)
    pos_offset: int = 0  # learned-position index offset (OPT uses 2)
    alibi: bool = False  # Bloom: linear attention bias instead of positions
    rotary_interleaved: bool = False  # GPT-J rotate_every_two vs NeoX rotate_half
    embed_layernorm: bool = False  # Bloom: LN right after the token embedding
    lm_head_bias: bool = False  # GPT-J: bias on the (untied) LM head
    remat: bool = False  # activation checkpointing per block
    remat_policy: str = "nothing_saveable"  # jax.checkpoint_policies name
    use_flash: Optional[bool] = None  # None = auto dispatch
    # flash-attention inner tile (autotunable); None: each kernel's own
    # measured one (ops/pallas/flash_attention._TILE)
    flash_block_q: Optional[int] = None
    flash_block_k: Optional[int] = None
    # speed-over-bit-exactness kernel flag (parity: the reference's
    # StochasticTransformer, op_builder/stochastic_transformer.py +
    # csrc/transformer/ds_transformer_cuda.cpp:63 stochastic_mode): attention
    # matmul operands ride the MXU's native bf16 pass, fp32 accumulation
    stochastic_mode: bool = False
    # stochastic-DEPTH training (Huang et al.): drop whole blocks with prob p
    # at train time, survivor delta scaled by 1/(1-p)
    stochastic_depth: float = 0.0
    # GPT-Neo-style alternating local attention: every `period`-th layer
    # (1-indexed within the period; GPT-Neo = period 2, layers 1,3,... local)
    # attends only to the trailing `window_size` positions
    local_attention_period: int = 0  # 0 = all layers global
    window_size: int = 256
    attention_scale: Optional[float] = None  # None = 1/sqrt(head_dim); GPT-Neo = 1.0
    has_lm_head: bool = True  # False: pure encoder (CLIP text tower) — only
    # return_hidden=True is valid; the logits path raises instead of fabricating
    # blocksparse attention: a SparsityConfig here routes every layer through
    # the Pallas blocksparse kernel (graft via ops.sparse_attention.
    # sparse_attention_utils; parity: sparse_attention_utils.py:225)
    sparse_attention: Optional[Any] = None
    # random-LTD (layer token dropping): the listed layers process only a
    # random `random_ltd_keep`-token subset at train time, dropped tokens
    # bypassing the layer (parity: data_routing/basic_layer.py:13; the engine
    # drives `keep` from the scheduled data_efficiency config)
    random_ltd_layer_ids: Tuple[int, ...] = ()
    random_ltd_keep: Optional[int] = None
    # sequence-parallel attention over the sp mesh axis: "dense" lets GSPMD
    # gather k/v (O(T) memory per chip); "ring" streams k/v blocks by
    # collective-permute, "ulysses" all-to-alls heads<->sequence — the
    # long-context memory savers (parallel/{ring_attention,ulysses}.py)
    seq_parallel_impl: str = "dense"
    # chunked cross-entropy: compute the LM-head logits + logsumexp over
    # `loss_chunk`-token sequence slices in a rematted scan, so the fp32
    # [B, T, V] logits tensor (3.07 GB at bs16/seq1024/50k vocab — the
    # largest single buffer at the v5e fit boundary, see docs/MFU_NOTES.md)
    # never materializes. 0 = off (whole-sequence loss).
    loss_chunk: int = 0
    # ---- the block as data, and the loop over the stack. The defaults say
    # the GPT-2 / GPT-NeoX block run once; every other value is what a
    # later architecture's block has (``benchmark/reference/ouro_ref.py``
    # has the equations of the first). Paths that do not compute a value
    # refuse it by name (:func:`require_default_block`).
    norm: str = "layernorm"  # "rmsnorm": x * rsqrt(mean(x^2) + eps) * gain,
    #                          no ``*_bias`` leaf beside a gain
    mlp_gated: bool = False  # act(h Wg) * (h Wu) before the down projection
    #                          (``mlp_gate_w``), the product taken in float32
    linear_bias: bool = True  # False: the linears have no ``*_b`` leaf
    post_norm: bool = False  # a norm on each sublayer's output before the
    #                          residual add (``post_attn_scale``,
    #                          ``post_mlp_scale``)
    rope_theta: float = 10000.0  # rotary base
    # rotate q and k in float32 and round the result once (else the cosines
    # and sines are rounded to the compute type first, as GPT-NeoX's are)
    rotary_float32: bool = False
    ut_steps: int = 1  # the stack runs this many times over one set of
    #                    weights; a pass attends to its own keys and values,
    #                    so the caches hold ``cache_layers(cfg)`` layers
    loop_norm: bool = False  # the final norm also closes every earlier pass
    # the serving programs return the residual stream after these layers of
    # every pass (after the pass's closing norm where that is n_layer), and
    # the embedding rows first: the boundaries a comparison held stretch by
    # stretch asks for. () returns none.
    state_layers: Tuple[int, ...] = ()
    # an exit gate on each pass's closing state (``exit_gate_w``/``_b``);
    # at 1.0 it is held as weights and never read, any other value would
    # end a token's passes early, which nothing here computes
    early_exit_threshold: Optional[float] = None
    # a linear's output stays float32 up to the next point that rounds
    # anyway (a norm's output, a matmul's input, the add to the stream):
    # the MXU accumulates in float32 either way, so it costs the wider
    # outputs' bytes, and a sublayer rounds half as often
    linear_out_float32: bool = False
    # the serving forwards (prefill chunks, prompts to pages, decode) keep
    # the residual stream in float32, and a float32 activation meets a bf16
    # matrix in two passes (:func:`_wm`): the token's sublayers round at the
    # cache, at the experts' input and inside attention's products, nowhere
    # else. Free where the weights' bytes bound a step (decode), about 40%
    # of a prefill chunk's matmul time
    stream_float32: bool = False
    # ---- the attention sublayer, the rotary scaling and the kinds of layer
    # as data (``benchmark/reference/deepseek_v2_ref.py`` has the equations
    # of the first model that sets them). "mla": latent attention, a
    # low-rank query path (``q_lora_rank``) and a low-rank key-value path
    # whose latent (``kv_lora_rank``, normed) and one rotated key of
    # ``qk_rope_dim`` for all heads are what a token caches; a head is
    # [qk_nope_dim | qk_rope_dim] wide for the scores and ``v_head_dim`` for
    # the values.
    attn_kind: str = "mha"
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_dim: int = 0
    qk_rope_dim: int = 0
    v_head_dim: int = 0
    rope_scaling: Optional["YarnScaling"] = None
    # a routed feed-forward in every layer from ``moe_dense_layers`` on: a
    # float32 softmax router over ``moe_experts`` in ``moe_groups`` groups,
    # the ``moe_topk_groups`` best groups kept and the ``moe_k`` largest
    # inside them taken, gates not renormalised and times ``moe_scale``;
    # experts and a shared expert are gated MLPs of ``moe_d_ff`` and
    # ``moe_shared_d_ff``. ``moe_held`` = (first, count) says which experts
    # this chip holds (None: all): the router keeps its full width and the
    # layer computes what its own experts give (``moe/dropless.py``).
    moe_experts: int = 0
    moe_held: Optional[Tuple[int, int]] = None
    moe_k: int = 0
    moe_groups: int = 1
    moe_topk_groups: int = 1
    moe_d_ff: int = 0
    moe_shared_d_ff: int = 0
    moe_scale: float = 1.0
    moe_dense_layers: int = 0
    # the gates of the ``moe_k`` experts taken are divided by their sum
    # before ``moe_scale`` (a published config's ``norm_topk_prob``)
    moe_norm_topk: bool = False
    # ---- fewer key-value heads than query heads, kinds of attention in a
    # period, a gate a head (``benchmark/reference/laguna_ref.py`` has the
    # equations of the first model that sets them). "gqa": ``n_kv_head``
    # heads of keys and values, each read by ``n_head / n_kv_head`` query
    # heads (query head ``i`` reads ``i // (n_head / n_kv_head)``), a head
    # ``head_width`` wide whatever ``d_model / n_head`` is; a token caches
    # ``n_kv_head`` rows a cache layer. ``attn_window`` > 0: a query sees
    # its last ``attn_window`` positions, its own included, and the layer's
    # cache is a ring of that many rows a slot, not pages
    # (:func:`init_paged_cache`). ``attn_gate``: attention's output a head
    # times ``sigmoid(h W_gate)``, ``h`` the normed input, before the
    # out-projection. ``attn_period``: layer ``l`` is of kind
    # ``attn_period[l % len]`` (:class:`AttnKind`: its own head count,
    # window and rotary set in place of the fields here); a kind with a
    # window and one without, each a stack of its own in the tree.
    n_kv_head: int = 0
    head_width: int = 0
    attn_window: int = 0
    attn_gate: bool = False
    attn_period: Tuple["AttnKind", ...] = ()
    # ---- a layer that is ONE sublayer, and the kinds of such layers as data
    # (``benchmark/reference/nemotron_h_ref.py`` has the equations of the
    # first model that sets them). ``layer_pattern``: a character a layer,
    # layer ``l`` is ``x + f(norm(x))`` with ``f`` a Mamba-2 mixer (``M``,
    # ``models/ssm.py``, its sizes in ``ssm``), a routed feed-forward
    # (``E``) or attention (``*``, ``attn_kind='gqa'``); a stack a kind in
    # the tree (``ssm_blocks``, ``moe_blocks``, ``attn_blocks``). Only the
    # ``*`` layers cache keys and values; an ``M`` layer keeps a state and a
    # convolution window a sequence or decode slot, no row a token
    # (:func:`init_paged_cache`). ``moe_score``: the router scores by
    # "softmax" or by "sigmoid" of its logits; ``moe_score_bias``: the
    # experts are CHOSEN by score plus a bias an expert (``router_bias``),
    # the gates stay the scores.
    layer_pattern: str = ""
    ssm: Optional[SsmMixer] = None
    moe_score: str = "softmax"
    moe_score_bias: bool = False
    # the held experts take a float32 stream's rows, and their own middle, in
    # two bf16 halves inside the one grouped product (the matrices read
    # once, the groups twice as long) and not rounded to bf16 in one: what a
    # routed layer then adds to the stream's error is the split's 2^-17, not
    # a rounding's 2^-9. For a model in which a flipped expert at an EARLIER
    # position reaches the compared one through a mixer's convolution window
    # (``moe/dropless.held_experts_ffn``; PERF.md PR 40 has the readings)
    moe_two_pass: bool = False
    # the ``*`` layers of a ``layer_pattern`` cache keys and values in
    # float32 whatever the served type is, and a prompt's products over them
    # are taken at full precision (``cache_dtype``, ``_gqa_attention``; the
    # decode kernel's products over float32 pages are float32's): rows
    # rounded to bf16 move that layer's output by 1.7e-3 of itself, which
    # the routers after it turn into flipped experts (PERF.md PR 40)
    attn_float32: bool = False
    # ---- a layer whose attention AND Mamba-2 mixer read the same normed
    # input side by side, their outputs summed into one delta, then a dense
    # gated MLP (``benchmark/reference/falcon_h1_ref.py`` has the equations of
    # the first model that sets them): ``ssm`` with NO ``layer_pattern`` and
    # ``attn_kind='gqa'``. Every layer then keeps pages AND a state a slot
    # (:func:`layer_runs`: one run whose mixer is ``attn+ssm``).
    # ``multipliers``: the fixed scalars a muP-parametrised model multiplies
    # its activations by (:class:`Multipliers`), one frozen value.
    multipliers: Optional["Multipliers"] = None
    # ---- latent attention of more than one kind in one model, and a learned
    # selection of the rows a full layer reads
    # (``benchmark/reference/dots3_note_ref.py`` has the equations of the
    # first model that sets them): with ``attn_kind='mla'`` an
    # ``attn_period``'s kinds each say their own latent ranks, head widths,
    # head count, window and rotary base (:class:`AttnKind`), ``attn_gate``
    # is PR 38's gate a head, and a kind with a window keeps a latent ring a
    # slot. ``index_topk`` > 0: an indexer of ``index_heads`` small heads of
    # ``index_dim`` scores every cached position (``I(t, s) = sum_j w_tj
    # relu(qI_tj . kI_s)``, the first ``qk_rope_dim`` dimensions of ``qI``
    # and ``kI`` rotated) and the softmax runs over the ``index_topk``
    # positions of largest score (ties to the lower position; all of them
    # while there are no more); a token then caches its index key (``kI``,
    # ``index_dim`` numbers) beside its latent row, in pages of the same
    # block table. ``mla_lora_rescale``: the normed query latent times
    # ``sqrt(d_model / q_lora_rank)`` and the normed key-value latent times
    # ``sqrt(d_model / kv_lora_rank)``, plain factors after the norms.
    index_heads: int = 0
    index_dim: int = 0
    index_topk: int = 0
    mla_lora_rescale: bool = False
    # the index keys are cached in float32 whatever the served type is, and
    # the index scores are taken at full precision from float32 queries
    # (``attn_float32``'s counterpart for the indexer): a selection is
    # discrete, and where the ``index_topk``-th and the next score lie closer
    # than a bf16 key's rounding the served path keeps another row than a
    # float32 forward, at EVERY position of a prompt; with seeded weights
    # attention over the selected rows is nearly flat, ten rows of 2,048
    # swapped move a full layer's output by a tenth, and the compared
    # position reads those rows' latents (PERF.md PR 51 has the readings)
    index_float32: bool = False

    def __post_init__(self):
        if self.norm not in ("layernorm", "rmsnorm"):
            raise ValueError(f"norm must be layernorm or rmsnorm, got "
                             f"{self.norm!r}")
        if self.attn_kind not in ("mha", "mla", "gqa"):
            raise ValueError(f"attn_kind must be mha, mla or gqa, got "
                             f"{self.attn_kind!r}")
        if self.attn_kind != "mha" or self.moe_experts:
            if self.linear_bias or self.norm != "rmsnorm" or self.post_norm:
                raise ValueError(
                    "latent attention, key-value heads and routed layers are "
                    "computed with RMSNorm, bias-free linears and no norm on "
                    "a sublayer's output (norm='rmsnorm', linear_bias=False, "
                    "post_norm=False)")
        if self.attn_kind == "mla":
            if not (self.rotary and not self.rotary_interleaved
                    and not self.alibi):
                raise ValueError("attn_kind='mla' rotates qk_rope_dim "
                                 "dimensions of a head, rotate-half form "
                                 "(rotary=True, rotary_interleaved=False)")
            if min(self.q_lora_rank, self.kv_lora_rank, self.qk_nope_dim,
                   self.qk_rope_dim, self.v_head_dim) < 1 \
                    or self.qk_rope_dim % 2:
                raise ValueError("attn_kind='mla' needs q_lora_rank, "
                                 "kv_lora_rank, qk_nope_dim, v_head_dim and "
                                 "an even qk_rope_dim")
        if self.attn_kind == "gqa":
            heads = tuple(k.n_head for k in self.attn_period) or (
                self.n_head,)
            if (not (self.rotary and not self.rotary_interleaved
                     and not self.alibi)
                    or self.n_kv_head < 1 or self.head_width < 1
                    or any(h % self.n_kv_head for h in heads)
                    or self.local_attention_period
                    or self.sparse_attention is not None):
                raise ValueError(
                    "attn_kind='gqa' needs n_kv_head dividing every kind's "
                    "n_head, a head_width, rotate-half rotary and no other "
                    "bias or sparsity on the scores (rotary=True, "
                    "rotary_interleaved=False, alibi=False)")
        elif (self.n_kv_head or self.head_width or (
                self.attn_kind != "mla" and (
                    self.attn_window or self.attn_gate or self.attn_period))):
            raise ValueError(
                "n_kv_head and head_width are attn_kind='gqa' fields, "
                "attn_window, attn_gate and attn_period fields of "
                "attn_kind='gqa' and 'mla'")
        if self.index_topk or self.index_heads or self.index_dim:
            if (self.attn_kind != "mla" or self.attn_window
                    or min(self.index_topk, self.index_heads) < 1
                    or self.index_dim < self.qk_rope_dim):
                raise ValueError(
                    "index_topk, index_heads and index_dim say the indexer "
                    "of a latent layer without a window (attn_kind='mla'): "
                    "all three set, index_dim at least qk_rope_dim")
        if (self.mla_lora_rescale or self.index_float32) \
                and self.attn_kind != "mla":
            raise ValueError("mla_lora_rescale and index_float32 are "
                             "attn_kind='mla' fields")
        if self.attn_period:
            kinds = set(self.attn_period)
            if (len(kinds) != len({bool(k.window) for k in kinds})
                    or self.attn_window or self.ut_steps != 1):
                raise ValueError(
                    f"attn_period {self.attn_period}: at most one kind "
                    "with a window and one without, attn_window left to the "
                    "kinds, the stack run once (ut_steps=1)")
        if self.moe_score not in ("softmax", "sigmoid"):
            raise ValueError(f"moe_score must be softmax or sigmoid, got "
                             f"{self.moe_score!r}")
        if self.ssm is not None and not self.layer_pattern:
            if (self.attn_kind != "gqa" or self.moe_experts
                    or self.attn_period or self.attn_window
                    or not self.mlp_gated or self.ut_steps != 1
                    or self.parallel_residual):
                raise ValueError(
                    "ssm without a layer_pattern: every layer runs attention "
                    "and the mixer on one normed input, then a gated MLP "
                    "(attn_kind='gqa', mlp_gated=True; no routed layers, "
                    "attn_period, attn_window or loop, sublayers in sequence)")
        elif self.layer_pattern:
            kinds = set(self.layer_pattern)
            if (kinds - set("ME*") or len(self.layer_pattern) != self.n_layer
                    or ("M" in kinds) != (self.ssm is not None)
                    or ("E" in kinds) != bool(self.moe_experts)
                    or ("*" in kinds) != (self.attn_kind == "gqa")
                    or (self.attn_float32 and "*" not in kinds)
                    or self.attn_kind == "mla" or self.attn_period
                    or self.attn_window or self.moe_dense_layers
                    or self.ut_steps != 1 or self.parallel_residual):
                raise ValueError(
                    f"layer_pattern {self.layer_pattern!r}: a character of "
                    f"M, E or * for each of {self.n_layer} layers; M needs "
                    "ssm, E moe_experts, * attn_kind='gqa', and each of "
                    "those a layer of its kind; no attn_period, attn_window, "
                    "moe_dense_layers or loop, sublayers in sequence")
        if self.moe_experts:
            first, count = self.held_experts
            per_group = self.moe_experts // max(self.moe_groups, 1)
            if (self.moe_experts % max(self.moe_groups, 1)
                    or not 1 <= self.moe_topk_groups <= self.moe_groups
                    or not 1 <= self.moe_k <= self.moe_topk_groups * per_group
                    or min(self.moe_d_ff, self.moe_shared_d_ff) < 0
                    or self.moe_d_ff < 1
                    or not 0 <= self.moe_dense_layers < self.n_layer
                    or first < 0 or count < 1
                    or first + count > self.moe_experts):
                raise ValueError(
                    f"routed layers: {self.moe_experts} experts in "
                    f"{self.moe_groups} groups, {self.moe_topk_groups} "
                    f"groups and {self.moe_k} experts a token, held "
                    f"{self.moe_held}, {self.moe_dense_layers} dense layers "
                    f"of {self.n_layer}: not a layer this computes")
        if self.ut_steps < 1:
            raise ValueError(f"ut_steps {self.ut_steps} must be at least 1")
        if self.early_exit_threshold not in (None, 1.0):
            raise ValueError(
                f"early_exit_threshold {self.early_exit_threshold}: only 1.0 "
                "(the exit gate never fires) is computed")
        marks = tuple(self.state_layers)
        if marks != tuple(sorted(set(marks))) or any(
                not 1 <= m <= self.n_layer for m in marks):
            raise ValueError(f"state_layers {marks} must rise within 1.."
                             f"{self.n_layer}")

    @property
    def ffn_dim(self) -> int:
        return self.d_ff if self.d_ff is not None else 4 * self.d_model

    @property
    def head_dim(self) -> int:
        if self.head_width:
            return self.head_width
        assert self.d_model % self.n_head == 0
        return self.d_model // self.n_head

    @property
    def kv_heads(self) -> int:
        """Heads of keys and values a token caches in one cache layer."""
        return self.n_kv_head or self.n_head

    @property
    def latent_width(self) -> int:
        """Width of a cached latent row: ``kv_lora_rank + qk_rope_dim``
        rounded up to whole lanes of 128 (640 for 576), the columns past the
        rotated key zero. The TPU lays an array whose last dimension is not a
        multiple of 128 out with another dimension minor (a pool [5, 1, 6145,
        64, 576] with the pages minor), and a kernel that reads rows then has
        the whole pool copied before and after every call (compile-only,
        PERF.md PR 34)."""
        return -(-(self.kv_lora_rank + self.qk_rope_dim) // 128) * 128

    @property
    def moe_width(self) -> int:
        """Columns of a held expert's up (and gate) matrix, rows of its down
        matrix: ``moe_d_ff`` rounded up to a multiple of 512 where it is
        wider than a lane row of 128 (2048 for 1856; 512 and 1536 stay), the
        columns and rows past ``moe_d_ff`` zero, which an activation that
        maps 0 to 0 leaves without effect. Two things the chip decided (my
        chip runs, PERF.md PR 40). The TPU lays a matrix whose last dimension
        is no multiple of 128 out with another dimension minor, and the
        grouped product over the experts' stacks then copies the whole up
        stack every dispatch (2.55 GB at 4 x 64 experts of 2688 x 1856; under
        that pressure the compiler rematerialised the decode step's in-place
        window update and read a window it had already shifted). And XLA's
        ``ragged-dot`` reads 64 experts of 2688 x 1920 (15 lane rows) at 65
        GB/s where 2688 x 2048 reads at 187: 10.2 ms a product against
        3.8. Since PR 42 the products on the chip are ``grouped_dot``'s, whose
        tiles are ours, and the second reason is gone: it reads 2688 x 1920
        at 648 GB/s, 1.02 ms a product for the 1.21 of 3072 x 2048 (16% fewer
        bytes, 16% less time; my chip runs, PERF.md PR 42). The first stands:
        1856 columns are no whole lanes and Mosaic refuses the matrix's copy
        (``grouped_dot._plan``: no tiles). So whole lanes (1920) are what
        the chip wants now, not multiples of 512; the padding stays as it is
        because the weights' layout is part of the benchmark's cell."""
        return _laid_out(self.moe_d_ff)

    @property
    def moe_rows(self) -> int:
        """Rows of a held expert's up matrix, columns of its down matrix:
        ``d_model`` rounded up likewise (3072 for 2688; 2048 and 5120 stay),
        zeros past ``d_model``; the grouped products take the stream's rows
        padded with zeros and hand back the first ``d_model`` columns.
        ``ragged-dot`` over 64 experts and 3072 rows, my chip runs (PERF.md PR
        40): 2688 x 2048 3.77 ms and 2048 x 2688 5.65 ms, 3072 x 2048 2.85 and
        2048 x 3072 2.87: a quarter more bytes read in two thirds the
        time. That was ``ragged-dot``; ``grouped_dot`` (PR 42) takes any
        number of rows (2688 is 21 lane rows; compile-only and my chip run:
        1920 x 2688 at 641 GB/s, 1.02 ms), so this padding is no longer what
        the chip wants either, and stays for the cell's sake."""
        return _laid_out(self.d_model)

    @property
    def held_experts(self) -> Tuple[int, int]:
        """(first, count) of the routed experts this chip holds."""
        return (tuple(self.moe_held) if self.moe_held is not None
                else (0, self.moe_experts))

    def layer_params(self) -> int:
        """Parameters of one block: the matrices, the linears' biases, the
        norms' gains (and biases)."""
        d, f = self.d_model, self.ffn_dim
        ups = 2 if self.mlp_gated else 1
        matrices = 4 * d * d + (ups + 1) * d * f
        biases = (5 * d + ups * f) if self.linear_bias else 0
        norms = ((4 if self.post_norm else 2) * d
                 * (2 if self.norm == "layernorm" else 1))
        return matrices + biases + norms

    def num_params(self) -> int:
        d, v = self.d_model, self.vocab_size
        emb = v * d + (0 if self.rotary else self.max_seq_len * d)
        return self.n_layer * self.layer_params() + emb + 2 * d


# what says another attention sublayer, cache or kind of layer than keys and
# values a head over one stack of dense blocks
KIND_FIELDS = ("attn_kind", "rope_scaling", "moe_experts", "moe_held",
               "moe_norm_topk", "n_kv_head", "head_width", "attn_window",
               "attn_gate", "attn_period", "layer_pattern", "ssm",
               "moe_score", "moe_score_bias", "moe_two_pass",
               "attn_float32", "multipliers", "index_heads", "index_dim",
               "index_topk", "mla_lora_rescale", "index_float32")
BLOCK_FIELDS = KIND_FIELDS + (
    "norm", "mlp_gated", "linear_bias", "post_norm", "rope_theta",
    "rotary_float32", "ut_steps", "loop_norm", "state_layers",
    "early_exit_threshold", "linear_out_float32", "stream_float32")


def require_default_block(cfg: GPTConfig, where: str,
                          fields: Tuple[str, ...] = BLOCK_FIELDS) -> None:
    """Raise for a config that says another block than the GPT-2 / GPT-NeoX
    one, or a loop: ``where`` computes neither, and says so by the field's
    name instead of computing something else. With ``fields=KIND_FIELDS``
    only for latent attention and routed layers, which ``where`` does not
    carry though it carries the other blocks. A config whose mixers keep a
    state (``ssm``) is refused by that, whatever else it says: a state a
    sequence or decode slot is what none of these paths carries."""
    if cfg.ssm is not None:
        raise ValueError(
            f"{where} does not support ssm={cfg.ssm!r}: it carries keys and "
            "values a token, not a state-space mixer's state and convolution "
            "window a sequence or decode slot (models/ssm.py, "
            "gpt.init_paged_cache)")
    for name in fields:
        value = getattr(cfg, name)
        if value != GPTConfig.__dataclass_fields__[name].default:
            raise ValueError(
                f"{where} does not support {name}={value!r}: it computes "
                + ("keys and values a head over one stack of dense blocks"
                   if fields is KIND_FIELDS else
                   "the layer-norm, ungated, biased block run once")
                + " (models/gpt.py, GPTConfig)")


@dataclasses.dataclass(frozen=True)
class YarnScaling:
    """YaRN rotary scaling (Peng et al. 2023), the ``rope_scaling`` group of a
    published config: frequencies blended between interpolated (``/ factor``)
    and extrapolated by a linear ramp over the dimensions whose wavelength
    makes between ``beta_slow`` and ``beta_fast`` turns in
    ``original_max_len`` positions."""
    factor: float
    original_max_len: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0

    @staticmethod
    def _m(factor: float, mscale: float) -> float:
        return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0

    @property
    def cos_sin_factor(self) -> float:
        """What the cosines and sines are multiplied by."""
        return (self._m(self.factor, self.mscale)
                / self._m(self.factor, self.mscale_all_dim))

    @property
    def softmax_factor(self) -> float:
        """What the softmax scale is multiplied by: ``m^2`` of
        ``mscale_all_dim`` (1 where that is 0)."""
        if not self.mscale_all_dim:
            return 1.0
        return self._m(self.factor, self.mscale_all_dim) ** 2

    def inv_freq(self, half: int, theta: float) -> np.ndarray:
        """The ``half`` rotary frequencies, float32."""
        dim = 2 * half
        extra = 1.0 / (theta ** (np.arange(half, dtype=np.float64) / half))
        inter = extra / self.factor

        def turn_dim(turns):
            return (dim * math.log(self.original_max_len
                                   / (turns * 2 * math.pi))
                    / (2 * math.log(theta)))

        low = max(math.floor(turn_dim(self.beta_fast)), 0)
        high = min(math.ceil(turn_dim(self.beta_slow)), dim - 1)
        ramp = np.clip((np.arange(half, dtype=np.float64) - low)
                       / max(high - low, 0.001), 0.0, 1.0)
        return (inter * ramp + extra * (1.0 - ramp)).astype(np.float32)


@dataclasses.dataclass(frozen=True)
class Multipliers:
    """The fixed scalars of a muP-parametrised model (``GPTConfig.
    multipliers``; a published ``falcon_h1`` config has twelve), each where
    the family's modelling code applies it: ``embed`` on the embedding rows,
    ``head`` on the logits; ``attn_in`` on the normed input of q, k and v,
    ``key`` on the keys before the rotation, ``attn_out`` on the attention's
    out-projection; ``ssm_in`` on the normed input of the mixer's
    in-projection, ``ssm`` on that projection's five segments ``z | x | B |
    C | dt``, ``ssm_out`` on the mixer's out-projection; ``mlp_gate`` on the
    gate's pre-activation, ``mlp_down`` on the MLP's output. None is folded
    into a weight: the tree holds the matrices as published."""
    embed: float = 1.0
    head: float = 1.0
    attn_in: float = 1.0
    key: float = 1.0
    attn_out: float = 1.0
    ssm_in: float = 1.0
    ssm: Tuple[float, float, float, float, float] = (1.0,) * 5
    ssm_out: float = 1.0
    mlp_gate: float = 1.0
    mlp_down: float = 1.0

    def __post_init__(self):
        if len(self.ssm) != 5:
            raise ValueError(f"ssm multipliers {self.ssm}: one a segment of "
                             "the in-projection, z | x | B | C | dt")


def _times(cfg: "GPTConfig", a, name: str):
    """``a`` times the config's multiplier ``name``; ``a`` itself where the
    config has none or it is 1 (no operation is traced)."""
    m = 1.0 if cfg.multipliers is None else getattr(cfg.multipliers, name)
    return a if m == 1.0 else a * m


@dataclasses.dataclass(frozen=True)
class AttnKind:
    """One kind of attention layer of a model that mixes them
    (``GPTConfig.attn_period``): what differs from kind to kind. ``window``
    0 sees the whole context. ``rotary_pct`` of a head's dimensions are
    rotated, at ``rope_theta`` and under ``rope_scaling``. A kind of latent
    attention also says its own geometry (0: the config's): the latent
    ranks, a head's nope, rope and value widths, and the indexer of a kind
    that selects the rows it reads (``index_topk`` 0: it reads them all)."""
    n_head: int
    window: int = 0
    rotary_pct: float = 1.0
    rope_theta: float = 10000.0
    rope_scaling: Optional[YarnScaling] = None
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_dim: int = 0
    qk_rope_dim: int = 0
    v_head_dim: int = 0
    index_heads: int = 0
    index_dim: int = 0
    index_topk: int = 0

    @property
    def name(self) -> str:
        """What the kind's stacks and its trace scope are called by."""
        return "window" if self.window else "full"


def kind_view(cfg: GPTConfig, kind: Optional[AttnKind]) -> GPTConfig:
    """``cfg`` as one kind of its layers sees it: the kind's head count,
    window and rotary set in the fields every function of the block reads,
    and no period. ``cfg`` itself where it has no kinds."""
    if kind is None:
        return cfg
    latent = {name: getattr(kind, name) for name in (
        "q_lora_rank", "kv_lora_rank", "qk_nope_dim", "qk_rope_dim",
        "v_head_dim") if getattr(kind, name)}
    return dataclasses.replace(
        cfg, n_head=kind.n_head, attn_window=kind.window,
        rotary_pct=kind.rotary_pct, rope_theta=kind.rope_theta,
        rope_scaling=kind.rope_scaling, attn_period=(),
        index_heads=kind.index_heads, index_dim=kind.index_dim,
        index_topk=kind.index_topk, **latent)


# a ``layer_pattern`` character: (stack, mixer sublayer, feed-forward sublayer)
PATTERN_LAYERS = {"M": ("ssm_blocks", "ssm", ""),
                  "E": ("moe_blocks", "", "routed"),
                  "*": ("attn_blocks", "attn", "")}


class LayerRun(NamedTuple):
    """Consecutive layers of one stack of the parameter tree, and what such a
    layer is: the one place that says it (:func:`layer_runs`)."""
    name: str                   # the stack
    offset: int                 # the run's first layer inside the stack
    count: int
    first: int                  # the run's first layer in the model
    kind: Optional[AttnKind]    # None: the config's one kind
    cache_first: int            # its first cache layer among those of its
    #                             attention's cache kind (pages or rings)
    ring: bool                  # a window layer: its cache is a ring a slot
    mixer: str = "attn"         # what mixes positions: "attn", "ssm" (a
    #                             state-space mixer), "attn+ssm" (both on one
    #                             normed input, outputs summed) or "" (none)
    ffn: str = "dense"          # the feed-forward: "dense", "routed" or ""
    per_pass: int = 0           # cache layers of its cache kind in one pass
    state_first: int = 0        # its first state layer among the states a
    #                             slot keeps (a run with a state-space mixer)

    @property
    def attends(self) -> bool:
        """Its layers cache keys and values a token."""
        return "attn" in self.mixer

    @property
    def mixes(self) -> bool:
        """Its layers keep a state and a convolution window a slot."""
        return "ssm" in self.mixer

    def cache_layer(self, i, u):
        """The cache layer, among those of the run's cache kind, that layer
        ``i`` of the model (one of the run's) reads and writes in pass ``u``
        (0, untraced, where the stack runs once: nothing is added)."""
        ahead = self.first - self.cache_first
        at = i - ahead if ahead else i
        return at if isinstance(u, int) and u == 0 else self.per_pass * u + at

    def state_layer(self, i):
        """The state layer that layer ``i`` of the model (one of the run's)
        reads and writes: a state-space mixer's stack runs once."""
        ahead = self.first - self.state_first
        return i - ahead if ahead else i


def layer_runs(cfg: GPTConfig) -> Tuple[LayerRun, ...]:
    """The model's layers in the order the forward applies them, as runs of
    like layers: ``blocks`` (a dense feed-forward) and ``moe_blocks`` (a
    routed one, from ``moe_dense_layers`` on), each split by the kinds of
    ``attn_period`` (``blocks_full``, ``moe_blocks_window``, ...). A stack
    of the tree holds every layer of its name; a run is a slice of it. With
    a ``layer_pattern`` a layer is ONE sublayer, and a run is consecutive
    layers of one character, of the stack ``PATTERN_LAYERS`` names; with
    ``ssm`` and no pattern every layer of ``blocks`` has both mixers
    (``attn+ssm``), a page layer AND a state layer each. The five spellings
    are read here and nowhere else: everything that asks what a layer is, or
    where its cache or its state lies, asks a run."""
    def layer(l):
        """(stack, kind, ring, mixer, ffn) of layer ``l``."""
        if cfg.layer_pattern:
            name, mixer, ffn = PATTERN_LAYERS[cfg.layer_pattern[l]]
            return name, None, False, mixer, ffn
        routed = cfg.moe_experts and l >= cfg.moe_dense_layers
        name = "moe_blocks" if routed else "blocks"
        kind = None
        if cfg.attn_period:
            kind = cfg.attn_period[l % len(cfg.attn_period)]
            name = f"{name}_{kind.name}"
        ring = bool(kind.window if kind is not None else cfg.attn_window)
        return (name, kind, ring, "attn" if cfg.ssm is None else "attn+ssm",
                "routed" if routed else "dense")

    # cache layers are counted a cache kind (pages: False, rings: True),
    # states in one count of their own
    runs, in_stack, cached, states, l = [], {}, {False: 0, True: 0}, 0, 0
    while l < cfg.n_layer:
        name, kind, ring, mixer, ffn = at = layer(l)
        n = 1
        while l + n < cfg.n_layer and layer(l + n) == at:
            n += 1
        run = LayerRun(name, in_stack.get(name, 0), n, l, kind, cached[ring],
                       ring, mixer, ffn, state_first=states)
        runs.append(run)
        in_stack[name] = in_stack.get(name, 0) + n
        cached[ring] += n * run.attends
        states += n * run.mixes
        l += n
    return tuple(run._replace(per_pass=cached[run.ring]) for run in runs)


def cache_row(cfg: GPTConfig, ring: bool = False) -> Tuple[int, int, int]:
    """(pools, heads, width) of what one token caches in one cache layer: a
    key and a value row for each of ``kv_heads`` heads (``n_head``, or the
    fewer of ``n_kv_head``), or with latent attention ONE row ``[latent |
    rotated key]`` with no head axis and no value pool (the values are the
    first ``kv_lora_rank`` columns of it). The cache's kind, for everything
    that sizes or addresses one. Where the config's kinds of latent layer
    differ in their ranks the row is a RUN's, not the config's: ``ring``
    asks for the window layers' (a slot's ring), else the layers' in pages
    (:func:`layer_runs`; a config without such a run has its own row)."""
    if cfg.attn_kind == "mla":
        return 1, 1, _run_view(cfg, ring).latent_width
    return 2, cfg.kv_heads, cfg.head_dim


def _run_view(cfg: GPTConfig, ring: bool) -> GPTConfig:
    """``cfg`` as its window layers see it (``ring``) or as its layers in
    pages do (:func:`kind_view` of the first such run; ``cfg`` where it has
    none, or no kinds)."""
    if not cfg.attn_period:
        return cfg
    return next((kind_view(cfg, r.kind) for r in layer_runs(cfg)
                 if r.attends and r.ring == ring), cfg)


def index_layers(cfg: GPTConfig) -> int:
    """Cache layers that keep an index key a token beside its row: the layers
    in pages whose kind selects the rows it reads (``index_topk``). All of
    the layers in pages or none: they are of one kind."""
    return paged_layers(cfg)[0] if _run_view(cfg, False).index_topk else 0


def index_topk_of(cfg: GPTConfig) -> int:
    """The rows a selection of the config's layers in pages keeps; 0 where
    they read every row."""
    return _run_view(cfg, False).index_topk


def chunks_to_pages(cfg: GPTConfig) -> bool:
    """A chunk of a long prompt can go to its pages as its layers compute it
    and read the earlier chunks back from there
    (``paged_prefill_step(chunk=)``): plain attention, and latent attention
    whose cache has kinds (pages under a selection, index keys, rings: such a
    model has no dense cache at all, :func:`init_cache`)."""
    return cfg.attn_kind == "mha" or (cfg.attn_kind == "mla" and bool(
        cfg.attn_period or cfg.index_topk or cfg.attn_window))


def cache_layers(cfg: GPTConfig) -> int:
    """Key and value layers a forward walks: one a pass and layer, cache
    layer ``n_layer * u + l`` in the order the forward applies them. What a
    dense cache and every count of a step's layers is sized by; a page pool
    holds :func:`paged_layers` of them. Only a layer with attention caches
    keys and values."""
    return cfg.ut_steps * sum(r.count for r in layer_runs(cfg) if r.attends)


def cache_dtype(cfg: GPTConfig, dtype):
    """The type keys and values are cached in: the served ``dtype``, or
    float32 where the config says so (``attn_float32``)."""
    return jnp.float32 if cfg.attn_float32 else dtype


def ssm_layers(cfg: GPTConfig) -> int:
    """Layers that keep a state and a convolution window a sequence (a
    decode slot in the serving cache) and no row a token: those whose mixer
    is a state-space one."""
    return sum(r.count for r in layer_runs(cfg) if r.mixes)


def ssm_bytes_per_slot(cfg: GPTConfig) -> int:
    """HBM bytes the mixers' states and windows cost a decode slot,
    whatever its request's length (float32)."""
    return ssm_layers(cfg) * cfg.ssm.slot_bytes() if cfg.ssm else 0


def paged_layers(cfg: GPTConfig) -> Tuple[int, int]:
    """(cache layers kept in pages, cache layers kept as a ring a slot) of
    the serving cache: a layer with a window keeps the last
    :func:`ring_rows` rows of each slot, whatever the request's length,
    every other layer pages through a block table."""
    rings = cfg.ut_steps * sum(r.count for r in layer_runs(cfg) if r.ring)
    return cache_layers(cfg) - rings, rings


def window_of(cfg: GPTConfig) -> int:
    """The window of the config's window layers; 0 where it has none."""
    return max((kind_view(cfg, r.kind).attn_window for r in layer_runs(cfg)
                if r.ring), default=0)


def ring_rows(cfg: GPTConfig, page_size: int) -> int:
    """Rows of a slot's ring: the window, up to whole pages (the decode
    kernel reads a ring as the slot's pages); 0 without a window."""
    return -(-window_of(cfg) // page_size) * page_size


# Named presets used by benchmarks (sizes follow GPT-2/GPT-NeoX families).
PRESETS: Dict[str, GPTConfig] = {
    "gpt2-125m": GPTConfig(n_layer=12, n_head=12, d_model=768),
    "gpt2-350m": GPTConfig(n_layer=24, n_head=16, d_model=1024),
    "gpt2-760m": GPTConfig(n_layer=24, n_head=16, d_model=1536),
    "gpt2-1.3b": GPTConfig(n_layer=24, n_head=32, d_model=2048),
    "gpt-neox-1.3b": GPTConfig(n_layer=24, n_head=16, d_model=2048, rotary=True, rotary_pct=0.25),
    "gpt-neox-6.7b": GPTConfig(n_layer=32, n_head=32, d_model=4096, rotary=True, rotary_pct=0.25),
    "gpt-neox-20b": GPTConfig(
        vocab_size=50432, n_layer=44, n_head=64, d_model=6144, max_seq_len=2048,
        rotary=True, rotary_pct=0.25),
    # BLOOM-7B1 (BASELINE.json config #3): ALiBi attention, embedding
    # layernorm, tied head — bigscience/bloom-7b1 geometry
    "bloom-7b1": GPTConfig(
        vocab_size=250880, n_layer=30, n_head=32, d_model=4096,
        max_seq_len=2048, alibi=True, embed_layernorm=True,
        tie_embeddings=True),
    # OPT-13B (BASELINE.json config #5 inference model): ReLU MLPs, learned
    # positions at offset 2 — facebook/opt-13b geometry
    "opt-13b": GPTConfig(
        vocab_size=50272, n_layer=40, n_head=40, d_model=5120,
        max_seq_len=2048, rotary=False, pos_offset=2, activation="relu",
        tie_embeddings=True),
    "tiny": GPTConfig(vocab_size=256, n_layer=2, n_head=4, d_model=64, max_seq_len=128),
}


# --------------------------------------------------------------------------- init
_PIECE = 1 << 25    # elements drawn at once: about an expert's matrices


def _normal_in_pieces(key, shape, std, dtype=jnp.float32):
    """``N(0, std)`` of ``shape`` rounded to ``dtype``, a large leaf drawn a
    piece at a time (slices of its leading axes, then of a matrix's rows):
    the float32 draw of a multi-gigabyte tree never exists beside its
    rounded leaves."""
    size = int(np.prod(shape))
    if size <= _PIECE:
        return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)
    n, pieces = 0, 1
    while n < len(shape) - 2 and size // pieces > _PIECE:
        pieces *= shape[n]
        n += 1
    rest = tuple(shape[n:])
    cut = 1
    while int(np.prod(rest)) // cut > _PIECE and rest[0] % (cut * 2) == 0:
        cut *= 2
    rest = (rest[0] // cut,) + rest[1:]
    drawn = jax.lax.map(
        lambda i: (jax.random.normal(jax.random.fold_in(key, i), rest,
                                     jnp.float32) * std).astype(dtype),
        jnp.arange(pieces * cut))
    return drawn.reshape(shape)


def stack_names(cfg: GPTConfig) -> Tuple[Tuple[str, int], ...]:
    """The model's stacks of like layers in the order the forward first
    reaches them, (name in the parameter tree, layers): the names of
    :func:`layer_runs`, each with all its runs' layers."""
    stacks: Dict[str, int] = {}
    for run in layer_runs(cfg):
        stacks[run.name] = stacks.get(run.name, 0) + run.count
    return tuple(stacks.items())


def _init_kinds(cfg: GPTConfig, rng, normal, std, res_std) -> Dict[str, Any]:
    """The tree of a model with latent attention, key-value heads or routed
    layers: a stack a kind of layer (:func:`stack_names`), every leaf stacked
    over its stack's layers. No bias, RMSNorm gains only."""
    d, v, H = cfg.d_model, cfg.vocab_size, cfg.n_head
    of_stack = {run.name: run for run in layer_runs(cfg)}

    def attention(key, l, kind):
        k = jax.random.split(key, 6)
        if cfg.attn_kind == "gqa":
            heads = (kind or cfg).n_head
            G, Dh = cfg.n_kv_head, cfg.head_dim
            out = {"q_w": normal(k[0], (l, d, heads * Dh), std),
                   "kv_w": normal(k[1], (l, d, 2 * G * Dh), std),
                   "attn_out_w": normal(k[2], (l, heads * Dh, d), res_std)}
            if cfg.attn_gate:
                out["attn_gate_w"] = normal(k[3], (l, d, heads), std)
            return out
        if cfg.attn_kind != "mla":
            return {"qkv_w": normal(k[0], (l, d, 3 * d), std),
                    "attn_out_w": normal(k[1], (l, d, d), res_std)}
        kc = kind_view(cfg, kind)   # a kind's own heads, ranks and widths
        heads, qr, r = kc.n_head, kc.q_lora_rank, kc.kv_lora_rank
        nope, rope, vd = kc.qk_nope_dim, kc.qk_rope_dim, kc.v_head_dim
        out = {"q_a_w": normal(k[0], (l, d, qr), std),
               "q_a_norm_scale": jnp.ones((l, qr)),
               "q_b_w": normal(k[1], (l, qr, heads * (nope + rope)), std),
               "kv_a_w": normal(k[2], (l, d, r + rope), std),
               "kv_a_norm_scale": jnp.ones((l, r)),
               "kv_b_w": normal(k[3], (l, r, heads * (nope + vd)), std),
               "attn_out_w": normal(k[4], (l, heads * vd, d), res_std)}
        if cfg.attn_gate:
            out["attn_gate_w"] = normal(k[5], (l, d, heads), std)
        if kc.index_topk:   # the indexer: queries from the query latent,
            # one key for all its heads (a LayerNorm on it), a weight a head
            ki = jax.random.split(jax.random.fold_in(key, 7), 3)
            hi, di = kc.index_heads, kc.index_dim
            out.update({
                "index_q_w": normal(ki[0], (l, qr, hi * di), std),
                "index_k_w": normal(ki[1], (l, d, di), std),
                "index_k_norm_scale": jnp.ones((l, di)),
                "index_k_norm_bias": jnp.zeros((l, di)),
                "index_w_w": normal(ki[2], (l, d, hi), std)})
        return out

    def gated(key, l, name, lead, f, width=None, rows=None):
        """An MLP's matrices: gate, up and down, or up and down alone where
        the block's MLPs have no gate (``mlp_gated``). ``width`` > ``f``,
        ``rows`` > ``d``: the matrices are that wide and that tall, zero past
        ``f`` and past ``d`` (``moe_width``, ``moe_rows``)."""
        k = jax.random.split(key, 3)
        w, r = width or f, rows or d

        def cut(a, real):
            """``a`` [..., x, y], zero past ``real`` = (rows, columns)."""
            for axis, n in zip((-2, -1), real):
                if a.shape[axis] != n:
                    keep = (jnp.arange(a.shape[axis]) < n).reshape(
                        (-1,) + (1,) * (-axis - 1))
                    a = jnp.where(keep, a, jnp.zeros((), a.dtype))
            return a

        out = {f"{name}_up_w": cut(normal(k[1], (l,) + lead + (r, w), std),
                                   (d, f)),
               f"{name}_down_w": cut(normal(k[2], (l,) + lead + (w, r),
                                            res_std), (f, d))}
        if cfg.mlp_gated:
            out[f"{name}_gate_w"] = cut(
                normal(k[0], (l,) + lead + (r, w), std), (d, f))
        return out

    def routed(k, l):
        out = {"router_w": normal(k[2], (l, d, cfg.moe_experts), std),
               **gated(k[3], l, "experts", (cfg.held_experts[1],),
                       cfg.moe_d_ff, cfg.moe_width, cfg.moe_rows)}
        if cfg.moe_score_bias:
            # small and not zero, so that choice and gate differ
            out["router_bias"] = 0.02 * jax.random.normal(
                jax.random.fold_in(k[2], 1), (l, cfg.moe_experts),
                jnp.float32)
        if cfg.moe_shared_d_ff:
            out.update(gated(k[4], l, "shared", (), cfg.moe_shared_d_ff))
        return out

    params: Dict[str, Any] = {
        "wte": normal(jax.random.fold_in(rng, 0), (v, d), std),
        "lnf_scale": jnp.ones((d,))}
    if not cfg.tie_embeddings:
        params["lm_head"] = normal(jax.random.fold_in(rng, 1), (v, d), std)
    for n, (name, l) in enumerate(stack_names(cfg)):
        k = jax.random.split(jax.random.fold_in(rng, 2 + n), 5)
        run = of_stack[name]    # a stack's runs are of one kind of layer
        # a norm before each sublayer the layer has: ln1 the mixer's
        stack = {norm: jnp.ones((l, d)) for norm, sub in (
            ("ln1_scale", run.mixer), ("ln2_scale", run.ffn)) if sub}
        if run.mixes:
            stack.update(ssm.init_mixer(cfg.ssm, k[0], l, d, normal, std,
                                        res_std))
        if run.attends:     # beside the mixer: a key of its own
            stack.update(attention(k[0] if not run.mixes else jax.random
                                   .fold_in(k[0], 1), l, run.kind))
        if run.ffn == "routed":
            stack.update(routed(k, l))
        elif run.ffn:
            if not cfg.mlp_gated:
                raise ValueError("a model with latent attention or routed "
                                 "layers has gated MLPs (mlp_gated=True)")
            stack.update(gated(k[1], l, "mlp", (), cfg.ffn_dim))
        params[name] = stack
    return params


def init_params(cfg: GPTConfig, rng: jax.Array,
                total_depth: Optional[int] = None,
                dtype=jnp.float32) -> Dict[str, Any]:
    """The parameter tree from a key. ``dtype``: the type the matrices are
    rounded to as they are drawn (gains stay float32); a tree of several
    gigabytes asks for the served type here, because its float32 form fits
    no chip (:func:`_normal_in_pieces`)."""
    d, f, v, l = cfg.d_model, cfg.ffn_dim, cfg.vocab_size, cfg.n_layer
    k = jax.random.split(rng, 8)
    std = 0.02
    # residual-out projections scaled by 1/sqrt(2L) (GPT-2 init); total_depth
    # overrides L when this stack is a slice of a deeper model (MoE interleave)
    res_std = std / np.sqrt(2.0 * (total_depth or l))

    def normal(key, shape, s):
        return (jax.random.normal(key, shape, jnp.float32) * s).astype(dtype)

    if cfg.attn_kind != "mha" or cfg.moe_experts:
        return _init_kinds(cfg, rng, functools.partial(
            _normal_in_pieces, dtype=dtype), std, res_std)
    blocks = {
        "ln1_scale": jnp.ones((l, d)), "ln1_bias": jnp.zeros((l, d)),
        "qkv_w": normal(k[1], (l, d, 3 * d), std), "qkv_b": jnp.zeros((l, 3 * d)),
        "attn_out_w": normal(k[2], (l, d, d), res_std), "attn_out_b": jnp.zeros((l, d)),
        "ln2_scale": jnp.ones((l, d)), "ln2_bias": jnp.zeros((l, d)),
        "mlp_up_w": normal(k[3], (l, d, f), std), "mlp_up_b": jnp.zeros((l, f)),
        "mlp_down_w": normal(k[4], (l, f, d), res_std), "mlp_down_b": jnp.zeros((l, d)),
    }
    if cfg.mlp_gated:
        blocks["mlp_gate_w"] = normal(k[7], (l, d, f), std)
        blocks["mlp_gate_b"] = jnp.zeros((l, f))
    if cfg.post_norm:
        for name in ("post_attn", "post_mlp"):
            blocks[f"{name}_scale"] = jnp.ones((l, d))
            blocks[f"{name}_bias"] = jnp.zeros((l, d))
    params: Dict[str, Any] = {
        "wte": normal(k[0], (v, d), std),
        "blocks": blocks,
        "lnf_scale": jnp.ones((d,)),
        "lnf_bias": jnp.zeros((d,)),
    }
    if not cfg.rotary and not cfg.alibi:
        params["wpe"] = normal(k[5], (cfg.max_seq_len + cfg.pos_offset, d), std)
    if cfg.embed_layernorm:
        params["emb_ln_scale"] = jnp.ones((d,))
        params["emb_ln_bias"] = jnp.zeros((d,))
    if not cfg.tie_embeddings:
        params["lm_head"] = normal(k[6], (v, d), std)
        if cfg.lm_head_bias:
            params["lm_head_b"] = jnp.zeros((v,))
    if cfg.early_exit_threshold is not None:
        params["exit_gate_w"] = normal(jax.random.fold_in(rng, 0xE817),
                                       (d, 1), std)
        params["exit_gate_b"] = jnp.zeros((1,))
    return _leaves_of(cfg, params)


def _leaves_of(cfg: GPTConfig, tree: Dict[str, Any]) -> Dict[str, Any]:
    """``tree`` (parameters, or their specs) without the leaves ``cfg``'s
    block does not have: a norm's ``*_bias`` under RMSNorm, a linear's
    ``*_b`` where the linears have none."""
    def keep(name: str) -> bool:
        if name.endswith("_bias"):
            return cfg.norm == "layernorm"
        if name.endswith("_b") and name not in ("lm_head_b", "exit_gate_b"):
            return cfg.linear_bias
        return True

    return {name: (_leaves_of(cfg, leaf) if name == "blocks" else leaf)
            for name, leaf in tree.items() if keep(name)}


def partition_specs(cfg: GPTConfig, param_shapes) -> Dict[str, Any]:
    """Megatron-style TP specs. Stacked layer leaves carry a leading L axis.
    A model with latent attention or routed layers is replicated: nothing
    shards it yet (``KIND_FIELDS`` are refused where a mesh axis would)."""
    if cfg.attn_kind != "mha" or cfg.moe_experts:
        shapes = jax.eval_shape(functools.partial(init_params, cfg),
                                jax.random.PRNGKey(0))
        return jax.tree_util.tree_map(lambda a: P(*(None,) * a.ndim), shapes)
    blocks = {
        "ln1_scale": P(None, None), "ln1_bias": P(None, None),
        "qkv_w": P(None, None, "tp"), "qkv_b": P(None, "tp"),
        "attn_out_w": P(None, "tp", None), "attn_out_b": P(None, None),
        "ln2_scale": P(None, None), "ln2_bias": P(None, None),
        "mlp_up_w": P(None, None, "tp"), "mlp_up_b": P(None, "tp"),
        "mlp_down_w": P(None, "tp", None), "mlp_down_b": P(None, None),
    }
    if cfg.mlp_gated:
        blocks["mlp_gate_w"] = P(None, None, "tp")
        blocks["mlp_gate_b"] = P(None, "tp")
    if cfg.post_norm:
        for name in ("post_attn", "post_mlp"):
            blocks[f"{name}_scale"] = P(None, None)
            blocks[f"{name}_bias"] = P(None, None)
    specs = {
        "wte": P("tp", None),  # vocab-parallel embedding
        "blocks": blocks,
        "lnf_scale": P(None),
        "lnf_bias": P(None),
    }
    if not cfg.rotary and not cfg.alibi:
        specs["wpe"] = P(None, None)
    if cfg.embed_layernorm:
        specs["emb_ln_scale"] = P(None)
        specs["emb_ln_bias"] = P(None)
    if not cfg.tie_embeddings:
        specs["lm_head"] = P("tp", None)
        if cfg.lm_head_bias:
            specs["lm_head_b"] = P("tp")
    if cfg.early_exit_threshold is not None:
        specs["exit_gate_w"] = P(None, None)
        specs["exit_gate_b"] = P(None)
    return _leaves_of(cfg, specs)


# --------------------------------------------------------------------------- layers
def layer_norm(x: jnp.ndarray, scale, bias, eps: float) -> jnp.ndarray:
    # fp32 statistics regardless of compute dtype (reference normalize_kernels.cu
    # accumulates in fp32 for the same reason).
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    y = (x32 - mu) * jax.lax.rsqrt(var + eps)
    return (y * scale + bias).astype(x.dtype)


def rms_norm(x: jnp.ndarray, scale, eps: float) -> jnp.ndarray:
    """``x * rsqrt(mean(x^2) + eps) * scale``, float32 inside like
    :func:`layer_norm`, rounded once to ``x``'s type."""
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (y * scale).astype(x.dtype)


def _norm(cfg: "GPTConfig", x: jnp.ndarray, w: Dict[str, Any], name: str
          ) -> jnp.ndarray:
    """The config's norm with the leaves ``<name>_scale`` (and ``_bias``)."""
    if cfg.norm == "rmsnorm":
        return rms_norm(x, w[f"{name}_scale"], cfg.layer_norm_eps)
    return layer_norm(x, w[f"{name}_scale"], w[f"{name}_bias"],
                      cfg.layer_norm_eps)


def _linear(cfg: "GPTConfig", h: jnp.ndarray, w: Dict[str, Any], name: str
            ) -> jnp.ndarray:
    """``h @ <name>_w`` (dense or quantized, :func:`_wm`) and its bias where
    the config's linears have one; float32 where the config keeps a
    linear's output so (``linear_out_float32``)."""
    y = _wm(h, w[f"{name}_w"], _out_type(cfg))
    return y + w[f"{name}_b"] if cfg.linear_bias else y


def _out_type(cfg: "GPTConfig"):
    return jnp.float32 if cfg.linear_out_float32 else None


def _rope(x: jnp.ndarray, positions: jnp.ndarray, rotary_dims: int,
          interleaved: bool = False, theta: float = 10000.0,
          float32: bool = False,
          scaling: Optional[YarnScaling] = None) -> jnp.ndarray:
    """Rotary embedding on the first ``rotary_dims`` of the head dim. x: [B,T,H,Dh].

    ``interleaved=False``: NeoX rotate_half (pair (i, i+half)).
    ``interleaved=True``: GPT-J rotate_every_two (pair (2i, 2i+1)).
    ``float32``: the products and sums in float32, rounded once to ``x``'s
    type; else cosines and sines are rounded to that type first.
    ``scaling``: YaRN's frequencies and its factor on the cosines and sines
    in place of ``theta``'s own."""
    if rotary_dims == 0:
        return x
    x_rot, x_pass = x[..., :rotary_dims], x[..., rotary_dims:]
    if float32 and x.dtype != jnp.float32:
        rotated = _rope(x_rot.astype(jnp.float32), positions, rotary_dims,
                        interleaved, theta, scaling=scaling)
        return jnp.concatenate([rotated.astype(x.dtype), x_pass], axis=-1)
    half = rotary_dims // 2
    if scaling is None:
        freqs = 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32)
                                 / half))
        factor = None
    else:
        freqs = jnp.asarray(scaling.inv_freq(half, theta))
        factor = scaling.cos_sin_factor
    angles = positions[:, :, None].astype(jnp.float32) * freqs[None, None, :]  # [B,T,half]
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    if factor is not None and factor != 1.0:
        cos, sin = cos * factor, sin * factor
    cos = cos[:, :, None, :].astype(x.dtype)
    sin = sin[:, :, None, :].astype(x.dtype)
    if interleaved:
        x1, x2 = x_rot[..., 0::2], x_rot[..., 1::2]
        r1 = x1 * cos - x2 * sin
        r2 = x2 * cos + x1 * sin
        rotated = jnp.stack([r1, r2], axis=-1).reshape(x_rot.shape)
    else:
        x1, x2 = x_rot[..., :half], x_rot[..., half:]
        rotated = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return jnp.concatenate([rotated, x_pass], axis=-1)


def alibi_slopes(n_heads: int) -> np.ndarray:
    """Bloom's per-head ALiBi slopes (handles non-power-of-two head counts).
    Parity: the reference's alibi softmax path (``softmax.cu`` alibi mode,
    ``model_implementations/transformers/ds_bloom.py``)."""
    def pow2_slopes(n):
        start = 2.0 ** (-(2.0 ** -(np.log2(n) - 3)))
        return start * (start ** np.arange(n))

    n = 2 ** int(np.floor(np.log2(n_heads)))
    slopes = pow2_slopes(n)
    if n < n_heads:
        extra = pow2_slopes(2 * n)[0::2][: n_heads - n]
        slopes = np.concatenate([slopes, extra])
    return slopes.astype(np.float32)


def _alibi_bias(cfg: GPTConfig, q_positions: jnp.ndarray, kv_len: int) -> jnp.ndarray:
    """[B, H, T, S] additive bias: slopes[h] * (s - t_abs)."""
    slopes = jnp.asarray(alibi_slopes(cfg.n_head))
    s_idx = jnp.arange(kv_len)[None, None, None, :]
    t_abs = q_positions[:, None, :, None]
    return slopes[None, :, None, None] * (s_idx - t_abs).astype(jnp.float32)


def _act(cfg: GPTConfig, h: jnp.ndarray) -> jnp.ndarray:
    if cfg.activation == "relu":
        return jax.nn.relu(h)
    if cfg.activation == "relu2":   # Nemotron's experts: relu(h) squared
        return jnp.square(jax.nn.relu(h))
    if cfg.activation == "gelu_exact":
        return jax.nn.gelu(h, approximate=False)
    if cfg.activation == "quick_gelu":  # CLIP: x * sigmoid(1.702 x)
        return h * jax.nn.sigmoid(1.702 * h)
    if cfg.activation == "silu":
        return jax.nn.silu(h)
    return jax.nn.gelu(h, approximate=True)


def _is_local_layer(cfg: GPTConfig, layer_idx) -> Optional[jnp.ndarray]:
    """Traced bool: does this layer use windowed (local) attention?
    GPT-Neo alternates [global, local] — the last layer of each period is
    local. None when the config never uses local attention."""
    if cfg.local_attention_period <= 1 or layer_idx is None:
        return None
    p = cfg.local_attention_period
    return (jnp.asarray(layer_idx) % p) == (p - 1)


def _local_window_bias(cfg: GPTConfig, q_positions: jnp.ndarray, kv_len: int,
                       is_local) -> jnp.ndarray:
    """[B, 1, T, S] additive bias masking keys older than window_size
    (inert for global layers: is_local is traced, the program is uniform)."""
    s_idx = jnp.arange(kv_len)[None, None, None, :]
    t_abs = q_positions[:, None, :, None]
    too_old = s_idx <= t_abs - cfg.window_size
    return jnp.where(jnp.logical_and(is_local, too_old),
                     jnp.float32(-1e30), jnp.float32(0.0))


def _rotate_qk(cfg: GPTConfig, q, k_, positions):
    if not cfg.rotary:
        return q, k_
    rd = int(cfg.rotary_pct * cfg.head_dim)
    rd -= rd % 2
    return tuple(_rope(t, positions, rd, cfg.rotary_interleaved,
                       cfg.rope_theta, cfg.rotary_float32, cfg.rope_scaling)
                 for t in (q, k_))


def _softmax_scale(cfg: GPTConfig) -> float:
    """``attention_scale`` where the config sets one, else one over the root
    of the width the scores are taken over, times YaRN's factor where the
    rotary is scaled."""
    if cfg.attention_scale is not None:
        return cfg.attention_scale
    width = (cfg.qk_nope_dim + cfg.qk_rope_dim if cfg.attn_kind == "mla"
             else cfg.head_dim)
    yarn = cfg.rope_scaling.softmax_factor if cfg.rope_scaling else 1.0
    return yarn / np.sqrt(width)


@jax.named_scope("attn")
def _attn_delta(cfg: GPTConfig, x: jnp.ndarray, w: Dict[str, jnp.ndarray],
                positions: jnp.ndarray, attend):
    """The attention sublayer's output before the residual add: norm, QKV,
    rotation, ``attend(q, k, v) -> (attention [B, T, H, Dh], carried)``, the
    out-projection and, where the block has one, the norm on it. ``attend``
    is all that differs between the forwards (``_attend_*``): whole
    sequences, a dense cache, the page pool; ``carried`` is what it hands
    back (the cache it wrote)."""
    if cfg.attn_kind == "mla":
        if not (cfg.attn_window or cfg.index_topk):
            return _mla_delta(cfg, x, w, positions, attend)
        with jax.named_scope("attn_window" if cfg.attn_window
                             else "attn_full"):  # a model of two kinds
            return _mla_delta(cfg, x, w, positions, attend)
    if cfg.attn_kind == "gqa":
        with jax.named_scope("attn_window" if cfg.attn_window
                             else "attn_full"):
            return _gqa_delta(cfg, x, w, positions, attend)
    B, T, D = x.shape
    H, Dh = cfg.n_head, cfg.head_dim
    qkv = _linear(cfg, _norm(cfg, x, w, "ln1"), w, "qkv")
    q, k_, v = (t.reshape(B, T, H, Dh) for t in jnp.split(qkv, 3, axis=-1))
    q, k_ = _rotate_qk(cfg, q, k_, positions)
    if cfg.linear_out_float32:      # rounded once, after the rotation
        q, k_, v = (t.astype(x.dtype) for t in (q, k_, v))
    attn, carried = attend(q, k_, v)
    out = checkpoint_name(
        _linear(cfg, attn.reshape(B, T, D).astype(x.dtype), w, "attn_out"),
        "attn_out")
    if cfg.post_norm:
        out = _norm(cfg, out, w, "post_attn")
    return out, carried


# ------------------------------------------------- fewer key-value heads
def _gqa_delta(cfg: GPTConfig, x: jnp.ndarray, w: Dict[str, jnp.ndarray],
               positions: jnp.ndarray, attend):
    """:func:`_attn_delta` with fewer key-value heads than query heads, for
    one kind of layer (``cfg`` is its :func:`kind_view`): ``q = h W_q``
    (``n_head`` heads), ``[k | v] = h W_kv`` (``n_kv_head`` heads each), q
    and k rotated by the kind's rotary set, ``attend(q [B, T, H, Dh], k, v
    [B, T, G, Dh]) -> (attention [B, T, H, Dh], carried)``; with
    ``attn_gate`` a head's attention times ``sigmoid(h W_gate)`` of it
    (float32), then the out-projection."""
    B, T, _ = x.shape
    H, G, Dh = cfg.n_head, cfg.n_kv_head, cfg.head_dim
    h = _norm(cfg, x, w, "ln1")
    wide = _out_type(cfg)
    a = _times(cfg, h, "attn_in")
    q = _wm(a, w["q_w"], wide).reshape(B, T, H, Dh)
    kv = _wm(a, w["kv_w"], wide).reshape(B, T, 2, G, Dh)
    q, k_ = _rotate_qk(cfg, q, _times(cfg, kv[:, :, 0], "key"), positions)
    v = kv[:, :, 1]
    if cfg.linear_out_float32:      # rounded once, after the rotation
        q, k_, v = (t.astype(x.dtype) for t in (q, k_, v))
    attn, carried = attend(q, k_, v)
    if cfg.attn_gate:
        gate = jax.nn.sigmoid(_wm(h, w["attn_gate_w"], jnp.float32)
                              .astype(jnp.float32))
        attn = attn.astype(jnp.float32) * gate[..., None]
    out = checkpoint_name(_times(cfg, _wm(
        attn.reshape(B, T, H * Dh).astype(x.dtype), w["attn_out_w"], wide),
        "attn_out"), "attn_out")
    return out, carried


# keys a chunk's scores are taken over at once; a longer cache goes a block
# of this many at a time under a running softmax (48 heads x 512 queries x
# 9216 keys of float32 scores are 906 MB, beside a pool that fills the chip)
_KEY_BLOCK = 512


def _gqa_attention(cfg: GPTConfig, q, k, v, positions, key_start=0,
                   live=None):
    """Causal softmax attention of ``q`` [B, T, H, Dh] at absolute
    ``positions`` [B, T] over keys and values [B, G, S, Dh] whose row ``j``
    holds position ``key_start + j``, query head ``i`` reading key-value head
    ``i // (H / G)``; with ``attn_window`` a query sees its last
    ``attn_window`` positions only, its own included. Float32 scores,
    probabilities rounded to the values' type, as :func:`_masked_attention`;
    [B, T, H, Dh] in the values' type. ``live`` (it may be traced): only
    rows below it can matter, and where the cache is long the keys go a
    block at a time up to there."""
    B, T, H, Dh = q.shape
    G, S = k.shape[1], k.shape[2]
    qg = q.reshape(B, T, G, H // G, Dh).astype(jnp.float32)
    scale = _softmax_scale(cfg)
    # float32 operands go through the MXU in one bf16 pass unless told
    exact = jax.lax.Precision.HIGHEST if cfg.attn_float32 else None
    t_idx = positions[:, None, None, :, None]               # [B, 1, 1, T, 1]

    def scores(k_rows, first):
        s = jnp.einsum("btgrd,bgsd->bgrts", qg,
                       k_rows.astype(jnp.float32), precision=exact) * scale
        s_idx = first + jnp.arange(k_rows.shape[2])
        seen = s_idx <= t_idx
        if cfg.attn_window:
            seen = seen & (s_idx > t_idx - cfg.attn_window)
        return jnp.where(seen, s, jnp.float32(-1e30))

    block = math.gcd(S, _KEY_BLOCK)
    if live is None or S <= 2 * block:
        probs = jax.nn.softmax(scores(k, key_start), axis=-1)
        out = jnp.einsum("bgrts,bgsd->btgrd", probs.astype(v.dtype), v,
                         precision=exact)
        return out.reshape(B, T, H, Dh)

    def body(j, carry):
        m, l, acc = carry
        k_j, v_j = (jax.lax.dynamic_slice_in_dim(a, j * block, block, 2)
                    for a in (k, v))
        s = scores(k_j, key_start + j * block)
        m_new = jnp.maximum(m, s.max(axis=-1))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[..., None])
        acc = acc * alpha[..., None] + jnp.einsum(
            "bgrts,bgsd->bgrtd", p.astype(v.dtype), v_j,
            preferred_element_type=jnp.float32, precision=exact)
        return m_new, alpha * l + p.sum(axis=-1), acc

    lead = (B, G, H // G, T)
    _, l, acc = jax.lax.fori_loop(
        0, -(-jnp.asarray(live, jnp.int32) // block), body,
        (jnp.full(lead, -1e30, jnp.float32), jnp.zeros(lead, jnp.float32),
         jnp.zeros(lead + (Dh,), jnp.float32)))
    out = (acc / l[..., None]).astype(v.dtype)
    return out.transpose(0, 3, 1, 2, 4).reshape(B, T, H, Dh)


# ------------------------------------------------------- latent attention
def _mla_delta(cfg: GPTConfig, x: jnp.ndarray, w: Dict[str, jnp.ndarray],
               positions: jnp.ndarray, attend):
    """:func:`_attn_delta` with latent attention (MLA, DeepSeek-V2): a
    low-rank query path with a norm inside, ``q = RMSNorm(h W_qa) W_qb``, a
    head of it ``[q_nope | q_rope]``; a low-rank key-value path
    ``[c_kv | k_rope] = h W_kva`` whose latent ``c_kv`` is normed and whose
    ``k_rope``, one for all heads, is rotated like ``q_rope``. What a token
    caches is ``[c_kv | k_rope]`` (and zeros up to ``latent_width``), no head
    axis; keys and values a head are ``c_kv W_kvb``. ``attend(q [B, T, H,
    nope + rope], latent [B, T, 1, latent_width], W_kvb [rank, H * (nope +
    v)]) -> (attention [B, T, H, v], carried)``: with latent attention the third argument is the matrix a
    form expands the cached rows by (:func:`_mla_attention`) or absorbs into
    the query and the output (:func:`_mla_absorb`), the same function."""
    B, T, _ = x.shape
    H, r = cfg.n_head, cfg.kv_lora_rank
    nope, rope = cfg.qk_nope_dim, cfg.qk_rope_dim
    eps = cfg.layer_norm_eps
    h = _norm(cfg, x, w, "ln1")

    def rotate(t):
        return _rope(t, positions, rope, False, cfg.rope_theta,
                     cfg.rotary_float32, cfg.rope_scaling)

    wide = _out_type(cfg)
    # the two rescales of the normed latents, plain factors (no weight
    # holds them)
    s_q = (math.sqrt(cfg.d_model / cfg.q_lora_rank)
           if cfg.mla_lora_rescale else 1.0)
    s_kv = (math.sqrt(cfg.d_model / r) if cfg.mla_lora_rescale else 1.0)
    with jax.named_scope("mla_q"):
        c_q = rms_norm(_wm(h, w["q_a_w"], wide), w["q_a_norm_scale"], eps)
        c_q = (c_q if s_q == 1.0 else c_q * s_q).astype(x.dtype)
        q = _wm(c_q, w["q_b_w"], wide).reshape(B, T, H, nope + rope)
        q = jnp.concatenate([q[..., :nope], rotate(q[..., nope:])],
                            axis=-1).astype(x.dtype)
    with jax.named_scope("mla_kv"):
        kv = _wm(h, w["kv_a_w"], wide)
        c_kv = rms_norm(kv[..., :r], w["kv_a_norm_scale"], eps)
        c_kv = c_kv if s_kv == 1.0 else c_kv * s_kv
        pad = jnp.zeros((B, T, 1, cfg.latent_width - r - rope), c_kv.dtype)
        latent = jnp.concatenate(
            [c_kv[:, :, None], rotate(kv[:, :, None, r:]), pad],
            axis=-1).astype(x.dtype)
    if cfg.index_topk:
        with jax.named_scope("index"):
            index = _index_parts(cfg, h, c_q, w, rotate)
        attn, carried = attend(q, latent, w["kv_b_w"], index)
    else:
        attn, carried = attend(q, latent, w["kv_b_w"])
    if cfg.attn_gate:
        gate = jax.nn.sigmoid(_wm(h, w["attn_gate_w"], jnp.float32)
                              .astype(jnp.float32))
        attn = attn.astype(jnp.float32) * gate[..., None]
    out = checkpoint_name(
        _wm(attn.reshape(B, T, H * cfg.v_head_dim).astype(x.dtype),
            w["attn_out_w"], wide), "attn_out")
    return out, carried


# ---------------------------------------------- a learned selection of rows
def _index_parts(cfg: GPTConfig, h, c_q, w, rotate):
    """The indexer's three values of the normed input ``h`` [B, T, d] and the
    query latent ``c_q``: queries ``qI = c_q W_qI`` [B, T, Hi, Di] and ONE
    key for all heads ``kI = LayerNorm(h W_kI)`` [B, T, Di], the first
    ``qk_rope_dim`` dimensions of both rotated as the attention's are, in
    ``h``'s type (``kI`` is what a token caches), and the heads' weights
    ``(h W_w) / sqrt(Hi Di)`` [B, T, Hi] in float32."""
    B, T, _ = h.shape
    Hi, Di, rope = cfg.index_heads, cfg.index_dim, cfg.qk_rope_dim
    wide = jnp.float32 if cfg.index_float32 else _out_type(cfg)
    q = _wm(c_q, w["index_q_w"], wide).reshape(B, T, Hi, Di)
    k = layer_norm(_wm(h, w["index_k_w"], wide), w["index_k_norm_scale"],
                   w["index_k_norm_bias"], cfg.layer_norm_eps)
    q = jnp.concatenate([rotate(q[..., :rope]), q[..., rope:]], axis=-1)
    k = jnp.concatenate([rotate(k[:, :, None, :rope])[:, :, 0],
                         k[..., rope:]], axis=-1)
    weights = _wm(h, w["index_w_w"], jnp.float32).astype(jnp.float32)
    kept = jnp.float32 if cfg.index_float32 else h.dtype
    return q.astype(kept), k.astype(kept), weights * (Hi * Di) ** -0.5


def _index_scores(q, weights, keys):
    """``I(t, s) = sum_j w_tj relu(qI_tj . kI_s)``, float32 [B, T, S]: ``q``
    [B, T, Hi, Di] against ``keys`` [B, S, Di] in the keys' type (what the
    cache holds), float32 sums."""
    exact = (jax.lax.Precision.HIGHEST if keys.dtype == jnp.float32
             else None)
    s = jnp.einsum("bthd,bsd->bths", q.astype(keys.dtype), keys,
                   preferred_element_type=jnp.float32, precision=exact)
    return jnp.einsum("bths,bth->bts", jax.nn.relu(s), weights,
                      precision=jax.lax.Precision.HIGHEST)


def _kth_largest(scores, k: int):
    """The ``k``-th largest of each row of float32 ``scores`` [..., S]
    (``-inf`` where a row has fewer finite ones): the floats' bits read as
    integers that order as the floats do, and the answer built a bit at a
    time from the top, each bit one count over the row. 32 passes whatever
    ``k`` is; ``lax.top_k`` of 1,024 rows of 17,408 for ``k`` 2,048 took
    20.3 ms on the v5e and this 0.7 (my chip run, PERF.md PR 51)."""
    bits = jax.lax.bitcast_convert_type(scores, jnp.int32)
    # negative floats order backwards: flip their magnitude; then to
    # unsigned order by flipping the sign bit
    key = jnp.where(bits < 0, bits ^ 0x7FFFFFFF, bits).astype(
        jnp.uint32) ^ jnp.uint32(0x80000000)

    def body(i, found):
        trial = found | (jnp.uint32(1) << (31 - i).astype(jnp.uint32))
        enough = (key >= trial[..., None]).sum(-1) >= k
        return jnp.where(enough, trial, found)

    found = jax.lax.fori_loop(0, 32, body,
                              jnp.zeros(scores.shape[:-1], jnp.uint32))
    back = (found ^ jnp.uint32(0x80000000)).astype(jnp.int32)
    back = jnp.where(back < 0, back ^ 0x7FFFFFFF, back)
    kth = jax.lax.bitcast_convert_type(back, jnp.float32)
    # fewer than k finite scores: every bit's count fell short, found is 0
    return jnp.where(found == 0, -jnp.inf, kth)


def _selected(scores, seen, k: int, kth=None):
    """Which of the ``seen`` positions [..., S] a query attends to: the ``k``
    of largest ``scores``, ties to the lower position; all of them where no
    more are seen. ``kth`` [...]: the ``k``-th largest seen score, where the
    caller has it already (``lax.top_k``'s last)."""
    if scores.shape[-1] <= k:
        return seen
    s = jnp.where(seen, scores, -jnp.inf)
    kth = (_kth_largest(s, k) if kth is None else kth)[..., None]
    above = s > kth
    level = (s == kth) & seen
    room = k - above.sum(-1, keepdims=True)
    return seen & (above | (level & (jnp.cumsum(level, axis=-1) <= room)))


def _seen_by(cfg: GPTConfig, positions, key_positions):
    """Which keys at ``key_positions`` [S] (or [B, S]) the queries at
    ``positions`` [B, T] may see, before any selection: those at or before
    the query, from position 0, inside the window where the kind has one.
    [B, T, S]."""
    s = jnp.asarray(key_positions)
    s = s[None, None, :] if s.ndim == 1 else s[:, None, :]
    t = positions[:, :, None]
    seen = (s <= t) & (s >= 0)
    if cfg.attn_window:
        seen = seen & (s > t - cfg.attn_window)
    return seen


def _kvb_heads(cfg: GPTConfig, kvb):
    """``W_kvb`` [rank, H * (nope + v)] as its key part [rank, H, nope] and
    its value part [rank, H, v]."""
    kvb = kvb.reshape(cfg.kv_lora_rank, cfg.n_head, -1)
    return kvb[..., :cfg.qk_nope_dim], kvb[..., cfg.qk_nope_dim:]


@jax.named_scope("mla_absorb")
def _mla_absorb(cfg: GPTConfig, q, kvb):
    """The query over the latent: ``[q_nope W_kvb,k^T | q_rope]``, [B, T, H,
    rank + rope]. Scores of it against cached rows ``[c_kv | k_rope]`` are
    ``q_nope . k_nope + q_rope . k_rope``."""
    return _mla_absorb_heads(q, _kvb_heads(cfg, kvb)[0], cfg.qk_nope_dim)


@jax.named_scope("mla_absorb")
def _mla_unabsorb(cfg: GPTConfig, o_lat, kvb):
    """Attention over the latent [B, T, H, rank] to a head's values
    [B, T, H, v]: ``(sum p c_kv) W_kvb,v = sum p v``."""
    _, w_v = _kvb_heads(cfg, kvb)
    if _meets_bf16(o_lat, w_v):
        return _two_pass(o_lat, lambda a: jnp.einsum(
            "bthr,rhv->bthv", a, w_v, preferred_element_type=jnp.float32),
            rows_axis=1)
    return jnp.einsum("bthr,rhv->bthv", o_lat.astype(kvb.dtype), w_v)


# a form's float32 scores [B, H, T, S] above this many bytes are taken a
# group of heads at a time
_MLA_SCORE_BYTES = 1 << 27


def _mla_attention(cfg: GPTConfig, q, rows, kvb, positions,
                   absorbed: bool = False, allowed=None):
    """Causal softmax attention of ``q`` [B, T, H, nope + rope] at absolute
    ``positions`` [B, T] over cached rows ``[c_kv | k_rope | 0]`` [B, S,
    latent_width] whose place is their position; [B, T, H, v] in the rows' type.
    Float32 scores, probabilities rounded to the values' type, as
    :func:`_masked_attention`. Un-absorbed, the rows are expanded to a
    head's keys and values by ``W_kvb`` ``kvb``; ``absorbed``, ``W_kvb`` goes
    into the query and the output and the rows are read as they lie (what
    the decode kernel does): a third of the products' operations at one
    token against the whole cache, 1.9 times them at a chunk of 512.
    A kind with a window sees its last ``attn_window`` positions;
    ``allowed`` [B, T, S] takes the mask's place whole (:func:`_selected`)."""
    B, T, H, _ = q.shape
    S, r, nope = rows.shape[1], cfg.kv_lora_rank, cfg.qk_nope_dim
    used = r + cfg.qk_rope_dim
    scale = _softmax_scale(cfg)
    if allowed is not None:
        mask = allowed[:, None]                             # [B, 1, T, S]
    elif cfg.attn_window:
        mask = _seen_by(cfg, positions, jnp.arange(S))[:, None]
    else:
        mask = (jnp.arange(S)[None, None, :]
                <= positions[:, :, None])[:, None]
    w_k, w_v = _kvb_heads(cfg, kvb)

    def softmax(s):
        return jax.nn.softmax(jnp.where(mask, s * scale, jnp.float32(-1e30)),
                              axis=-1)

    def heads(q, w_k, w_v):
        f32 = jnp.float32
        if absorbed:
            q_lat = _mla_absorb_heads(q, w_k, nope)
            p = softmax(jnp.einsum("bthc,bsc->bhts", q_lat.astype(f32),
                                   rows[..., :used].astype(f32)))
            o_lat = jnp.einsum("bhts,bsr->bthr", p.astype(rows.dtype),
                               rows[..., :r])
            return jnp.einsum("bthr,rhv->bthv", o_lat, w_v)
        k_nope = jnp.einsum("bsr,rhn->bshn", rows[..., :r], w_k)
        v = jnp.einsum("bsr,rhv->bshv", rows[..., :r], w_v)
        s = (jnp.einsum("bthn,bshn->bhts", q[..., :nope].astype(f32),
                        k_nope.astype(f32))
             + jnp.einsum("bthp,bsp->bhts", q[..., nope:].astype(f32),
                          rows[..., r:used].astype(f32)))
        return jnp.einsum("bhts,bshv->bthv", softmax(s).astype(v.dtype), v)

    group = H
    while group % 2 == 0 and 4 * B * group * T * S > _MLA_SCORE_BYTES:
        group //= 2
    if group == H:
        return heads(q, w_k, w_v).astype(rows.dtype)
    n = H // group
    out = jax.lax.map(
        lambda a: heads(*a),
        (jnp.moveaxis(q.reshape(B, T, n, group, -1), 2, 0),
         jnp.moveaxis(w_k.reshape(r, n, group, -1), 1, 0),
         jnp.moveaxis(w_v.reshape(r, n, group, -1), 1, 0)))
    return jnp.moveaxis(out, 0, 2).reshape(B, T, H, -1).astype(rows.dtype)


def _mla_absorb_heads(q, w_k, nope: int):
    def absorb(a):
        return jnp.einsum("bthn,rhn->bthr", a, w_k,
                          preferred_element_type=q.dtype)
    q_abs = (_two_pass(q[..., :nope], absorb, rows_axis=1)
             if _meets_bf16(q, w_k) else absorb(q[..., :nope]))
    return jnp.concatenate([q_abs, q[..., nope:]], axis=-1)


def _attend_sequence(cfg: GPTConfig, positions: jnp.ndarray, layer_idx=None):
    """``attend`` over whole sequences, no cache: the training forward."""
    if cfg.attn_kind == "mla":
        def attend_latent(q, latent, kvb, index=None):
            allowed = None
            if index is not None:
                with jax.named_scope("index"):
                    allowed = _selected(
                        _index_scores(index[0], index[2], index[1]),
                        _seen_by(cfg, positions, jnp.arange(q.shape[1])),
                        cfg.index_topk)
            return _mla_attention(cfg, q, latent[:, :, 0], kvb, positions,
                                  allowed=allowed), None
        return attend_latent
    if cfg.attn_kind == "gqa":
        return lambda q, k_, v: (_gqa_attention(
            cfg, q, k_.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3),
            positions), None)

    def attend(q, k_, v):
        T = q.shape[1]
        bias = _alibi_bias(cfg, positions, T) if cfg.alibi else None
        is_local = _is_local_layer(cfg, layer_idx)
        if is_local is not None:
            lb = _local_window_bias(cfg, positions, T, is_local)
            bias = lb if bias is None else bias + lb
        if cfg.sparse_attention is not None:
            if bias is not None:
                raise ValueError(
                    "sparse_attention cannot compose with alibi/local-window "
                    "biases (the blocksparse kernel has no bias input)")
            from ..ops.sparse_attention import sparse_attention as _sparse

            return _sparse(q, k_, v, cfg.sparse_attention, causal=True,
                           softmax_scale=cfg.attention_scale), None
        if cfg.seq_parallel_impl in ("ring", "ulysses") and _sp_active():
            if bias is not None:
                raise ValueError(
                    f"seq_parallel_impl='{cfg.seq_parallel_impl}' cannot "
                    f"compose with alibi/local-window biases")
            from ..parallel import ring_attention, ulysses_attention

            fn = (ring_attention if cfg.seq_parallel_impl == "ring"
                  else ulysses_attention)
            return fn(q, k_, v, _bound_mesh(), causal=True,
                      softmax_scale=cfg.attention_scale), None
        return multihead_attention(q, k_, v, causal=True, bias=bias,
                                   use_flash=cfg.use_flash,
                                   softmax_scale=cfg.attention_scale,
                                   block_q=cfg.flash_block_q,
                                   block_k=cfg.flash_block_k,
                                   stochastic_mode=cfg.stochastic_mode), None
    return attend


def _attention_delta(cfg: GPTConfig, x: jnp.ndarray, w: Dict[str, jnp.ndarray],
                     positions: jnp.ndarray, layer_idx=None) -> jnp.ndarray:
    """Attention output (pre-residual): attn_out(MHA(ln1(x)))."""
    return _attn_delta(cfg, x, w, positions,
                       _attend_sequence(cfg, positions, layer_idx))[0]


def _bound_mesh():
    """The mesh governing the CURRENT trace: the engine traces its programs
    inside ``mesh_context(engine.mesh)``, so the trace-bound mesh is the
    right one even when several engines with different topologies coexist
    (a process-global would go stale). Falls back to the default topology for
    direct (non-engine) calls."""
    from ..runtime.topology import bound_mesh, get_topology

    pm = bound_mesh()
    if pm is not None:
        return pm
    try:
        topo = get_topology()
    except Exception:
        return None
    return topo.mesh if topo is not None else None


def _sp_active() -> bool:
    """True when the trace-bound mesh has sp > 1 (the ring/Ulysses paths only
    make sense with the sequence dim actually sharded)."""
    mesh = _bound_mesh()
    return mesh is not None and dict(mesh.shape).get("sp", 1) > 1


def _meets_bf16(h: jnp.ndarray, w: jnp.ndarray) -> bool:
    """A float32 activation against a bf16 matrix: what :func:`_two_pass`
    is for (a ``stream_float32`` stream over served weights)."""
    return h.dtype == jnp.float32 and w.dtype == jnp.bfloat16


def _two_pass(h: jnp.ndarray, product, rows_axis: int = -2) -> jnp.ndarray:
    """``product`` of float32 rows ``h`` with a bf16 matrix at 16 bits of
    mantissa: ``h`` is split into its bf16 rounding and the bf16 rounding of
    what that left, both go through the one product (the matrix is read
    once, the rows are twice as many) and the two float32 results are
    added. One pass would round ``h`` to 8 bits inside the MXU."""
    hi, lo = split_bf16(h)
    both = product(jnp.concatenate([hi, lo], axis=rows_axis))
    a, b = jnp.split(both, 2, axis=rows_axis)
    return a + b


def split_bf16(h: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Float32 ``h`` as two bf16 arrays whose sum has 16 bits of its
    mantissa: its rounding and the rounding of what that left.
    ``reduce_precision`` and not a cast there and back, which the compiler
    may drop (``xla_allow_excess_precision``), leaving nothing for the
    second."""
    hi = jax.lax.reduce_precision(h, exponent_bits=8, mantissa_bits=7)
    return hi.astype(jnp.bfloat16), (h - hi).astype(jnp.bfloat16)


def _wm(h: jnp.ndarray, leaf, out=None) -> jnp.ndarray:
    """``h @ W`` where W is dense OR a quantized leaf: int8 ``{"q","s"}`` or
    packed int4 ``{"q4","s"}``. ``out``: the type a dense product is
    accumulated and returned in (None: the operands').

    Quantized leaves route through the Pallas quantized-weight matmuls
    (ops/pallas/int8_matmul.py): the narrow weights stay in HBM,
    dequantization happens per VMEM tile — no bf16 weight buffer exists at
    any scope, and decode moves half (int8) or a quarter (int4) of the
    weight bytes (the decode bottleneck)."""
    if not _is_qleaf(leaf):
        if _meets_bf16(h, leaf):
            return _two_pass(h, lambda a: jnp.matmul(
                a, leaf, preferred_element_type=jnp.float32))
        return h @ leaf if out is None else jnp.matmul(
            h, leaf, preferred_element_type=out)
    shape = h.shape
    if "q4" in leaf:
        from ..ops.pallas.int8_matmul import int4_matmul

        q4, s = leaf["q4"], leaf["s"]
        group = (2 * q4.size) // s.size
        out = int4_matmul(h.reshape(-1, shape[-1]), q4, s.reshape(-1),
                          group_size=group)
        return out.reshape(*shape[:-1], 2 * q4.shape[1])
    from ..ops.pallas.int8_matmul import int8_matmul

    q, s = leaf["q"], leaf["s"]
    group = q.size // s.size
    out = int8_matmul(h.reshape(-1, shape[-1]), q, s.reshape(-1),
                      group_size=group)
    return out.reshape(*shape[:-1], q.shape[1])


@jax.named_scope("mlp")
def _mlp_delta(cfg: GPTConfig, x: jnp.ndarray, w: Dict[str, jnp.ndarray]) -> jnp.ndarray:
    """MLP output (pre-residual): mlp(ln2(x)), gated where the block's is,
    and the norm on it where the block has one."""
    out = checkpoint_name(_mlp_on(cfg, _norm(cfg, x, w, "ln2"), w), "mlp_out")
    if cfg.post_norm:
        out = _norm(cfg, out, w, "post_mlp")
    return out


def _mlp_on(cfg: GPTConfig, h: jnp.ndarray, w: Dict[str, jnp.ndarray],
            name: str = "mlp") -> jnp.ndarray:
    """The MLP ``<name>_up``, ``<name>_gate``, ``<name>_down`` of the normed
    input ``h``: the dense layers' (``mlp``) and a routed layer's shared
    expert (``shared``)."""
    up = _linear(cfg, h, w, f"{name}_up")
    if cfg.mlp_gated:
        gate = _linear(cfg, h, w, f"{name}_gate")
        mid = (_act(cfg, _times(cfg, gate.astype(jnp.float32), "mlp_gate"))
               * up.astype(jnp.float32)).astype(h.dtype)
    else:
        mid = _act(cfg, up).astype(h.dtype)
    return _times(cfg, _linear(cfg, mid, w, f"{name}_down"), "mlp_down")


@jax.named_scope("mlp")
def _moe_delta(cfg: GPTConfig, x: jnp.ndarray, w: Dict[str, jnp.ndarray]):
    """A routed layer's feed-forward (pre-residual) and the experts it chose,
    int32 [B, T, k]: the router in float32 from the served activations, over
    all ``moe_experts``; ``w`` is one layer's weights, or, with an index
    ``experts_layer``, one layer's but for the experts' whole stacks; the part of the result the held experts give
    (``moe/dropless.py``; every token reaches its experts, none is dropped);
    the shared expert for every token. On the chip that holds all experts
    that is the whole layer; on one of several it is this chip's term of the
    sum the exchange would make, and nothing stands in for the others."""
    from ..moe import dropless

    B, T, D = x.shape
    h = _norm(cfg, x, w, "ln2")
    flat = h.reshape(B * T, D)
    with jax.named_scope("moe_router"):
        logits = jnp.dot(flat.astype(jnp.float32),
                         w["router_w"].astype(jnp.float32),
                         precision=jax.lax.Precision.HIGHEST)
        # the score and the bias are said only where they are not the
        # softmax's: tests and the benchmark's drift tools stand in for
        # ``route`` with its positional form
        how = ({} if cfg.moe_score == "softmax" and not cfg.moe_score_bias
               else dict(score=cfg.moe_score, bias=w.get("router_bias")))
        chosen, gates = dropless.route(
            logits, cfg.moe_k, cfg.moe_groups, cfg.moe_topk_groups,
            cfg.moe_scale, cfg.moe_norm_topk, **how)
    with jax.named_scope("moe_experts"):
        # the experts take their rows in the weights' type, one pass, also
        # from a float32 stream: two passes there cost a decode step 7 ms
        # of 40 and moved the compared logits by 3% (PERF.md, PR 34)
        split = (split_bf16 if cfg.moe_two_pass
                 and _meets_bf16(flat, w["experts_up_w"]) else None)
        out = dropless.held_experts_ffn(
            flat if split else flat.astype(w["experts_up_w"].dtype), chosen,
            gates,
            w.get("experts_gate_w"), w["experts_up_w"],
            w["experts_down_w"], cfg.held_experts,
            functools.partial(_act, cfg), layer=w.get("experts_layer"),
            split=split,
            out=jnp.float32 if flat.dtype == jnp.float32
            else _out_type(cfg))
    if cfg.moe_shared_d_ff:
        with jax.named_scope("moe_shared"):
            out = out + _mlp_on(cfg, flat, w, "shared")
    out = checkpoint_name(out.reshape(B, T, D), "mlp_out")
    return out, chosen.reshape(B, T, -1)


# ------------------------------------------------- the mixer of an ``M`` layer
def _ssm_scale(cfg: GPTConfig):
    """(input, the in-projection's five segments, output) multipliers of the
    mixer (``ssm.mix_sequence(scale=)``); None for a config without any."""
    m = cfg.multipliers
    return None if m is None else (m.ssm_in, tuple(m.ssm), m.ssm_out)


def _mix_sequence(cfg: GPTConfig):
    """``mix`` of :func:`_block_on` over whole sequences, each from a zero
    state; nothing carried. None for a config without a mixer."""
    if cfg.ssm is None:
        return None

    def mix(h, w):
        return ssm.mix_sequence(cfg.ssm, h, w, None, None, linear=_wm,
                                eps=cfg.layer_norm_eps,
                                scale=_ssm_scale(cfg))[0], None
    return mix


def _mix_dense_cache(cfg: GPTConfig, caches, layer, real):
    """``mix`` over a dense cache's states ``caches`` = (``ssm_state`` [L, B,
    H, P, N], ``ssm_conv`` [L, B, K - 1, C]): ``T`` new tokens a row, the
    first ``real`` [B] of them real (None: all), from the state and window
    of mixer ``layer``; carries the two stacks, that layer's updated."""
    def mix(h, w):
        state, window = (jax.lax.dynamic_index_in_dim(a, layer, 0,
                                                      keepdims=False)
                         for a in caches)
        out, state, window = ssm.mix_sequence(
            cfg.ssm, h, w, state, window, linear=_wm,
            eps=cfg.layer_norm_eps, real=real, scale=_ssm_scale(cfg))
        return out, tuple(
            jax.lax.dynamic_update_index_in_dim(a, new.astype(a.dtype),
                                                layer, 0)
            for a, new in zip(caches, (state, window)))
    return mix


def _mix_prompt_slots(cfg: GPTConfig, pools, layer, lengths, slots):
    """``mix`` for whole prompts that start at position 0: row ``f`` runs
    from a zero state over its ``lengths[f]`` real tokens, and what its last
    real token left goes into decode slot ``slots[f]`` of mixer ``layer`` of
    the carried pools' states (their last two entries, ``SSM_KEYS``). A row
    of length 0 names no slot and is dropped."""
    if slots is None:
        raise ValueError("a config with a mixer keeps a state a decode slot: "
                         "paged_prefill_step(slots=)")

    def mix(h, w):
        out, state, window = ssm.mix_sequence(
            cfg.ssm, h, w, None, None, linear=_wm, eps=cfg.layer_norm_eps,
            real=lengths, scale=_ssm_scale(cfg))
        slot = jnp.where(lengths > 0, slots, pools[-2].shape[1])
        return out, pools[:-2] + tuple(
            a.at[layer, slot].set(new.astype(a.dtype), mode="drop")
            for a, new in zip(pools[-2:], (state, window)))
    return mix


def _mix_decode_slots(cfg: GPTConfig, pools, layer, active, impl, live):
    """``mix`` for ONE new token a decode slot: mixer ``layer`` of the
    carried pools' states is updated where it lies, for the rows that hold a
    request (``active``), through ``ops/pallas/ssm_decode``; ``live``: its
    grid, the same for every layer of a step."""
    def mix(h, w):
        out, states, windows = ssm.mix_token(
            cfg.ssm, h, w, pools[-2], pools[-1], layer, active, linear=_wm,
            eps=cfg.layer_norm_eps, impl=impl, live=live,
            scale=_ssm_scale(cfg))
        return out, pools[:-2] + (states, windows)
    return mix


def attention_sublayer(cfg: GPTConfig, x: jnp.ndarray, w: Dict[str, jnp.ndarray],
                       positions: jnp.ndarray, dropout_rng, train: bool,
                       layer_idx=None) -> jnp.ndarray:
    """Pre-LN self-attention + residual (shared by dense and MoE blocks)."""
    attn = _attention_delta(cfg, x, w, positions, layer_idx=layer_idx)
    return x + _dropout(attn, cfg.dropout, dropout_rng, train, salt=0)


def _block_on(cfg: GPTConfig, x: jnp.ndarray, w: Dict[str, jnp.ndarray],
              positions: jnp.ndarray, attend, drop=None, mix=None,
              mixer: str = "attn", ffn: str = "dense"):
    """THE transformer block, for every forward: ``x`` [B, T, D] through the
    sublayers the layer has, each added to the stream. ``mixer`` and ``ffn``
    are its :class:`LayerRun`'s words (the defaults: the GPT-2 block, for the
    callers that hold no run and refuse every other block by name). The
    mixer: attention (:func:`_attn_delta` over ``attend``) or a state-space
    mixer (``mix(h, w) -> (output, carried)`` of the normed input,
    :func:`_mix_sequence` and its like, ``carried`` the states it wrote).
    With both (``attn+ssm``) the two read the same normed input side by side
    and their outputs are summed into the one delta.
    The feed-forward: the MLP or the routed experts (:func:`_moe_delta`);
    NeoX/GPT-J's parallel residual feeds both sublayers the same input.
    ``drop(delta, salt)`` is the training forward's dropout, the salt a
    sublayer's place among those the layer has. Returns the stream, what the
    mixer carried (None without one; with both the pair (attention's, the
    state-space mixer's)), and the experts a routed layer chose [B, T, k]
    (None from any other)."""
    y, carried, chosen, salt = x, None, None, 0
    if mixer:
        delta = None
        if "ssm" in mixer:
            with jax.named_scope("ssm"):
                delta, carried = mix(_norm(cfg, x, w, "ln1"), w)
        if "attn" in mixer:
            attn, rows = _attn_delta(cfg, x, w, positions, attend)
            delta, carried = ((attn, rows) if delta is None
                              else (delta + attn, (rows, carried)))
        # a float32 delta is added in float32 and the stream rounded once
        y = (x + (delta if drop is None else drop(delta, salt))).astype(
            x.dtype)
        salt += 1
    if ffn:
        mlp_in = x if cfg.parallel_residual else y
        if ffn == "routed":
            delta, chosen = _moe_delta(cfg, mlp_in, w)
        else:
            delta = _mlp_delta(cfg, mlp_in, w)
        y = (y + (delta if drop is None else drop(delta, salt))).astype(
            x.dtype)
    return y, carried, chosen


def _block(cfg: GPTConfig, x: jnp.ndarray, w: Dict[str, jnp.ndarray],
           positions: jnp.ndarray, dropout_rng, train: bool,
           layer_idx=None, mixer: str = "attn", ffn: str = "dense"
           ) -> jnp.ndarray:
    """:func:`_block_on` over whole sequences (training, no cache)."""
    return _block_on(
        cfg, x, w, positions, _attend_sequence(cfg, positions, layer_idx),
        lambda delta, salt: _dropout(delta, cfg.dropout, dropout_rng, train,
                                     salt), _mix_sequence(cfg), mixer, ffn)[0]


def _dropout(x, rate, rng, train, salt: int):
    if rate == 0.0 or not train or rng is None:
        return x
    key = jax.random.fold_in(rng, salt)
    keep = jax.random.bernoulli(key, 1.0 - rate, x.shape)
    return jnp.where(keep, x / (1.0 - rate), 0.0).astype(x.dtype)


def _head_quantization():
    """Active quantized-LM-head config (``zero_quantized_head``), or None.
    Read from the same trace-bound config the gather windowing rides; inert
    inside the quantized-gradient shard_map (no config is bound there)."""
    from ..runtime.zero.gather import _active_cfg

    zcfg = _active_cfg()
    if zcfg is None or int(getattr(zcfg, "stage", 0)) < 3:
        return None
    if not (getattr(zcfg, "zero_quantized_weights", False)
            and getattr(zcfg, "zero_quantized_head", False)):
        return None
    from ..comm.quantized import QuantizedCommConfig

    return QuantizedCommConfig.from_zero_config(zcfg)


# --------------------------------------------------------------------------- forward
@jax.named_scope("embed")
def _embed(cfg: GPTConfig, params: Dict[str, Any], input_ids: jnp.ndarray,
           positions: jnp.ndarray) -> jnp.ndarray:
    """Token (+ learned position) embedding and its optional layer norm: the
    input of the first block, still in the embedding table's type."""
    x = jnp.take(params["wte"], input_ids, axis=0)
    if cfg.multipliers is not None:     # in float32: the callers round once
        x = _times(cfg, x.astype(jnp.float32), "embed")
    if not cfg.rotary and not cfg.alibi:
        x = x + jnp.take(params["wpe"], positions + cfg.pos_offset, axis=0)
    if cfg.embed_layernorm:
        x = _norm(cfg, x, params, "emb_ln")
    return x


@jax.named_scope("head_loss")
def _head(cfg: GPTConfig, params: Dict[str, Any], x: jnp.ndarray, qh=None
          ) -> jnp.ndarray:
    """LM head over post-LN hidden states; on the quantized wire where the
    caller passes the bound ``zero_quantized_head`` as ``qh``."""
    head = params["wte"] if cfg.tie_embeddings else params["lm_head"]
    if qh is not None:
        # zero_quantized_head: the head gather rides the int wire AND the
        # dequantized fp copy is never materialized — the payload feeds the
        # logits matmul's prologue (ops/pallas/dequant_matmul.py on TPU, the
        # fused XLA fallback elsewhere), with a straight-through backward
        from ..comm.quantized import quantized_matmul_reshard

        B2, T2, D2 = x.shape
        logits = quantized_matmul_reshard(
            x.reshape(-1, D2), head.astype(x.dtype).T, P(None, "tp"),
            qh.bits, qh.block_size, "qmatmul[lm_head]").reshape(B2, T2, -1)
    else:
        logits = jnp.einsum("btd,vd->btv", x, head.astype(x.dtype))
    logits = _times(cfg, logits, "head")
    if cfg.lm_head_bias and not cfg.tie_embeddings:
        logits = logits + params["lm_head_b"].astype(logits.dtype)
    return logits


def _head_input(cfg: GPTConfig, params, x: jnp.ndarray) -> jnp.ndarray:
    """The normed state as a prompt's head takes it: a float32 stream's in
    the head's own type (one rounding, the last), any other as it is."""
    if not cfg.stream_float32:
        return x
    return x.astype((params["wte"] if cfg.tie_embeddings
                     else params["lm_head"]).dtype)


def _lm_logits(cfg: GPTConfig, params: Dict[str, Any], x: jnp.ndarray
               ) -> jnp.ndarray:
    """Final norm and LM head of the cached (inference) forwards."""
    return _head(cfg, params, _norm(cfg, x, params, "lnf"))


def _passes(cfg: GPTConfig, params: Dict[str, Any], x: jnp.ndarray, carry,
            one_pass, xs=None, final_scope: str = "loop_norm"):
    """The passes of a forward over its one stack of blocks, and the norm
    that closes each: the final norm after the last pass (every model has
    it) and, with ``loop_norm``, after every earlier pass too.

    ``one_pass(x, carry, u, xs_u) -> (x, carry, ys_u, marks_u)`` applies the
    ``n_layer`` blocks once: ``u`` is the pass (0 where there is one, traced
    where the stack loops), ``carry`` what the passes hand on whole (the page
    pool), ``xs_u``/``ys_u`` what pass ``u`` alone reads and writes (its
    layers of a dense cache, ``xs`` leading with ``ut_steps``), ``marks_u``
    the stream after each of ``state_layers`` under ``n_layer`` (None where
    there is none). Returns the stream after the final norm, the carry, the
    ``ys`` (leading with ``ut_steps`` where the stack loops) and the states
    of :func:`_states` but for the embedding rows, pass by pass."""
    closes = cfg.n_layer in cfg.state_layers

    def marks_of(marks, x):
        if not cfg.state_layers:
            return None
        end = x[None] if closes else x[:0][None]
        return end if marks is None else jnp.concatenate([marks, end])

    if cfg.ut_steps == 1:
        x, carry, ys, marks = one_pass(x, carry, 0, xs)
        with jax.named_scope(final_scope):
            x = _norm(cfg, x, params, "lnf")
        return x, carry, ys, marks_of(marks, x)

    last = cfg.ut_steps - 1

    def body(c, xs_u):
        x, carry, u = c
        with jax.named_scope("ut_loop"):
            x, carry, ys, marks = one_pass(x, carry, u, xs_u)
            with jax.named_scope("loop_norm"):
                closed = _norm(cfg, x, params, "lnf")
            x = closed if cfg.loop_norm else jnp.where(u == last, closed, x)
        return (x, carry, u + 1), (ys, marks_of(marks, x))

    (x, carry, _), (ys, marks) = jax.lax.scan(
        body, (x, carry, jnp.int32(0)), xs, length=cfg.ut_steps)
    if marks is not None:       # [ut_steps, marks a pass, ...] -> in order
        marks = marks.reshape((-1,) + marks.shape[2:])
    return x, carry, ys, marks


def _states(cfg: GPTConfig, x0: jnp.ndarray, marks) -> jnp.ndarray:
    """What a serving program returns beside its tokens: the residual stream
    of each row ``[B, boundaries, T, D]`` at the embedding rows ``x0`` and
    after each of ``state_layers`` of every pass (``marks`` of
    :func:`_passes`); ``[B, 0, T, D]`` for a config that names none."""
    if marks is None:
        return jnp.zeros((x0.shape[0], 0) + x0.shape[1:], x0.dtype)
    return jnp.concatenate([x0[None], marks]).transpose(1, 0, 2, 3)


def _scan_blocks(cfg: GPTConfig, x: jnp.ndarray, carry, blocks, step,
                 xs=None, first: int = 0, marks=None, offset: int = 0):
    """One pass over one stack of like blocks as a ``lax.scan``:
    ``step(x, carry, layer_w, i, xs_i) -> (x, carry, ys_i)`` for layer ``i``,
    counted from ``first`` (the layers of the stacks before this one);
    ``offset``: where the blocks' first layer lies inside the experts' whole
    stacks, which are handed over unsliced. Returns (x, carry, ys, marks):
    ``marks`` the stream after each of ``state_layers`` under ``n_layer``,
    None where there is none; a later stack is handed the earlier one's.

    Dense weight stacks are the scan's input. Quantized ({"q"/"q4","s"})
    stacks are INDEXED per layer, not scanned over: scan xs get a
    loop-friendly layout, and for a quantized stack XLA realizes that as a
    full transposed COPY of every weight array (measured: OPT-13B int8 decode
    carried 11.8 GB of s8 copies, the difference between fitting a 13B model
    in 15.75 GB HBM and OOMing at 27 GB). A dynamic_index_in_dim on the
    leading axis reads the argument buffer in place; the {q,s} leaves then
    flow into the Pallas int8-weight matmuls via _wm, and no bf16 weight
    buffer exists at any scope."""
    quantized = _is_qleaf(_a_matrix(blocks))
    # the held experts' stacks are not the scan's input either: a layer reads
    # its own inside the whole stack (``experts_layer``; dropless.py says why)
    whole = {k: v for k, v in blocks.items() if k in EXPERT_STACKS}
    blocks = {k: v for k, v in blocks.items() if k not in whole}
    length = jax.tree_util.tree_leaves(blocks)[0].shape[0]
    inner = tuple(m for m in cfg.state_layers if m < cfg.n_layer)
    if inner and marks is None:
        marks = jnp.zeros((len(inner),) + x.shape, x.dtype)

    def body(c, layer_in):
        x, i, carry, marks = c
        layer_w, xs_i = layer_in
        if quantized:
            layer_w = jax.tree_util.tree_map(
                lambda a: jax.lax.dynamic_index_in_dim(a, i - first, 0,
                                                       keepdims=False),
                blocks)
        if whole:
            layer_w = dict(layer_w, **whole,
                           experts_layer=i - (first - offset))
        x, carry, ys_i = step(x, carry, layer_w, i, xs_i)
        if inner:
            hit = (jnp.asarray(inner, jnp.int32) == i + 1).reshape(
                (-1,) + (1,) * x.ndim)
            marks = jnp.where(hit, x[None], marks)
        return (x, i + 1, carry, marks), ys_i

    (x, _, carry, marks), ys = jax.lax.scan(
        body, (x, jnp.int32(first), carry, marks),
        (None if quantized else blocks, xs), length=length)
    return x, carry, ys, marks


EXPERT_STACKS = ("experts_gate_w", "experts_up_w", "experts_down_w")


def _a_matrix(blocks):
    """A weight matrix of a stack: what says its type and whether it is
    quantized."""
    return blocks[next(k for k in ("qkv_w", "q_a_w", "q_w", "ssm_in_w",
                                   "router_w") if k in blocks)]


def _stacks(cfg: GPTConfig, params, experts_whole: bool = True) -> list:
    """(blocks, run) of each run of like layers, in order
    (:func:`layer_runs`): the run's layers of its stack and, with
    ``experts_whole``, the experts' stacks unsliced (a layer reads its own
    inside them, ``_scan_blocks``)."""
    sizes = dict(stack_names(cfg))
    out = []
    for run in layer_runs(cfg):
        blocks = params[run.name]
        if run.count != sizes[run.name]:
            blocks = {
                k: (v if experts_whole and k in EXPERT_STACKS
                    else jax.tree_util.tree_map(
                        lambda a: a[run.offset:run.offset + run.count], v))
                for k, v in blocks.items()}
        out.append((blocks, run))
    return out


def _scan_stacks(cfg: GPTConfig, params, x: jnp.ndarray, carry, step,
                 xs=None):
    """One pass over every run of like layers in order, each a
    :func:`_scan_blocks` of ``step(run, x, carry, layer_w, i, xs_i)``.
    ``step`` returns as ``ys_i`` a pair: what layer ``i`` hands back for the
    cache (``xs``' counterpart, stacked over all layers) and the experts it
    chose (None from a dense layer; stacked over the routed layers). Returns
    (x, carry, cache ys, chosen, marks)."""
    stacks = _stacks(cfg, params)
    if len(stacks) == 1:
        blocks, run = stacks[0]
        x, carry, (ys, chosen), marks = _scan_blocks(
            cfg, x, carry, blocks, functools.partial(step, run), xs)
        return x, carry, ys, chosen, marks
    marks, all_ys, all_chosen = None, [], []
    for blocks, run in stacks:
        xs_s = jax.tree_util.tree_map(
            lambda a: a[run.first:run.first + run.count], xs)
        x, carry, (ys, chosen), marks = _scan_blocks(
            cfg, x, carry, blocks, functools.partial(step, run), xs_s,
            run.first, marks, run.offset)
        all_ys.append(ys)
        if chosen is not None:
            all_chosen.append(chosen)
    ys = jax.tree_util.tree_map(lambda *a: jnp.concatenate(a), *all_ys)
    return (x, carry, ys,
            jnp.concatenate(all_chosen) if all_chosen else None, marks)


def _compute_input(cfg: GPTConfig, params, x: jnp.ndarray) -> jnp.ndarray:
    """The embedding rows in the type the blocks compute in: the weights',
    or the norm gains' where the weight stacks are quantized (which the new
    block fields do not reach: :func:`require_default_block`)."""
    qkv_w = _a_matrix(_stacks(cfg, params)[0][0])
    if _is_qleaf(qkv_w):
        require_default_block(cfg, "a quantized weight stack")
        return x.astype(params["lnf_scale"].dtype)
    return x.astype(qkv_w.dtype)


def forward(cfg: GPTConfig, params: Dict[str, Any], input_ids: jnp.ndarray,
            rngs: Optional[Dict[str, jax.Array]] = None, train: bool = True,
            return_hidden: bool = False, pld_theta=None) -> jnp.ndarray:
    """Return logits [B, T, V] (or the final-LN hidden states [B, T, D] with
    ``return_hidden`` — the encoder surface CLIP-style text towers need).

    ``pld_theta``: traced scalar keep-probability from the engine's Progressive
    Layer Drop schedule (reference ``runtime/progressive_layer_drop.py:5``);
    gates each block with the paper's depth-scaled probability."""
    B, T = input_ids.shape
    if T > cfg.max_seq_len:
        raise ValueError(
            f"sequence length {T} exceeds max_seq_len {cfg.max_seq_len} "
            f"(out-of-range position lookups would return NaN)")
    positions = jnp.broadcast_to(jnp.arange(T)[None, :], (B, T))
    x = _embed(cfg, params, input_ids, positions).astype(
        _a_matrix(_stacks(cfg, params)[0][0]).dtype)
    # residual stream sharded over batch and (if sp>1) sequence
    x = maybe_shard(x, P(BATCH, "sp", None))

    drng = (rngs or {}).get("dropout")

    policy = None
    if cfg.remat:
        if cfg.remat_policy == "save_attn_mlp_out":
            # selective: keep each sublayer's projected output (2*d_model per
            # token per layer) so backward skips recomputing the output
            # projections; everything else (flash internals, ln, gelu) remats
            policy = jax.checkpoint_policies.save_only_these_names(
                "attn_out", "mlp_out")
        else:
            policy = getattr(jax.checkpoint_policies, cfg.remat_policy)

    def block_fn_of(run):
        """The block of one run's kind of layer."""
        kcfg = kind_view(cfg, run.kind)

        def block_fn(x, layer_w, pos, lrng, layer_idx):
            return _block(kcfg, x, layer_w, pos, lrng, train, layer_idx,
                          run.mixer, run.ffn)
        return (jax.checkpoint(block_fn, policy=policy) if cfg.remat
                else block_fn)

    sd = cfg.stochastic_depth if train else 0.0
    if pld_theta is not None and (sd > 0.0 or not train):
        raise ValueError(
            "progressive_layer_drop is train-only and exclusive with "
            "stochastic_depth (both gate whole blocks)")
    use_ltd = (train and cfg.random_ltd_keep is not None
               and cfg.random_ltd_keep < T and cfg.random_ltd_layer_ids)
    ltd_ids = jnp.asarray(cfg.random_ltd_layer_ids or (0,), jnp.int32)

    def body(drng, block_fn, carry, layer_w):
        x, i = carry
        lrng = jax.random.fold_in(drng, i) if drng is not None else None
        if use_ltd:
            from ..runtime.data_pipeline.data_routing.random_ltd import (
                random_ltd_gather, random_ltd_scatter)

            def ltd_branch(xx):
                krng = jax.random.fold_in(
                    lrng if lrng is not None else jax.random.PRNGKey(0x17D), i)
                kept, idx = random_ltd_gather(xx, cfg.random_ltd_keep, krng)
                kept_pos = jnp.take_along_axis(positions, idx, axis=1)
                out = block_fn(kept, layer_w, kept_pos, lrng, i)
                return random_ltd_scatter(out, idx, xx)

            y = jax.lax.cond(jnp.isin(i, ltd_ids), ltd_branch,
                             lambda xx: block_fn(xx, layer_w, positions,
                                                 lrng, i), x)
        else:
            y = block_fn(x, layer_w, positions, lrng, i)
        if pld_theta is not None:
            # PLD depth scaling (arXiv:2010.13369): deeper layers drop first —
            # layer i keeps with p_i = 1 - (i+1)/L * (1 - theta(t)); surviving
            # deltas are rescaled so eval runs the full stack uncorrected
            keep_p = (1.0 - (jnp.asarray(i + 1, jnp.float32) / cfg.n_layer)
                      * (1.0 - pld_theta))
            if lrng is None:
                # a fixed fallback key would freeze the drop mask across steps
                # (layers past their draw would never train again)
                raise ValueError(
                    "progressive_layer_drop needs a dropout rng: pass "
                    "rngs={'dropout': key} to forward()")
            keep = jax.random.bernoulli(
                jax.random.fold_in(jax.random.fold_in(lrng, 0x91D), i), keep_p)
            # max() keeps the untaken branch's gradient finite when keep_p -> 0
            x = x + jnp.where(keep, (y - x) / jnp.maximum(keep_p, 1e-3),
                              0.0).astype(x.dtype)
        elif sd > 0.0 and lrng is not None:
            # stochastic depth: drop the whole block with prob sd; the
            # surviving delta is scaled so eval needs no correction
            keep = jax.random.bernoulli(jax.random.fold_in(lrng, 0x5D), 1.0 - sd)
            x = x + jnp.where(keep, (y - x) / (1.0 - sd), 0.0).astype(x.dtype)
        else:
            x = y
        return (x, i + 1), None

    # layer loop with explicit ZeRO-3 gather windowing (stage3_max_live_parameters
    # / stage3_prefetch_bucket_size; plain per-layer scan when unconfigured)
    from ..runtime.zero.gather import zero3_layer_scan

    specs = partition_specs(cfg, None)

    def one_pass(x, _, u, __):
        # a looped stack draws each pass's dropout anew
        prng = (drng if drng is None or cfg.ut_steps == 1
                else jax.random.fold_in(drng, u))
        c = (x, jnp.int32(0))
        with jax.named_scope("blocks"):
            # the layer count runs on from run to run
            for blocks, run in _stacks(cfg, params, experts_whole=False):
                c = zero3_layer_scan(
                    functools.partial(body, prng, block_fn_of(run)),
                    c, blocks,
                    gathered_spec=jax.tree_util.tree_map(
                        lambda s: P(*tuple(s)[1:]), specs[run.name],
                        is_leaf=lambda s: isinstance(s, P)))
        return c[0], None, None, None

    x = _passes(cfg, params, x, None, one_pass, final_scope="head_loss")[0]
    if return_hidden:
        return x
    if not cfg.has_lm_head:
        raise ValueError(
            "this config is a pure encoder (has_lm_head=False, e.g. an "
            "imported CLIP text tower): call forward(..., return_hidden=True) "
            "— there is no LM head to produce logits with")
    with jax.named_scope("head_loss"):
        return _head(cfg, params, x, _head_quantization())


def next_token_loss(forward_fn, max_seq_len: int, batch: Dict[str, jnp.ndarray]
                    ) -> Tuple[jnp.ndarray, Dict[str, Any]]:
    """Shared next-token cross-entropy: handles the optional "labels"/"loss_mask"
    keys and the seq-vs-seq+1 packing cases identically for every GPT variant
    (dense / MoE / pipelined). ``forward_fn(input_ids) -> logits``."""
    input_ids = batch["input_ids"]
    labels = batch.get("labels")
    if labels is None:
        labels = input_ids[:, 1:]
        if input_ids.shape[1] > max_seq_len:
            # seq+1 token packing: slice inputs to max_seq_len (labels align 1:1)
            logits = forward_fn(input_ids[:, :-1])
        else:
            # keep the full (tile-friendly) length through attention; drop the
            # last logit instead of the last input token
            logits = forward_fn(input_ids)[:, :-1]
    else:
        logits = forward_fn(input_ids)
    with jax.named_scope("head_loss"):
        logits32 = logits.astype(jnp.float32)
        logz = jax.scipy.special.logsumexp(logits32, axis=-1)
        gold = jnp.take_along_axis(logits32, labels[..., None],
                                   axis=-1)[..., 0]
        nll = logz - gold
        mask = batch.get("loss_mask")
        if mask is not None:
            mask = mask.astype(jnp.float32)
            if labels.shape != batch["input_ids"].shape:
                mask = mask[:, 1:]
            loss = jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
        else:
            loss = jnp.mean(nll)
    return loss, {"num_tokens": nll.size}


def _chunked_ce(hidden: jnp.ndarray, head: jnp.ndarray,
                head_bias: Optional[jnp.ndarray], targets: jnp.ndarray,
                mask: jnp.ndarray, chunk: int) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Masked cross entropy over `chunk`-token sequence slices.

    Each scan step computes ONE chunk's logits (``[B, chunk, V]``) and its
    fp32 logsumexp, and the step is rematted so backward recomputes the chunk
    logits instead of keeping them — peak memory holds one chunk's logits,
    not ``[B, T, V]``. Returns (sum of masked nll, sum of mask)."""
    B, T, D = hidden.shape
    if T % chunk:
        raise ValueError(f"loss_chunk {chunk} must divide seq len {T}")
    n = T // chunk
    h = hidden.reshape(B, n, chunk, D).transpose(1, 0, 2, 3)
    t = targets.reshape(B, n, chunk).transpose(1, 0, 2)
    m = mask.reshape(B, n, chunk).transpose(1, 0, 2)

    def body(carry, xs):
        h_c, t_c, m_c = xs
        logits = jnp.einsum("bcd,vd->bcv", h_c, head.astype(h_c.dtype))
        if head_bias is not None:
            logits = logits + head_bias.astype(logits.dtype)
        logits32 = logits.astype(jnp.float32)
        logz = jax.scipy.special.logsumexp(logits32, axis=-1)
        gold = jnp.take_along_axis(logits32, t_c[..., None], axis=-1)[..., 0]
        nll = (logz - gold) * m_c
        s, c = carry
        return (s + jnp.sum(nll), c + jnp.sum(m_c)), None

    (s, c), _ = jax.lax.scan(jax.checkpoint(body),
                             (jnp.float32(0.0), jnp.float32(0.0)), (h, t, m))
    return s, c


def _chunk_targets(cfg: GPTConfig, batch: Dict[str, jnp.ndarray]
                   ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, int]:
    """(input_ids_for_forward, targets [B,T], mask [B,T], num_real_targets)
    replicating :func:`next_token_loss`'s label/mask/packing semantics on
    full-T tiles (unmatched positions masked out; ``num_real_targets`` is
    the whole-sequence path's ``nll.size`` — the padded dummy position in
    the standard shift case is excluded)."""
    input_ids = batch["input_ids"]
    labels = batch.get("labels")
    loss_mask = batch.get("loss_mask")
    if labels is None and input_ids.shape[1] > cfg.max_seq_len:
        # seq+1 token packing: inputs are the first max_seq_len tokens
        ids_in = input_ids[:, :-1]
        shift_targets = input_ids[:, 1:]
    else:
        ids_in = input_ids
        shift_targets = None
    B, T = ids_in.shape
    if labels is not None:
        targets = labels
        mask = (loss_mask.astype(jnp.float32) if loss_mask is not None
                else jnp.ones((B, T), jnp.float32))
        return ids_in, targets, mask, int(targets.size)
    elif shift_targets is not None:
        targets = shift_targets
        mask = (loss_mask[:, 1:].astype(jnp.float32)
                if loss_mask is not None else jnp.ones((B, T), jnp.float32))
        return ids_in, targets, mask, int(targets.size)
    else:
        # standard next-token shift: last position has no target — mask it
        # (and pad targets with a dummy 0 there) so chunks tile the full T
        targets = jnp.concatenate(
            [input_ids[:, 1:], jnp.zeros((B, 1), input_ids.dtype)], axis=1)
        mask = jnp.concatenate(
            [jnp.ones((B, T - 1), jnp.float32),
             jnp.zeros((B, 1), jnp.float32)], axis=1)
        if loss_mask is not None:
            shifted = jnp.concatenate(
                [loss_mask[:, 1:], jnp.zeros((B, 1), loss_mask.dtype)], axis=1)
            mask = mask * shifted.astype(jnp.float32)
    return ids_in, targets, mask, int(targets.size - B)  # dummy col excluded


@jax.named_scope("head_loss")
def chunked_head_loss(cfg: GPTConfig, params, hidden: jnp.ndarray,
                      targets: jnp.ndarray, mask: jnp.ndarray,
                      num_tokens: Optional[int] = None
                      ) -> Tuple[jnp.ndarray, Dict[str, Any]]:
    """Chunked LM head + masked cross entropy over post-LN ``hidden`` — shared
    by the dense and pipelined models."""
    head = params["wte"] if cfg.tie_embeddings else params["lm_head"]
    head_b = (params.get("lm_head_b")
              if (cfg.lm_head_bias and not cfg.tie_embeddings) else None)
    s, c = _chunked_ce(hidden, head, head_b, targets, mask, cfg.loss_chunk)
    # masked mean == next_token_loss semantics in every case: without a
    # loss_mask the mask counts exactly the real target positions
    return s / jnp.maximum(c, 1.0), {
        "num_tokens": int(num_tokens if num_tokens is not None
                          else targets.size)}


def chunked_loss(cfg: GPTConfig, params, batch: Dict[str, jnp.ndarray],
                 rngs=None, train: bool = True, pld_theta=None
                 ) -> Tuple[jnp.ndarray, Dict[str, Any]]:
    """:func:`loss_fn` semantics with the LM head + cross entropy evaluated in
    ``cfg.loss_chunk``-token slices (see :func:`_chunked_ce`). Numerically the
    same masked mean as :func:`next_token_loss`."""
    ids_in, targets, mask, n_tok = _chunk_targets(cfg, batch)
    hidden = forward(cfg, params, ids_in, rngs=rngs, train=train,
                     return_hidden=True, pld_theta=pld_theta)
    return chunked_head_loss(cfg, params, hidden, targets, mask,
                             num_tokens=n_tok)


def loss_fn(cfg: GPTConfig, params, batch: Dict[str, jnp.ndarray],
            rngs=None, train: bool = True, pld_theta=None
            ) -> Tuple[jnp.ndarray, Dict[str, Any]]:
    """Next-token cross entropy. ``batch``: {"input_ids": [B,T]} (+ optional
    "labels"/"loss_mask")."""
    if cfg.loss_chunk:
        if not cfg.has_lm_head:
            raise ValueError("loss_chunk needs an LM head")
        return chunked_loss(cfg, params, batch, rngs=rngs, train=train,
                            pld_theta=pld_theta)
    return next_token_loss(
        lambda ids: forward(cfg, params, ids, rngs=rngs, train=train,
                            pld_theta=pld_theta),
        cfg.max_seq_len, batch)


# ------------------------------------------------------- ZeRO-Infinity stream
class GPTStream:
    """ZeRO-Infinity unit decomposition of the GPT stack (``Module.stream``).

    The model is exposed as ``embed`` / ``layer_0..L-1`` / ``final`` units so
    the param-stream runner (:mod:`deepspeed_tpu.runtime.zero.infinity`) can
    keep master weights in host RAM and stream ONE unit at a time through HBM —
    the ``offload_param`` capability (reference: ``deepspeed/runtime/zero/
    partition_parameters.py`` remote-device "cpu"/"nvme" + ``docs/_pages/
    training.md:301`` 13B-on-one-V100). Host init is numpy — the full model is
    never materialized on device — and every layer unit is shape-identical, so
    the runner compiles exactly one fwd and one bwd program for all L layers.
    """

    def __init__(self, cfg: GPTConfig):
        require_default_block(cfg, "GPTStream (ZeRO-Infinity units)")
        self.cfg = cfg
        self.n_layer = cfg.n_layer
        self.tied = cfg.tie_embeddings

    def unit_names(self):
        return (["embed"] + [f"layer_{i}" for i in range(self.n_layer)]
                + ["final"])

    # ---------------------------------------------------------- host init
    def init_unit(self, name: str, seed: int) -> Dict[str, np.ndarray]:
        cfg = self.cfg
        d, f, v = cfg.d_model, cfg.ffn_dim, cfg.vocab_size
        idx = self.unit_names().index(name)
        rng = np.random.default_rng([int(seed) & 0x7FFFFFFF, idx])
        std = 0.02
        res_std = float(std / np.sqrt(2.0 * cfg.n_layer))

        def normal(shape, s):
            # float(s): a np.float64 scalar would NEP50-promote the product
            return rng.standard_normal(shape, np.float32) * np.float32(s)

        def ones(shape):
            return np.ones(shape, np.float32)

        def zeros(shape):
            return np.zeros(shape, np.float32)

        if name == "embed":
            out = {"wte": normal((v, d), std)}
            if not cfg.rotary and not cfg.alibi:
                out["wpe"] = normal((cfg.max_seq_len + cfg.pos_offset, d), std)
            if cfg.embed_layernorm:
                out["emb_ln_scale"] = ones((d,))
                out["emb_ln_bias"] = zeros((d,))
            return out
        if name == "final":
            out = {"lnf_scale": ones((d,)), "lnf_bias": zeros((d,))}
            if not cfg.tie_embeddings:
                out["lm_head"] = normal((v, d), std)
                if cfg.lm_head_bias:
                    out["lm_head_b"] = zeros((v,))
            return out
        return {
            "ln1_scale": ones((d,)), "ln1_bias": zeros((d,)),
            "qkv_w": normal((d, 3 * d), std), "qkv_b": zeros((3 * d,)),
            "attn_out_w": normal((d, d), res_std), "attn_out_b": zeros((d,)),
            "ln2_scale": ones((d,)), "ln2_bias": zeros((d,)),
            "mlp_up_w": normal((d, f), std), "mlp_up_b": zeros((f,)),
            "mlp_down_w": normal((f, d), res_std), "mlp_down_b": zeros((d,)),
        }

    # ---------------------------------------------------------- device programs
    def embed_fwd(self, emb: Dict[str, jnp.ndarray], input_ids: jnp.ndarray,
                  compute_dtype) -> jnp.ndarray:
        cfg = self.cfg
        B, T = input_ids.shape
        x = jnp.take(emb["wte"], input_ids, axis=0)
        if "wpe" in emb:
            positions = jnp.broadcast_to(jnp.arange(T)[None, :], (B, T))
            x = x + jnp.take(emb["wpe"], positions + cfg.pos_offset, axis=0)
        if cfg.embed_layernorm:
            x = layer_norm(x, emb["emb_ln_scale"], emb["emb_ln_bias"],
                           cfg.layer_norm_eps)
        return x.astype(compute_dtype)

    def layer_fwd(self, w: Dict[str, jnp.ndarray], x: jnp.ndarray,
                  layer_idx, rng) -> jnp.ndarray:
        cfg = self.cfg
        B, T = x.shape[:2]
        positions = jnp.broadcast_to(jnp.arange(T)[None, :], (B, T))
        return _block(cfg, x, w, positions, rng, train=True,
                      layer_idx=layer_idx)

    def head_loss(self, final: Dict[str, jnp.ndarray], wte: jnp.ndarray,
                  x: jnp.ndarray, input_ids: jnp.ndarray,
                  labels: Optional[jnp.ndarray] = None,
                  loss_mask: Optional[jnp.ndarray] = None) -> jnp.ndarray:
        """Same semantics as :func:`next_token_loss`: explicit ``labels`` score
        the full sequence; otherwise next-token targets are the shifted input
        ids. ``loss_mask`` weights positions (shifted alongside the labels)."""
        cfg = self.cfg
        x = layer_norm(x, final["lnf_scale"], final["lnf_bias"],
                       cfg.layer_norm_eps)
        head = wte if cfg.tie_embeddings else final["lm_head"]
        logits = jnp.einsum("btd,vd->btv", x, head.astype(x.dtype))
        if cfg.lm_head_bias and not cfg.tie_embeddings:
            logits = logits + final["lm_head_b"].astype(logits.dtype)
        if labels is None:
            logits32 = logits[:, :-1].astype(jnp.float32)
            labels = input_ids[:, 1:]
            if loss_mask is not None:
                loss_mask = loss_mask[:, 1:]
        else:
            logits32 = logits.astype(jnp.float32)
        logz = jax.scipy.special.logsumexp(logits32, axis=-1)
        gold = jnp.take_along_axis(logits32, labels[..., None], axis=-1)[..., 0]
        nll = logz - gold
        if loss_mask is not None:
            mask = loss_mask.astype(jnp.float32)
            return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
        return jnp.mean(nll)


# ------------------------------------------------------------- int8 weights
def quantize_for_inference(cfg: GPTConfig, params, bits: int = 8,
                           group_size: int = 128):
    """Replace the stacked block weight matrices with per-layer-grouped int8
    ``{"q", "s"}`` leaves. The cached forward feeds them to the Pallas
    int8-weight matmul (``ops/pallas/int8_matmul.py``): s8 stays in HBM and
    dequantization happens per VMEM tile, so no bf16 weight buffer exists at
    any scope (parity: the reference's int8 inference kernels consuming
    quantized weights directly, ``csrc/transformer/inference/csrc/
    dequantize.cu`` + GroupQuantizer, ``module_inject/replace_module.py:144``).
    ``group_size`` defaults to 128 — the kernel needs scale runs covering
    whole lanes; smaller groups fall back to XLA dequant-then-matmul."""
    from ..ops.quantizer import quantize

    require_default_block(cfg, "quantize_for_inference")
    L = cfg.n_layer
    blocks = {}
    for k, v in params["blocks"].items():
        per_layer = int(v.size) // L
        if v.ndim >= 3 and per_layer % group_size == 0 and not k.startswith("ln"):
            ng_l = max(1, per_layer // group_size)
            q, s = quantize(v, bits=bits, num_groups=L * ng_l)
            if bits == 4 and v.shape[-1] % 2 == 0:
                # two nibbles per byte (pack_int4 half-split layout): the
                # weight stack shrinks to a QUARTER of bf16 — 20B decode
                # becomes chip-resident on one v5e
                from ..ops.pallas.int8_matmul import pack_int4

                blocks[k] = {"q4": pack_int4(q), "s": s.reshape(L, ng_l)}
            else:
                blocks[k] = {"q": q, "s": s.reshape(L, ng_l)}
        else:
            blocks[k] = v
    out = dict(params)
    out["blocks"] = blocks
    return out


def init_quantized_decode_params(cfg: GPTConfig, seed: int = 0,
                                 bits: int = 4, group_size: int = 128,
                                 compute_dtype=jnp.bfloat16):
    """Build the quantized decode tree WITHOUT ever materializing the fp32
    model: layer units are host-initialized one at a time (``GPTStream``
    numpy init), quantized + nibble-packed in numpy, and only the narrow
    stacks are pushed to the device. A 20B model's device footprint is the
    ~10 GB int4 stacks + bf16 embeddings — the fp32 tree (80 GB) that
    ``init_params`` -> ``quantize_for_inference`` would need never exists on
    host OR device, which is what makes a MEASURED 20B-decode row possible
    on one chip. Quantization math is bit-identical to
    ``ops/quantizer.quantize`` (symmetric group-wise, round-half-even)."""
    import ml_dtypes

    s = GPTStream(cfg)
    L = cfg.n_layer
    qmax = 2.0 ** (bits - 1) - 1.0
    cd_np = (ml_dtypes.bfloat16 if jnp.dtype(compute_dtype) == jnp.bfloat16
             else np.float32)

    def np_quantize(w, ng):
        g = np.ascontiguousarray(w, np.float32).reshape(ng, -1)
        absmax = np.max(np.abs(g), axis=1, keepdims=True)
        scales = np.where(absmax > 0, absmax / qmax, 1.0).astype(np.float32)
        q = np.clip(np.round(g / scales), -qmax - 1, qmax).astype(np.int8)
        return q.reshape(w.shape), scales[:, 0]

    def np_pack4(q):
        F = q.shape[-1]
        lo = q[..., : F // 2].astype(np.int32) & 0xF
        hi = q[..., F // 2:].astype(np.int32)
        return (lo | (hi << 4)).astype(np.int8)

    acc_q: Dict[str, list] = {}
    acc_s: Dict[str, list] = {}
    acc_dense: Dict[str, list] = {}
    packed_keys = set()
    for i in range(L):
        unit = s.init_unit(f"layer_{i}", seed)
        for k, v in unit.items():
            # same predicate as quantize_for_inference (there: stacked
            # ndim >= 3 == per-layer ndim >= 2)
            if (v.ndim >= 2 and v.size % group_size == 0
                    and not k.startswith("ln")):
                q, sc = np_quantize(v, v.size // group_size)
                if bits == 4 and v.shape[-1] % 2 == 0:
                    q = np_pack4(q)
                    packed_keys.add(k)
                acc_q.setdefault(k, []).append(q)
                acc_s.setdefault(k, []).append(sc)
            else:
                acc_dense.setdefault(k, []).append(v.astype(cd_np))
        del unit
    blocks: Dict[str, Any] = {}
    for k in acc_q:
        qk = "q4" if k in packed_keys else "q"
        blocks[k] = {qk: jnp.asarray(np.stack(acc_q[k])),
                     "s": jnp.asarray(np.stack(acc_s[k]))}
        acc_q[k] = None
    for k in acc_dense:
        blocks[k] = jnp.asarray(np.stack(acc_dense[k]))
    params: Dict[str, Any] = {"blocks": blocks}
    for unit in ("embed", "final"):
        for k, v in s.init_unit(unit, seed).items():
            params[k] = jnp.asarray(v.astype(cd_np))
    return params


def _is_qleaf(v) -> bool:
    return isinstance(v, dict) and set(v.keys()) in ({"q", "s"}, {"q4", "s"})


def quantized_partition_specs(params, specs):
    """Expand spec leaves to match ``{"q", "s"}`` quantized leaves (int8 keeps
    the weight's spec; per-layer scales replicate)."""
    from jax.sharding import PartitionSpec as P_

    def expand(leaf, spec):
        if _is_qleaf(leaf):
            qk = "q4" if "q4" in leaf else "q"
            return {qk: spec, "s": P_(None, None)}
        return spec

    return jax.tree_util.tree_map(
        expand, params, specs, is_leaf=_is_qleaf)


# --------------------------------------------------------------------- KV-cache decode
def init_cache(cfg: GPTConfig, batch_size: int, max_len: int, dtype=jnp.bfloat16):
    """Per-layer stacked KV cache. Parity: the reference's inference workspace
    (``csrc/transformer/inference/includes/inference_context.h``) — here a pytree
    of [L, B, H, S, Dh] arrays living in HBM, L the :func:`cache_layers` (one
    a pass and layer). Heads lead the sequence axis so the Pallas decode
    kernel streams Mosaic-tileable (block_k, Dh) slices."""
    if cfg.attn_kind == "mla" and (cfg.attn_period or cfg.index_topk
                                   or cfg.attn_window):
        raise ValueError(
            "a dense cache holds one row shape a token and every layer: "
            f"attn_period={cfg.attn_period!r}, index_topk={cfg.index_topk}, "
            f"attn_window={cfg.attn_window} of attn_kind='mla' keep pages, "
            "index keys and rings (init_paged_cache, paged_prefill_step)")
    pools, heads, width = cache_row(cfg)
    shape = (cache_layers(cfg), batch_size, heads, max_len, width)
    dtype = cache_dtype(cfg, dtype)
    cache = {"k": jnp.zeros(shape, dtype), "pos": jnp.zeros((), jnp.int32)}
    if pools == 2:      # latent attention caches one row: no "v"
        cache["v"] = jnp.zeros(shape, dtype)
    if cfg.ssm is not None:     # a state and a window a mixer and sequence
        lead = (ssm_layers(cfg), batch_size)
        cache[SSM_KEYS[0]] = jnp.zeros(lead + cfg.ssm.state_shape(),
                                       jnp.float32)
        cache[SSM_KEYS[1]] = jnp.zeros(lead + cfg.ssm.window_shape(),
                                       jnp.float32)
    return cache


DENSE_KEYS = ("k", "v")
# the mixers' states and convolution windows, float32: [M layers, sequences
# (a dense cache) or decode slots (the serving cache), ...]
SSM_KEYS = ("ssm_state", "ssm_conv")


def dense_caches(cache) -> Tuple[jnp.ndarray, ...]:
    """The dense cache's arrays in ``DENSE_KEYS`` order: (k, v), or the one
    latent cache."""
    return tuple(cache[k] for k in DENSE_KEYS if k in cache)


def _masked_attention(cfg: GPTConfig, q, k, v, positions, layer_idx=None):
    """Softmax attention of ``q`` [B, T, H, Dh] at absolute ``positions``
    [B, T] over keys and values [B, H, S, Dh] whose place is their position:
    float32 scores under a validity + causal mask (and the local window and
    ALiBi where the config has them). [B, T, H, Dh] in the values' type."""
    S = k.shape[2]
    logits = jnp.einsum("bthd,bhsd->bhts", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * _softmax_scale(cfg)
    s_idx = jnp.arange(S)[None, :]
    t_idx = positions[:, :, None]  # absolute position of each query token
    mask = s_idx <= t_idx  # [B, T, S]
    is_local = _is_local_layer(cfg, layer_idx)
    if is_local is not None:
        # windowed layers additionally drop keys older than window_size
        mask = jnp.logical_and(
            mask, jnp.logical_or(~is_local, s_idx > t_idx - cfg.window_size))
    if cfg.alibi:
        logits = logits + _alibi_bias(cfg, positions, S)
    logits = jnp.where(mask[:, None, :, :], logits, jnp.float32(-1e30))
    probs = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhts,bhsd->bthd", probs.astype(v.dtype), v)


def _attend_dense_cache(cfg: GPTConfig, k_cache, v_cache, pos, positions,
                        layer_idx=None):
    """``attend`` that appends at ``pos`` to one layer of a dense cache
    [B, H, S, Dh] and attends over it; carries the two caches. With latent
    attention ``v_cache`` is None and the one cache [B, 1, S, latent_width] is
    carried alone: one token attends absorbed, a chunk expands the rows."""
    if cfg.attn_kind == "mla":
        def attend_latent(q, latent, kvb):
            rows = jax.lax.dynamic_update_slice(
                k_cache, latent.transpose(0, 2, 1, 3).astype(k_cache.dtype),
                (0, 0, pos, 0))
            return _mla_attention(cfg, q, rows[:, 0], kvb, positions,
                                  absorbed=q.shape[1] == 1), (rows,)
        return attend_latent
    if cfg.attn_kind == "gqa":
        def attend_gqa(q, k_, v):
            T, S = q.shape[1], k_cache.shape[2]
            k_c, v_c = (jax.lax.dynamic_update_slice(
                c, a.transpose(0, 2, 1, 3).astype(c.dtype), (0, 0, pos, 0))
                for c, a in ((k_cache, k_), (v_cache, v)))
            if cfg.attn_window and T + cfg.attn_window < S:
                # the chunk's own rows and the window before them
                span = T + cfg.attn_window
                first = jnp.clip(pos + T - span, 0, S - span)
                rows = (jax.lax.dynamic_slice_in_dim(c, first, span, 2)
                        for c in (k_c, v_c))
                attn = _gqa_attention(cfg, q, *rows, positions, first)
            else:
                attn = _gqa_attention(cfg, q, k_c, v_c, positions,
                                      live=pos + T)
            return attn, (k_c, v_c)
        return attend_gqa

    def attend(q, k_, v):
        T = q.shape[1]
        k_c = jax.lax.dynamic_update_slice(
            k_cache, k_.transpose(0, 2, 1, 3).astype(k_cache.dtype),
            (0, 0, pos, 0))
        v_c = jax.lax.dynamic_update_slice(
            v_cache, v.transpose(0, 2, 1, 3).astype(v_cache.dtype),
            (0, 0, pos, 0))
        use_kernel = (cfg.use_flash is True
                      or (cfg.use_flash is None
                          and jax.default_backend() == "tpu"))
        if cfg.alibi or cfg.local_attention_period > 1:
            use_kernel = False  # decode kernel has no bias/window input yet
        if T == 1 and use_kernel:
            # per-token decode: fused Pallas cache-attention kernel (parity:
            # softmax_context, csrc/transformer/inference); auto mode gates
            # on the TPU backend like the prefill flash dispatch
            # (ops/attention.py)
            from ..ops.pallas.decode_attention import decode_attention

            attn = decode_attention(q.astype(k_c.dtype), k_c, v_c, pos + 1,
                                    softmax_scale=_softmax_scale(cfg))
        else:
            # prefill: attend over the whole cache, masked
            attn = _masked_attention(cfg, q, k_c, v_c, positions, layer_idx)
        return attn, (k_c, v_c)
    return attend


def _cache_positions(x, pos):
    B, T = x.shape[:2]
    return pos + jnp.broadcast_to(jnp.arange(T)[None, :], (B, T))


def attn_with_cache(cfg: GPTConfig, x, w, k_cache, v_cache, pos, layer_idx=None):
    """Cached self-attention sublayer (pre-LN + residual), shared by the dense
    and MoE cached forwards.

    x: [B, T, D] new tokens (T=prompt len at prefill, 1 at decode);
    k_cache/v_cache: [B, H, S, Dh]; pos: scalar — tokens already in the cache.
    Returns (x + attn_out, k_cache, v_cache).
    """
    positions = _cache_positions(x, pos)
    attn, (k_cache, v_cache) = _attn_delta(
        cfg, x, w, positions,
        _attend_dense_cache(cfg, k_cache, v_cache, pos, positions, layer_idx))
    return x + attn, k_cache, v_cache


def _block_with_cache(cfg: GPTConfig, x, w, k_cache, v_cache, pos,
                      layer_idx=None):
    """:func:`_block_on` over one layer's slice of a dense KV cache."""
    positions = _cache_positions(x, pos)
    x, (k_cache, v_cache), _ = _block_on(
        cfg, x, w, positions,
        _attend_dense_cache(cfg, k_cache, v_cache, pos, positions, layer_idx))
    return x, k_cache, v_cache


def forward_with_cache(cfg: GPTConfig, params, input_ids: jnp.ndarray, cache,
                       return_states: bool = False, real=None):
    """Prefill or decode: run ``input_ids`` [B, T] through the model appending to
    ``cache``; returns (logits [B, T, V], new_cache) and, with
    ``return_states``, :func:`_states` of the new tokens third. Pass ``u`` of
    a looped stack reads and writes cache layers ``n_layer * u ..``.

    ``real`` (a scalar or [B]; None: all ``T``): the real tokens of a padded
    chunk. Keys and values past them are written and never read; a mixer's
    state is what the last REAL token left (a config with a ``layer_pattern``
    or a mixer, whose caches are carried whole through its layers: the keys
    and values and the mixers' states each count their own layers)."""
    B, T = input_ids.shape
    if cfg.layer_pattern or cfg.ssm is not None:
        return _forward_with_cache_pattern(cfg, params, input_ids, cache,
                                           return_states, real)
    pos = cache["pos"]
    positions = pos + jnp.broadcast_to(jnp.arange(T)[None, :], (B, T))
    x0 = _embed(cfg, params, input_ids, positions)
    x0 = (x0.astype(jnp.float32) if cfg.stream_float32
          else _compute_input(cfg, params, x0))
    x = maybe_shard(x0, P(BATCH, None, None))

    def step(run, x, _, layer_w, i, kv):
        kcfg = kind_view(cfg, run.kind)
        x, kv, chosen = _block_on(
            kcfg, x, layer_w, positions, _attend_dense_cache(
                kcfg, kv[0], kv[1] if len(kv) > 1 else None, pos, positions,
                i), mixer=run.mixer, ffn=run.ffn)
        return x, None, (kv, chosen)

    def one_pass(x, _, u, kv):
        with jax.named_scope("blocks"):
            x, _, kv, _, marks = _scan_stacks(cfg, params, x, None, step, kv)
        return x, None, kv, marks

    def by_pass(a):     # [cache layers, ...] <-> [ut_steps, n_layer, ...]
        return a if cfg.ut_steps == 1 else a.reshape(
            (cfg.ut_steps, cfg.n_layer) + a.shape[1:])

    x, _, new, marks = _passes(
        cfg, params, x, None, one_pass,
        xs=tuple(by_pass(a) for a in dense_caches(cache)))
    new_cache = {"pos": pos + T}
    for key, a in zip(DENSE_KEYS, new):
        new_cache[key] = a.reshape(cache[key].shape)
    logits = _head(cfg, params, _head_input(cfg, params, x))
    if return_states:
        return logits, new_cache, _states(cfg, x0, marks)
    return logits, new_cache


def _forward_with_cache_pattern(cfg: GPTConfig, params, input_ids, cache,
                                return_states, real):
    """:func:`forward_with_cache` of a config with a ``layer_pattern`` or a
    state-space mixer: the caches carried whole, a layer reading and writing
    its cache layer, its state layer, or both (:class:`LayerRun`)."""
    B, T = input_ids.shape
    pos = cache["pos"]
    positions = pos + jnp.broadcast_to(jnp.arange(T)[None, :], (B, T))
    x0 = _embed(cfg, params, input_ids, positions)
    x0 = (x0.astype(jnp.float32) if cfg.stream_float32
          else _compute_input(cfg, params, x0))
    keys = tuple(k for k in DENSE_KEYS + SSM_KEYS if k in cache)
    n_kv = sum(k in DENSE_KEYS for k in keys)
    if real is not None:
        real = jnp.broadcast_to(jnp.asarray(real, jnp.int32), (B,))

    def step(run, x, caches, layer_w, i, _):
        attend = mix = None
        if run.attends:
            layer = run.cache_layer(i, 0)
            kv = tuple(jax.lax.dynamic_index_in_dim(a, layer, 0, False)
                       for a in caches[:n_kv])
            attend = _attend_dense_cache(cfg, kv[0], kv[1], pos, positions,
                                         i)
        if run.mixes:
            mix = _mix_dense_cache(cfg, caches[n_kv:], run.state_layer(i),
                                   real)
        x, new, chosen = _block_on(cfg, x, layer_w, positions, attend,
                                   mix=mix, mixer=run.mixer, ffn=run.ffn)
        rows, states = (new if run.attends and run.mixes
                        else (new, None) if run.attends else (None, new))
        if rows is not None:
            caches = tuple(
                jax.lax.dynamic_update_index_in_dim(a, n, layer, 0)
                for a, n in zip(caches[:n_kv], rows)) + caches[n_kv:]
        if states is not None:
            caches = caches[:n_kv] + states
        return x, caches, (None, chosen)

    def one_pass(x, caches, u, _):
        with jax.named_scope("blocks"):
            x, caches, _, _, marks = _scan_stacks(cfg, params, x, caches,
                                                  step)
        return x, caches, None, marks

    x, caches, _, marks = _passes(
        cfg, params, maybe_shard(x0, P(BATCH, None, None)),
        tuple(cache[k] for k in keys), one_pass)
    new_cache = dict(zip(keys, caches), pos=pos + T)
    logits = _head(cfg, params, _head_input(cfg, params, x))
    if return_states:
        return logits, new_cache, _states(cfg, x0, marks)
    return logits, new_cache


# ----------------------------------------------------------- paged KV decode
KV_QMAX = {8: 127.0, 4: 7.0}


def init_paged_cache(cfg: GPTConfig, num_pages: int, page_size: int,
                     dtype=jnp.bfloat16, kv_bits: Optional[int] = None,
                     ring_slots: int = 0) -> Dict[str, jnp.ndarray]:
    """Block-allocated KV cache: one shared page pool per cache layer
    (:func:`cache_layers`: a layer of every pass of a looped stack),
    [L, H, P, page_size, Dh]. Requests own pages through a *block table*
    (``inference/serving/paging.py``); HBM holds ``P * page_size`` token
    slots total, shared by every in-flight request — the vLLM/paged-attention
    memory model, vs the contiguous :func:`init_cache` which reserves
    ``max_len`` slots per batch row whether used or not.

    ``kv_bits`` (8 or 4) stores the pools QUANTIZED: int8 payloads (int4
    nibble-packs two values per byte along Dh) plus one symmetric fp32
    scale per (layer, head, page) in ``k_scales``/``v_scales`` —
    2x/4x the token capacity at fixed HBM vs bf16 pools, dequantized per
    tile inside the Pallas decode kernel. A quantized cache is recognized
    by the presence of the scale stacks.

    Page 0 is the allocator's reserved sink: inactive decode slots and
    masked scatter lanes write there, so pool page ids handed to requests
    start at 1.

    The layers share ONE array so that a decode step can carry it whole
    through its layer loop and address a layer inside it
    (:func:`paged_decode_step`): the step then holds the pool once and
    neither slices a layer out nor stacks one back.

    Two kinds of cache layer in one tree (:func:`paged_layers`): a layer
    with a window (``attn_period``) keeps no pages but, for each of
    ``ring_slots`` decode slots, the last :func:`ring_rows` rows, position
    ``t`` at row ``t mod R``: ``k_ring``/``v_ring`` [L_window, H, slots, R,
    Dh]. The serving programs name the slot, so a ring needs no table and no
    allocator, and a slot's rows cost the same at any length; the pools then
    hold the other layers only.

    A third kind: a state-space mixer (``ssm``) keeps no row a token at all
    but, for each of ``ring_slots`` decode slots, its state and its
    convolution window, float32 whatever ``dtype`` is: ``ssm_state``
    [mixers, slots, heads, head_dim, state] and ``ssm_conv`` [mixers, slots,
    K - 1, conv_width] (``SSM_KEYS``). They are addressed by slot as the
    rings are. Under a ``layer_pattern`` the pools hold the ``*`` layers
    only; a layer with both mixers keeps its pages AND its state."""
    layers, rings = paged_layers(cfg)
    pools, heads, width = cache_row(cfg)
    if cfg.ssm is not None:
        if ring_slots < 1:
            raise ValueError("a config with a mixer keeps a state a decode "
                             "slot: init_paged_cache(ring_slots=)")
    if cfg.attn_float32 and kv_bits:
        raise ValueError("attn_float32=True keeps keys and values in float32:"
                         f" kv_bits={kv_bits} would round them")
    dtype = cache_dtype(cfg, dtype)
    if kv_bits is None or kv_bits == 0:
        # latent attention: ONE pool [L, 1, P, page_size, latent_width], no
        # value pool (the values are the first ``rank`` columns of a row)
        shape = (layers, heads, num_pages, page_size, width)
        cache = {key: jnp.zeros(shape, dtype) for key in POOL_KEYS[:pools]}
        if rings:
            if ring_slots < 1:
                raise ValueError("a config with window layers keeps a ring a "
                                 "decode slot: init_paged_cache(ring_slots=)")
            ring = (rings, heads, ring_slots, ring_rows(cfg, page_size),
                    cache_row(cfg, ring=True)[2])
            cache.update({key: jnp.zeros(ring, dtype)
                          for key in RING_KEYS[:pools]})
        if index_layers(cfg):
            # a fourth kind: the index keys of the layers that select their
            # rows, pages of the SAME block table as the latent rows (a pool
            # of its own: 128 numbers beside 640 in one row would be 768,
            # and the latent kernel reads whole rows); and the positions
            # each slot's last decode step selected, [layers, slots, topk]
            # int32, -1 past the live ones: the step's own output, carried
            # with the pools it is made from
            if ring_slots < 1:
                raise ValueError("a config that selects its rows keeps each "
                                 "slot's last selection: "
                                 "init_paged_cache(ring_slots=)")
            view = _run_view(cfg, False)
            cache[INDEX_KEYS[0]] = jnp.zeros(
                (index_layers(cfg), 1, num_pages, page_size, view.index_dim),
                jnp.float32 if cfg.index_float32 else dtype)
            cache[INDEX_KEYS[1]] = jnp.full(
                (index_layers(cfg), ring_slots, view.index_topk), -1,
                jnp.int32)
        if cfg.ssm is not None:
            lead = (ssm_layers(cfg), ring_slots)
            cache[SSM_KEYS[0]] = jnp.zeros(lead + cfg.ssm.state_shape(),
                                           jnp.float32)
            cache[SSM_KEYS[1]] = jnp.zeros(lead + cfg.ssm.window_shape(),
                                           jnp.float32)
        return cache
    if kv_bits not in KV_QMAX:
        raise ValueError(f"kv_bits must be 8 or 4 (or None), got {kv_bits}")
    require_default_block(cfg, f"a quantized page pool (kv_bits={kv_bits})")
    if kv_bits == 4 and cfg.head_dim % 2:
        raise ValueError("int4 KV needs an even head_dim (nibble packing)")
    dq = cfg.head_dim // 2 if kv_bits == 4 else cfg.head_dim
    shape = (layers, cfg.n_head, num_pages, page_size, dq)
    sshape = (layers, cfg.n_head, num_pages)
    return {"k_pages": jnp.zeros(shape, jnp.int8),
            "v_pages": jnp.zeros(shape, jnp.int8),
            "k_scales": jnp.ones(sshape, jnp.float32),
            "v_scales": jnp.ones(sshape, jnp.float32)}


def paged_cache_bits(paged_cache, head_dim: int) -> Optional[int]:
    """The cache's KV quantization width (None = dense pools)."""
    if "k_scales" not in paged_cache:
        return None
    return 4 if paged_cache["k_pages"].shape[-1] * 2 == head_dim else 8


def paged_kv_bytes_per_token(cfg: GPTConfig, kv_bits: Optional[int] = None,
                             page_size: int = 64,
                             dtype=jnp.bfloat16) -> float:
    """HBM bytes one cached token costs in an :func:`init_paged_cache`
    pool: dense payload at ``dtype``, or quantized payload at ``kv_bits``
    plus the amortized fp32 per-(layer, head, page) scales. The ONE byte
    formula shared by the AOT fit ladder, the serving engine's equal-HBM
    A/B axis, and the bench's emulated pool sizing — a scale-layout change
    in ``init_paged_cache`` must be priced here, once."""
    pools, heads, width = cache_row(cfg)
    layers = paged_layers(cfg)[0]       # a ring's rows are a slot's, not a
    per_tok = pools * layers * heads * width    # token's: ring_bytes_per_slot
    if not kv_bits:
        # and an index key a token and selecting layer, in pages of its own
        keys = index_layers(cfg) * _run_view(cfg, False).index_dim
        return float(per_tok * jnp.dtype(cache_dtype(cfg, dtype)).itemsize
                     + keys * (4 if cfg.index_float32
                               else jnp.dtype(dtype).itemsize))
    payload = per_tok // (2 if kv_bits == 4 else 1)
    scales = pools * layers * heads * 4 / page_size
    return float(payload + scales)


def ring_bytes_per_slot(cfg: GPTConfig, page_size: int = 64,
                        dtype=jnp.bfloat16) -> int:
    """HBM bytes the window layers' rings cost a decode slot, whatever its
    request's length: :func:`ring_rows` rows of :func:`cache_row` in each."""
    pools, heads, width = cache_row(cfg, ring=True)
    return (pools * paged_layers(cfg)[1] * heads * ring_rows(cfg, page_size)
            * width * jnp.dtype(dtype).itemsize)


def dense_kv_bytes(cfg: GPTConfig, rows: int, max_len: int,
                   dtype=jnp.bfloat16) -> int:
    """Bytes of an :func:`init_cache` of ``rows`` sequences of ``max_len``:
    what a token caches (:func:`cache_row`) in every cache layer."""
    pools, heads, width = cache_row(cfg)
    return int(pools * cache_layers(cfg) * heads * width
               * jnp.dtype(dtype).itemsize * rows * max_len)


def _pack_kv_int4(q: jnp.ndarray) -> jnp.ndarray:
    """Values in [-8, 7] pack two per byte along the last dim — the one
    canonical half-split layout (``ops.pallas.int8_matmul.pack_int4``,
    inverted by ``decode_attention.unpack_kv_int4``)."""
    from ..ops.pallas.int8_matmul import pack_int4

    return pack_int4(q)


@jax.named_scope("kv_write")
def write_prompt_kv_batch(paged_cache: Dict[str, jnp.ndarray],
                          dense_cache: Dict[str, jnp.ndarray],
                          block_tables: jnp.ndarray,  # [F, pages_per_seq]
                          lengths: jnp.ndarray,       # [F] valid tokens/row
                          starts: Optional[jnp.ndarray] = None,  # [F] or 0
                          cfg: Optional[GPTConfig] = None,
                          slots: Optional[jnp.ndarray] = None,   # [F]
                          ) -> Dict[str, jnp.ndarray]:
    """Write a BATCH of prefilled requests' dense K/V into their pages.

    Prefill runs on the contiguous cache (the existing, tested
    :func:`forward_with_cache` path, compiled per bucket shape); each row's
    K/V is then placed into the pages its block-table row names — the
    prefill/decode disaggregation boundary. Positions past a row's length
    (bucket padding, or a wholly inactive row with length 0) are dropped.
    ``starts`` additionally drops positions BELOW a per-row floor: a request
    admitted with shared prefix pages (copy-on-write prefix caching) must
    never write the pages it only borrows, so its write begins at the first
    unshared position.

    Dense pools go through the one page-block writer,
    :func:`_write_prompt_pages`, a cache layer at a time with the pools as
    the loop's carry: [gcd(S, ps), Dh] blocks at ``[layer, head, page,
    piece]``, every index named, so no copy of a pool exists beside the
    pool and a caller that donates it (the engine's ``jit_scatter``) gets it
    back updated in place. With layer and head left as slices of one
    scatter the TPU compiler re-laid both pools head-minor and back, four
    copies of 3.03 GB at 481 pages (PERF.md, PR 33).

    Quantized pools (``init_paged_cache(kv_bits=...)``) quantize at scatter
    time: one symmetric scale per (layer, head, page) from the absmax of
    the tokens landing in that page, payloads rounded/clipped exactly like
    ``ops.quantizer.quantize``. They still scatter [L, H]-sliced windows.

    A cache with rings (``cfg`` with window layers, and the decode slot of
    each row in ``slots``): the dense cache holds every layer, and each run
    of :func:`layer_runs` goes to its own kind of cache layer, pages or the
    slot's ring (:func:`_write_ring`)."""
    k = jnp.asarray(dense_cache["k"])  # [L, F, H, S, Dh]
    S = k.shape[3]
    F = k.shape[1]
    P = paged_cache["k_pages"].shape[2]
    ps = paged_cache["k_pages"].shape[3]
    tables = jnp.asarray(block_tables, jnp.int32)
    lengths = jnp.asarray(lengths, jnp.int32)
    if starts is None:
        starts = jnp.zeros((F,), jnp.int32)
    else:
        starts = jnp.broadcast_to(jnp.asarray(starts, jnp.int32), (F,))
    bits = paged_cache_bits(paged_cache, k.shape[-1])
    if bits is None:
        dt = paged_cache["k_pages"].dtype

        # (k, v), or the one latent cache
        sides = tuple(jnp.asarray(a) for a in dense_caches(dense_cache))

        def one_layer(layer, pools, run=None):
            rows = tuple(a[layer if run is None else run.first + layer]
                         .astype(dt) for a in sides)
            if run is None:     # past the pools: the mixers' states
                return _write_prompt_pages(
                    pools[:len(rows)], layer, rows, tables, lengths,
                    starts) + pools[len(rows):]
            return _write_prompt_rows(
                pools, run.ring, run.cache_first + layer, rows, tables,
                lengths, starts, slots)

        pools = paged_pools(paged_cache)
        if RING_KEYS[0] not in paged_cache:
            pools = jax.lax.fori_loop(0, k.shape[0], one_layer, pools)
        else:
            slots = jnp.asarray(slots, jnp.int32)
            for run in layer_runs(cfg):
                pools = jax.lax.fori_loop(
                    0, run.count, functools.partial(one_layer, run=run),
                    pools)
        if SSM_KEYS[0] in paged_cache:
            # a prompt's state has no position: what its last chunk left
            # goes whole into the request's decode slot
            slot = jnp.where(lengths > 0, jnp.asarray(slots, jnp.int32),
                             pools[-2].shape[1])
            pools = pools[:-2] + tuple(
                a.at[:, slot].set(jnp.asarray(dense_cache[key]).astype(
                    a.dtype), mode="drop")
                for a, key in zip(pools[-2:], SSM_KEYS))
        return _as_cache(paged_cache, pools)
    v = jnp.asarray(dense_cache["v"])
    pos = jnp.broadcast_to(jnp.arange(S)[None, :], (F, S))
    page_of_pos = jnp.take_along_axis(tables, pos // ps, axis=1)  # [F, S]
    valid = (pos >= starts[:, None]) & (pos < lengths[:, None])
    # invalid positions get page id P (out of bounds) -> mode="drop"
    page = jnp.where(valid, page_of_pos, P)
    off = pos % ps
    qmax = KV_QMAX[bits]
    L, _, H, _, Dh = k.shape
    Sp = -(-S // ps) * ps  # pad S up to whole pages for the grouped absmax
    npg = Sp // ps
    vmask = valid
    if Sp != S:
        vmask = jnp.concatenate(
            [valid, jnp.zeros((F, Sp - S), bool)], axis=1)
    vmask_g = vmask.reshape(F, npg, ps)
    any_valid = vmask_g.any(axis=2)  # [F, npg]
    # page ids per (row, page-slot); unwritten pages scatter out of bounds.
    # The dense scratch may be PADDED past the table (its S rounds up to
    # whole prefill chunks, the table to whole pages of max_model_len) —
    # pad the excess page slots with the drop index; they can never hold a
    # valid token, matching the dense path's clip-then-mask semantics.
    tbl = tables[:, :npg]
    if tbl.shape[1] < npg:
        tbl = jnp.concatenate(
            [tbl, jnp.full((F, npg - tbl.shape[1]), P, jnp.int32)], axis=1)
    pages_w = jnp.where(any_valid, tbl, P)

    def quantize_side(x, pages_key, scales_key):
        xt = x.transpose(0, 2, 1, 3, 4).astype(jnp.float32)  # [L,H,F,S,Dh]
        if Sp != S:
            xt = jnp.concatenate(
                [xt, jnp.zeros(xt.shape[:3] + (Sp - S, Dh), jnp.float32)],
                axis=3)
        xg = xt.reshape(L, H, F, npg, ps, Dh)
        amax = jnp.max(jnp.abs(xg) * vmask_g[None, None, :, :, :, None],
                       axis=(4, 5))                          # [L,H,F,npg]
        scales = jnp.where(amax > 0, amax / qmax, 1.0)
        q = jnp.clip(jnp.round(xg / scales[..., None, None]),
                     -qmax - 1, qmax)
        if bits == 4:
            q = _pack_kv_int4(q)
        else:
            q = q.astype(jnp.int8)
        q = q.reshape(L, H, F, Sp, q.shape[-1])[:, :, :, :S]
        return {
            pages_key: paged_cache[pages_key].at[:, :, page, off, :].set(
                q, mode="drop"),
            # k_scales[l, h, pages_w[f, j]] = scales[l, h, f, j]
            scales_key: paged_cache[scales_key].at[:, :, pages_w].set(
                scales, mode="drop"),
        }

    out = quantize_side(k, "k_pages", "k_scales")
    out.update(quantize_side(v, "v_pages", "v_scales"))
    return out


def write_prompt_kv(paged_cache: Dict[str, jnp.ndarray],
                    dense_cache: Dict[str, jnp.ndarray],
                    block_table: jnp.ndarray,  # [pages_per_seq] int32
                    length: jnp.ndarray,       # scalar int32: valid tokens
                    row: int = 0,
                    start: jnp.ndarray = 0, cfg: Optional[GPTConfig] = None,
                    slot=None) -> Dict[str, jnp.ndarray]:
    """Single-request :func:`write_prompt_kv_batch` over ``dense_cache`` row
    ``row``. ``start`` skips positions below it (shared prefix pages);
    ``cfg`` and the request's decode ``slot`` for a cache with rings."""
    one = {key: dense_cache[key][:, row:row + 1]
           for key in DENSE_KEYS + SSM_KEYS if key in dense_cache}
    table = jnp.asarray(block_table, jnp.int32)[None]
    return write_prompt_kv_batch(paged_cache, one, table,
                                 jnp.asarray(length, jnp.int32)[None],
                                 jnp.asarray(start, jnp.int32)[None], cfg,
                                 None if slot is None
                                 else jnp.asarray(slot, jnp.int32)[None])


def _token_rows(layer, n_head: int, page: jnp.ndarray, off: jnp.ndarray):
    """The index of row ``b``'s token in every head of a layer of a pool
    stack [L, H, P, ps, Dh]: ``pool.at[rows].set(values [B, H, Dh])``.

    Every index is explicit, the head too, so what is scattered is B x H
    rows of Dh. With the heads left as a slice the scattered window is
    [H, Dh], and for that window the TPU compiler lays the whole stack out
    head-minor, against the layout the decode kernel reads: a loop that
    carries the stack then converts all of it, both ways, in every layer
    (compile-only, PERF.md PR 28)."""
    return (layer, jnp.arange(n_head)[None, :], page[:, None], off[:, None])


def _append_kv_token(pages_q: jnp.ndarray, scales: jnp.ndarray,
                     tok: jnp.ndarray, page: jnp.ndarray, off: jnp.ndarray,
                     bits: int, layer=None) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """THE sequential quantized-pool append: one token per batch row into its
    tail page. ``pages_q``: [H, P, ps, Dq]; ``scales``: [H, P]; ``tok``:
    [H, B, Dh] float32; ``page``/``off``: [B]. With a ``layer`` index the
    pool and the scales are the whole stacks, [L, H, P, ps, Dq] and
    [L, H, P], and only that layer's pages are read or written, where they
    lie: a loop that carries the stacks appends in place.

    A row OPENING a page (offset 0) re-establishes the page scale from its
    own token (the pool's prior value there is garbage — init, or a recycled
    page's previous tenant); mid-page the scale grows monotonically and, on
    the rare step where some row's scale actually grew, the page's existing
    payload requantizes under it via ``lax.cond`` (ratio 1.0 rows round-trip
    bit-identically). Shared by the single-token decode step AND the
    speculative commit scatter (:func:`commit_window_kv`) so the two paths
    cannot drift — committing n accepted tokens reproduces n sequential
    appends of the same values (payloads bitwise; scales to the last ULP,
    where XLA may compile the ``amax / qmax`` divide as a reciprocal
    multiply in one program and not the other)."""
    from ..ops.pallas.decode_attention import unpack_kv_int4

    if layer is None:  # one layer's pool: a stack of one
        pages_q, scales = _append_kv_token(pages_q[None], scales[None], tok,
                                           page, off, bits, layer=0)
        return pages_q[0], scales[0]
    qmax = KV_QMAX[bits]
    tok = tok.transpose(1, 0, 2)                      # [B, H, Dh]
    B = tok.shape[0]
    # a scalar and an index vector around a slice: the indexed axes lead,
    # so every value below is [B, H, ...]
    opening = (off == 0)[:, None]                     # [B, 1]
    s_old = scales[layer, :, page]                    # [B, H]
    rows = _token_rows(layer, tok.shape[1], page, off)
    amax = jnp.max(jnp.abs(tok), axis=-1)
    fresh = jnp.where(amax > 0, amax / qmax, 1.0)
    s_new = jnp.where(opening, fresh, jnp.maximum(s_old, fresh))
    tq = jnp.clip(jnp.round(tok / s_new[..., None]), -qmax - 1, qmax)
    if bits == 4:
        tq = _pack_kv_int4(tq)
    else:
        tq = tq.astype(jnp.int8)

    def token_only(pages_q):
        # the common decode step: the page scale already covers the
        # token — one [B, H, Dq] position write, no page rewrite
        return pages_q.at[rows].set(tq)

    def requantize(pages_q):
        # some mid-page row's scale GREW: rescale that page's existing
        # payload under the new scale (opening rows just overwrite
        # garbage), then insert the token
        cur = pages_q[layer, :, page]                 # [B, H, ps, Dq]
        cur = (unpack_kv_int4(cur) if bits == 4
               else cur.astype(jnp.float32))
        ratio = (s_old / s_new)[..., None, None]
        curq = jnp.clip(jnp.round(cur * ratio), -qmax - 1, qmax)
        curq = (_pack_kv_int4(curq) if bits == 4
                else curq.astype(jnp.int8))
        curq = curq.at[jnp.arange(B), :, off, :].set(tq)
        return pages_q.at[layer, :, page].set(curq)

    grew = jnp.any(jnp.logical_and(~opening, s_new > s_old))
    pages_q = jax.lax.cond(grew, requantize, token_only, pages_q)
    return pages_q, scales.at[layer, :, page].set(s_new)


RING_KEYS = ("k_ring", "v_ring")
# the index keys' pages and each slot's last selection (init_paged_cache)
INDEX_KEYS = ("index_pages", "selected")
POOL_KEYS = (("k_pages", "v_pages", "k_scales", "v_scales") + RING_KEYS
             + SSM_KEYS + INDEX_KEYS)


def paged_pools(paged_cache: Dict[str, jnp.ndarray]) -> Tuple[jnp.ndarray, ...]:
    """The cache's arrays in ``POOL_KEYS`` order: (k_pages, v_pages) and,
    where the pools are quantized, their scale stacks, or, where window
    layers keep rings, those; last, where mixers keep states, the states and
    the windows. The tuple a decode step's layer loop carries."""
    return tuple(paged_cache[k] for k in POOL_KEYS if k in paged_cache)


def _pool_names(paged_cache) -> Tuple[str, ...]:
    """The keys of :func:`paged_pools`' arrays, in its order."""
    return tuple(k for k in POOL_KEYS if k in paged_cache)


def _as_cache(paged_cache, pools) -> Dict[str, jnp.ndarray]:
    """:func:`paged_pools` back under ``paged_cache``'s keys."""
    return dict(zip(_pool_names(paged_cache), pools))


def append_and_attend(pools, layer, q, k_, v, tables, lengths, softmax_scale,
                      impl=None, q_dtype=None, work=None):
    """Append one new token per row to layer ``layer`` of the pool stacks and
    attend over that layer's pages, both where they lie. ``pools``:
    :func:`paged_pools` of the whole cache, [L, H, P, ps, Dh] (and [L, H, P]
    scales); q/k_/v: [B, 1, H, Dh] post-rope, H the pools' heads (a tensor-
    parallel shard passes its own); ``lengths``: [B] tokens already cached;
    ``work``: :func:`paged_work` of the step, the same for every layer (the
    kernel builds its own without it). Returns (attn [B, 1, H, Dh], pools).

    The append comes first, so the kernel sees the new token in the pool. It
    is a scatter of B x H x Dh values into the stack: in a loop that carries
    ``pools`` XLA writes it in place, and no layer's pool is sliced out or
    stacked back.

    Quantized append: a row OPENING a new page (offset 0) establishes the
    page scale from its own token — the pool's prior value there is
    garbage (init, or a recycled page's previous tenant). Mid-page, the
    token quantizes against the page scale; when its absmax exceeds what
    the scale covers, the scale GROWS and the page's existing payload
    requantizes under it (one [ps, Dh] elementwise pass, taken via
    ``lax.cond`` only on steps where some row actually grew — the common
    step is a single-position write) — no clipping of outlier tokens,
    scales only ever grow within a page's lifetime."""
    from ..ops.pallas.decode_attention import paged_decode_attention

    k_pages, v_pages = pools[0], pools[1]
    k_scales, v_scales = pools[2:] if len(pools) == 4 else (None, None)
    ps, Dh = k_pages.shape[3], q.shape[-1]
    # append the new token's k/v into each row's current tail page
    page = jnp.take_along_axis(tables, (lengths // ps)[:, None],
                               axis=1)[:, 0]  # [B]
    off = lengths % ps
    with jax.named_scope("kv_write"):
        if k_scales is None:
            dt = k_pages.dtype
            rows = _token_rows(layer, k_pages.shape[1], page, off)
            k_pages = k_pages.at[rows].set(k_[:, 0].astype(dt))
            v_pages = v_pages.at[rows].set(v[:, 0].astype(dt))
        else:
            bits = 4 if k_pages.shape[-1] * 2 == Dh else 8
            # shared sequential append semantics (opening / grow / requantize):
            # _append_kv_token, also the speculative commit scatter's writer
            k_pages, k_scales = _append_kv_token(
                k_pages, k_scales,
                k_[:, 0].transpose(1, 0, 2).astype(jnp.float32), page, off,
                bits, layer=layer)
            v_pages, v_scales = _append_kv_token(
                v_pages, v_scales,
                v[:, 0].transpose(1, 0, 2).astype(jnp.float32), page, off,
                bits, layer=layer)
    qdt = k_pages.dtype if k_scales is None else q_dtype
    attn = paged_decode_attention(q.astype(qdt), k_pages, v_pages,
                                  lengths + 1, tables,
                                  softmax_scale=softmax_scale, impl=impl,
                                  k_scales=k_scales, v_scales=v_scales,
                                  layer=layer, work=work)
    pools = ((k_pages, v_pages) if k_scales is None
             else (k_pages, v_pages, k_scales, v_scales))
    return attn, pools


def paged_work(paged_cache: Dict[str, jnp.ndarray], tables, lengths):
    """The live pages of a decode step, for the paged kernel's grid
    (``ops/pallas/decode_attention.paged_work_list``): ``lengths`` [B] tokens
    already cached, so the list covers the token the step appends. It depends
    on the lengths and the tables alone: built once a step, outside the layer
    loop, and handed to every layer's :func:`append_and_attend`."""
    from ..ops.pallas.decode_attention import paged_pool_list

    return paged_pool_list(lengths + 1, tables, paged_cache["k_pages"],
                           "k_scales" in paged_cache)


def _attend_pages(cfg: GPTConfig, pools, layer, tables, lengths, impl,
                  q_dtype, work, names=("k_pages",)):
    """``attend`` for ONE new token per row over the page pool: cache layer
    ``layer`` of ``pools`` (:func:`paged_pools` of the whole cache) is
    appended to and read where it lies (:func:`append_and_attend`); carries
    the pools."""
    if cfg.attn_kind == "mla":
        return lambda q, latent, kvb, index=None: append_and_attend_latent(
            cfg, pools, layer, q, latent, kvb, tables, lengths, impl=impl,
            index=index, names=names, work=work)
    if cfg.attn_kind == "gqa":
        return lambda q, k_, v: append_and_attend_gqa(
            cfg, pools, layer, q, k_, v, tables, lengths, work, impl=impl)

    def attend(q, k_, v):
        return append_and_attend(pools, layer, q, k_, v, tables, lengths,
                                 _softmax_scale(cfg), impl=impl,
                                 q_dtype=q_dtype, work=work)
    return attend


def append_and_attend_latent(cfg: GPTConfig, pools, layer, q, latent, kvb,
                             tables, lengths, impl=None, index=None,
                             names=("k_pages",), work=None):
    """:func:`append_and_attend` over the latent pool: each row's new
    ``[c_kv | k_rope | 0]`` [B, 1, 1, latent_width] goes into its tail page
    of cache layer ``layer`` of the one pool [L, 1, P, ps, latent_width], and
    the query, with ``W_kvb`` absorbed, attends over the pages as they lie
    (``ops/pallas/decode_attention.paged_decode_mla``: all heads against one
    row a token, both products on the MXU, a page read once for all heads).
    ``work``: :func:`mla_work`, the step's live groups of pages (None: each
    call lists its own). Returns (attention [B, 1, H, v], pools)."""
    from ..ops.pallas.decode_attention import paged_decode_mla

    work = work or {"full": None, "ring": None}
    if len(names) > 1:      # a model whose kinds of latent layer differ
        return _append_and_attend_kinds(cfg, dict(zip(names, pools)), names,
                                        layer, q, latent, kvb, tables,
                                        lengths, impl, index, work)
    pool, = pools
    ps = pool.shape[3]
    page = jnp.take_along_axis(tables, (lengths // ps)[:, None],
                               axis=1)[:, 0]
    with jax.named_scope("kv_write"):
        pool = pool.at[_token_rows(layer, 1, page, lengths % ps)].set(
            latent[:, 0].astype(pool.dtype))
    q_lat = _mla_absorb(cfg, q, kvb)
    q_lat = jnp.pad(q_lat, ((0, 0),) * 3 + ((0, pool.shape[-1]
                                             - q_lat.shape[-1]),))
    # a float32 query (``stream_float32``) takes the attention over the
    # latent back in float32: the kernel accumulates it so either way
    o_lat = paged_decode_mla(
        q_lat if q.dtype == jnp.float32 else q_lat.astype(pool.dtype), pool,
        lengths + 1, tables, rank=cfg.kv_lora_rank,
        softmax_scale=_softmax_scale(cfg), impl=impl, layer=layer,
        out_dtype=q.dtype, work=work["full"])
    return _mla_unabsorb(cfg, o_lat, kvb), (pool,)


def _append_and_attend_kinds(cfg: GPTConfig, named, names, layer, q, latent,
                             kvb, tables, lengths, impl, index, work):
    """:func:`append_and_attend_latent` for one kind of a model whose latent
    layers are of several (``cfg`` its :func:`kind_view`), ``layer`` counted
    among the cache layers of its kind, ``named`` the carried arrays by
    their keys. A window layer appends to its slot's latent ring and reads
    the ring's rows inside the window, the ring read as the slot's pages (as
    :func:`append_and_attend_gqa` reads one). A layer in pages appends its
    row and, where it selects (``index`` = :func:`_index_parts` of the
    token), its index key to the tail page; scores the slot's live index
    keys, keeps the ``index_topk`` best exactly (``lax.top_k``: ties to the
    lower position) and attends over those rows alone: the kernel walks the
    block table as without a selection and admits the selected rows
    (``allowed``). The other route, the selected rows gathered one by one
    into a compact pool the kernel reads whole, measured the same 14.66 ms a
    step for 14.63 at 32 slots of 9,984 rows (an XLA gather of 2,048 rows a
    slot runs at 44 GB/s; my chip runs, PERF.md PR 51) and is not kept. A
    request of at most ``index_topk`` rows reads every live row, what a
    layer without an indexer reads. The positions kept go into
    ``selected[layer]`` [slots, topk], -1 after them."""
    from ..ops.pallas.decode_attention import paged_decode_mla

    pool = named["k_pages"]
    ps, B = pool.shape[3], q.shape[0]
    scale, rank = _softmax_scale(cfg), cfg.kv_lora_rank

    def over(rows, lens, table, at=None, **how):
        """The absorbed attention over ``rows``' pages."""
        q_lat = _mla_absorb(cfg, q, kvb)
        q_lat = jnp.pad(q_lat, ((0, 0),) * 3 + (
            (0, rows.shape[-1] - q_lat.shape[-1]),))
        o_lat = paged_decode_mla(
            q_lat if q.dtype == jnp.float32 else q_lat.astype(rows.dtype),
            rows, lens, table, rank=rank, softmax_scale=scale, impl=impl,
            layer=at, out_dtype=q.dtype, **how)
        return _mla_unabsorb(cfg, o_lat, kvb)

    def done(attn):
        return attn, tuple(named[k] for k in names)

    if cfg.attn_window:
        with jax.named_scope("kv_write"):
            ring = _ring_append(named[RING_KEYS[0]], layer, latent[:, 0],
                                lengths)
        named[RING_KEYS[0]] = ring
        L, _, n_slots, R, C = ring.shape
        ring_tables, listed = work["ring"] or (_ring_tables(n_slots, R, ps),
                                               None)
        return done(over(ring.reshape(L, 1, n_slots * (R // ps), ps, C),
                         lengths + 1, ring_tables, layer,
                         ring=(R, cfg.attn_window), work=listed))
    page = jnp.take_along_axis(tables, (lengths // ps)[:, None],
                               axis=1)[:, 0]
    at = _token_rows(layer, 1, page, lengths % ps)
    with jax.named_scope("kv_write"):
        pool = named["k_pages"] = pool.at[at].set(
            latent[:, 0].astype(pool.dtype))
        if index is not None:
            keys = named[INDEX_KEYS[0]]     # [B, 1 token = 1 head, Di]
            keys = named[INDEX_KEYS[0]] = keys.at[at].set(
                index[1].astype(keys.dtype))
    if index is None:
        return done(over(pool, lengths + 1, tables, layer,
                         work=work["full"]))
    S, k = tables.shape[1] * ps, cfg.index_topk
    with jax.named_scope("index"):
        with jax.named_scope("kv_read"):
            slot_keys = keys[layer, 0][tables].reshape(B, S, -1)
        live = jnp.arange(S)[None, :] < (lengths + 1)[:, None]
        scores = jnp.where(live, _index_scores(index[0], index[2],
                                               slot_keys)[:, 0], -jnp.inf)
        best, taken = jax.lax.top_k(scores, min(k, S))
        valid = best > -jnp.inf
        kept = jnp.where(valid & (lengths > 0)[:, None], taken, -1)
        named[INDEX_KEYS[1]] = named[INDEX_KEYS[1]].at[layer].set(
            jnp.pad(kept, ((0, 0), (0, k - kept.shape[1])),
                    constant_values=-1).astype(jnp.int32))
        allowed = _selected(scores, live, k, kth=best[:, -1])
    return done(over(pool, lengths + 1, tables, layer, allowed=allowed,
                     work=work["full"]))


def _attend_prompt_pages(cfg: GPTConfig, pools, layer, tables, lengths,
                         starts, positions, slots=None, chunk=None,
                         names=("k_pages",)):
    """``attend`` for whole prompts that start at position 0: each row's keys
    and values go into the pages its table names, in cache layer ``layer`` of
    the carried pools, and the row attends to its own tokens as the pool's
    type holds them. No dense cache of every layer exists beside the pool.

    ``chunk`` = (pos, align) of :func:`_write_prompt_pages`: the rows are a
    chunk that starts at position ``pos``. It is written first, then the row
    attends over the places its table names up to the chunk's last, read
    back from the pages (:func:`_attend_table_rows`): the rows of its
    earlier chunks and its own, under the mask :func:`_masked_attention`
    applies. Plain attention only."""
    if cfg.attn_kind == "mla" and len(names) > 1:
        return _attend_prompt_kinds(cfg, dict(zip(names, pools)), names,
                                    layer, tables, lengths, starts,
                                    positions, slots, chunk)
    if chunk is not None and cfg.attn_kind != "mha":
        raise ValueError(
            f"a chunk of a prompt reads its earlier rows from pages under "
            f"attn_kind='mha' only, not {cfg.attn_kind!r}: such a prompt "
            f"takes forward_with_cache and write_prompt_kv")
    if cfg.attn_kind == "mla":
        def attend_latent(q, latent, kvb):
            rows = latent.transpose(0, 2, 1, 3).astype(pools[0].dtype)
            with jax.named_scope("kv_write"):
                written = _write_prompt_pages(pools, layer, (rows,), tables,
                                              lengths, starts)
            return _mla_attention(cfg, q, rows[:, 0], kvb,
                                  positions), written
        return attend_latent
    if cfg.attn_kind == "gqa":
        def attend_gqa(q, k_, v):
            rows = tuple(t.transpose(0, 2, 1, 3).astype(pools[0].dtype)
                         for t in (k_, v))
            with jax.named_scope("kv_write"):
                written = _write_prompt_rows(
                    pools, bool(cfg.attn_window), layer, rows, tables,
                    lengths, starts, slots)
            return _gqa_attention(cfg, q, *rows, positions), written
        return attend_gqa

    def attend(q, k_, v):
        dt, S = pools[0].dtype, q.shape[1]
        k_c = k_.transpose(0, 2, 1, 3).astype(dt)       # [F, H, S, Dh]
        v_c = v.transpose(0, 2, 1, 3).astype(dt)
        with jax.named_scope("kv_write"):
            written = _write_prompt_pages(pools, layer, (k_c, v_c), tables,
                                          lengths, starts, chunk)
        if chunk is not None:
            return _attend_table_rows(cfg, q, written, layer, tables,
                                      positions, chunk[0] + S), written
        return _masked_attention(cfg, q, k_c, v_c, positions), written
    return attend


def _attend_prompt_kinds(cfg: GPTConfig, named, names, layer, tables,
                         lengths, starts, positions, slots, chunk):
    """:func:`_attend_prompt_pages` for one kind of a model whose latent
    layers are of several (``cfg`` its :func:`kind_view`; ``named`` the
    carried arrays by their keys): whole prompts from position 0, or with
    ``chunk`` = (pos, align) a chunk of one whose earlier chunks lie in its
    pages and its slot's ring already. No row is expanded but a block's, and
    no dense cache exists beside the pool.

    A window layer attends over the ring's rows of the positions before the
    chunk and the chunk's own (:func:`_mla_window_attention`), read BEFORE
    the chunk's last rows overwrite the ring. A layer in pages writes the
    chunk's rows (and index keys) first, reads the table's rows back whole
    (one latent row a place: 22 MB at 17,408 places) and attends over them a
    block of keys at a time (:func:`_mla_table_attention`), each query of
    the chunk under its own selection where the kind selects."""
    pos0, align = (0, None) if chunk is None else chunk
    ring_key, index_key = RING_KEYS[0], INDEX_KEYS[0]

    def attend_latent(q, latent, kvb, index=None):
        dt = named["k_pages"].dtype
        rows = latent.transpose(0, 2, 1, 3).astype(dt)      # [F, 1, S, C]
        S = rows.shape[2]
        if cfg.attn_window:
            before = None
            if chunk is not None:
                with jax.named_scope("kv_read"):
                    before = _ring_rows_before(named[ring_key], layer,
                                               slots, pos0)
            attn = _mla_window_attention(cfg, q, before, rows[:, 0], kvb,
                                         positions, pos0)
            with jax.named_scope("kv_write"):
                named[ring_key], = _write_ring(
                    (named[ring_key],), layer, (rows,), slots, lengths,
                    pos0=None if chunk is None else pos0)
            return attn, tuple(named[k] for k in names)
        with jax.named_scope("kv_write"):
            named["k_pages"], = _write_prompt_pages(
                (named["k_pages"],), layer, (rows,), tables, lengths, starts,
                chunk)
            if index is not None:   # the index keys in their pool's type
                own_keys = index[1].astype(named[index_key].dtype)
                named[index_key], = _write_prompt_pages(
                    (named[index_key],), layer, (own_keys[:, None],), tables,
                    lengths, starts, chunk)
        carried = tuple(named[k] for k in names)
        if chunk is None:   # the rows are the prompts' own
            allowed = None
            if index is not None and S > cfg.index_topk:
                with jax.named_scope("index"):
                    allowed = _selected(
                        _index_scores(index[0], index[2], own_keys),
                        _seen_by(cfg, positions, jnp.arange(S)),
                        cfg.index_topk)
            return _mla_attention(cfg, q, rows[:, 0], kvb, positions,
                                  allowed=allowed), carried
        with jax.named_scope("kv_read"):
            cached = _table_rows(named["k_pages"], layer, tables)[:, 0]
        allowed, live = None, pos0 + S
        if index is not None and cached.shape[1] > cfg.index_topk:
            with jax.named_scope("index"):
                with jax.named_scope("kv_read"):
                    keys = _table_rows(named[index_key], layer, tables)[:, 0]
                allowed = _selected(
                    _index_table_scores(index[0], index[2], keys, live),
                    _seen_by(cfg, positions, jnp.arange(cached.shape[1])),
                    cfg.index_topk)
        return _mla_table_attention(cfg, q, cached, kvb, positions, live,
                                    allowed), carried
    return attend_latent


def _ring_rows_before(ring, layer, slots, pos):
    """The rows a slot's ring holds of the ``R`` positions before ``pos``,
    in position order: [F, R, C] of cache layer ``layer`` of a latent ring
    stack [L, 1, slots, R, C], row ``j`` position ``pos - R + j`` (a
    position below 0 names a row nothing reads)."""
    R = ring.shape[3]
    at = (pos - R + jnp.arange(R)) % R
    return ring[layer, 0, slots[:, None], at[None, :]]


# keys a chunk's scores are taken over at once where its rows are expanded a
# block at a time: 128 heads x 1024 queries x 512 keys of float32 scores are
# 268 MB
_LATENT_BLOCK = 512


def _expanded(cfg: GPTConfig, rows, kvb):
    """Cached latent rows [F, S, C] as a head's keys and values by ``W_kvb``:
    (k_nope [F, S, H, nope], v [F, S, H, v], k_rope [F, S, rope])."""
    r = cfg.kv_lora_rank
    w_k, w_v = _kvb_heads(cfg, kvb)
    return (jnp.einsum("fsr,rhn->fshn", rows[..., :r], w_k),
            jnp.einsum("fsr,rhv->fshv", rows[..., :r], w_v),
            rows[..., r:r + cfg.qk_rope_dim])


def _latent_scores(cfg: GPTConfig, q, k_nope, k_rope):
    """Float32 scores [F, H, T, S] of ``q`` [F, T, H, nope + rope] against
    expanded keys, scaled."""
    f32, nope = jnp.float32, cfg.qk_nope_dim
    return (jnp.einsum("fthn,fshn->fhts", q[..., :nope].astype(f32),
                       k_nope.astype(f32))
            + jnp.einsum("fthp,fsp->fhts", q[..., nope:].astype(f32),
                         k_rope.astype(f32))) * _softmax_scale(cfg)


def _mla_window_attention(cfg: GPTConfig, q, before, own, kvb, positions,
                          pos0):
    """Attention of a window kind's chunk ``q`` [F, T, H, nope + rope] at
    ``positions`` [F, T] (``pos0 ..``) over the rows of the positions before
    it ``before`` [F, R, C] (None: the chunk starts its prompt) and its own
    ``own`` [F, T, C]: the rows expanded once, then a block of queries at a
    time against the static slice of keys its window reaches. Float32
    scores, probabilities rounded to the rows' type; [F, T, H, v]."""
    keys = own if before is None else jnp.concatenate([before, own], axis=1)
    ahead = keys.shape[1] - own.shape[1]
    T, W = own.shape[1], cfg.attn_window
    k_nope, v, k_rope = _expanded(cfg, keys, kvb)
    key_pos = pos0 - ahead + jnp.arange(keys.shape[1])
    block = math.gcd(T, _LATENT_BLOCK)
    out = []
    for i in range(T // block):
        lo, hi = max(0, ahead + i * block - (W - 1)), ahead + (i + 1) * block
        at = slice(i * block, (i + 1) * block)
        s = _latent_scores(cfg, q[:, at], k_nope[:, lo:hi], k_rope[:, lo:hi])
        seen = _seen_by(cfg, positions[:, at], key_pos[lo:hi])[:, None]
        p = jax.nn.softmax(jnp.where(seen, s, jnp.float32(-1e30)), axis=-1)
        out.append(jnp.einsum("fhts,fshv->fthv", p.astype(v.dtype),
                              v[:, lo:hi]))
    return jnp.concatenate(out, axis=1).astype(own.dtype)


def _index_table_scores(q, weights, keys, live):
    """:func:`_index_scores` of a chunk's queries over the index keys a
    table names ``keys`` [F, S, Di], a block of keys at a time up to
    ``live`` (traced); ``-inf`` past it. [F, T, S]."""
    F, T = q.shape[:2]
    S = keys.shape[1]
    block = math.gcd(S, 2 * _LATENT_BLOCK)

    def body(j, scores):
        keys_j = jax.lax.dynamic_slice_in_dim(keys, j * block, block, 1)
        return jax.lax.dynamic_update_slice_in_dim(
            scores, _index_scores(q, weights, keys_j), j * block, 2)

    return jax.lax.fori_loop(
        0, -(-jnp.asarray(live, jnp.int32) // block), body,
        jnp.full((F, T, S), -jnp.inf, jnp.float32))


def _mla_table_attention(cfg: GPTConfig, q, rows, kvb, positions, live,
                         allowed=None):
    """Attention of a chunk ``q`` [F, T, H, nope + rope] at ``positions``
    over cached rows ``rows`` [F, S, C] whose place is their position, a
    block of keys at a time up to ``live`` (traced) under a running softmax:
    a block's rows are expanded by ``W_kvb`` (never all: at 16k rows and 128
    heads that is 2.1 GB of float32 a layer), ``allowed`` [F, T, S] says
    which rows a query's selection admits (None: every one at or before
    it). Float32 scores, probabilities rounded to the rows' type;
    [F, T, H, v]."""
    F, T, H, _ = q.shape
    S = rows.shape[1]
    block = math.gcd(S, _LATENT_BLOCK)
    if F == 1 and (cfg.use_flash is True or (
            cfg.use_flash is None and jax.default_backend() == "tpu")):
        return _mla_table_attention_kernel(cfg, q, rows, kvb, positions,
                                           live, allowed)

    def body(j, carry):
        m, l, acc = carry
        k_nope, v, k_rope = _expanded(cfg, jax.lax.dynamic_slice_in_dim(
            rows, j * block, block, 1), kvb)
        s = _latent_scores(cfg, q, k_nope, k_rope)
        seen = (_seen_by(cfg, positions, j * block + jnp.arange(block))
                if allowed is None else jax.lax.dynamic_slice_in_dim(
                    allowed, j * block, block, 2))[:, None]
        s = jnp.where(seen, s, jnp.float32(-1e30))
        m_new = jnp.maximum(m, s.max(axis=-1))
        alpha = jnp.exp(m - m_new)
        # a block may hold no row a query's selection admits
        p = jnp.where(seen, jnp.exp(s - m_new[..., None]), 0.0)
        acc = acc * alpha[..., None] + jnp.einsum(
            "fhts,fshv->fhtv", p.astype(v.dtype), v,
            preferred_element_type=jnp.float32)
        return m_new, alpha * l + p.sum(axis=-1), acc

    lead = (F, H, T)
    _, l, acc = jax.lax.fori_loop(
        0, -(-jnp.asarray(live, jnp.int32) // block), body,
        (jnp.full(lead, -1e30, jnp.float32), jnp.zeros(lead, jnp.float32),
         jnp.zeros(lead + (cfg.v_head_dim,), jnp.float32)))
    return (acc / l[..., None]).astype(rows.dtype).transpose(0, 2, 1, 3)


def _mla_table_attention_kernel(cfg: GPTConfig, q, rows, kvb, positions,
                                live, allowed):
    """:func:`_mla_table_attention` of ONE request's chunk on the TPU: the
    live rows' keys and values expanded once, a block at a time, into [H, S,
    nope] and [H, S, v] (the rotated key stays the one row it is), then
    ``ops/pallas/chunk_attention.masked_chunk_attention``, whose score tiles
    never leave VMEM. The plain form's blocks of scores are [H, T, 512] of
    float32 in HBM, written once and read three times: at 128 heads and a
    chunk of 1024 over 16k rows 34 GB, 2.4 s of a 16k prompt's admission
    (my chip run, PERF.md PR 51)."""
    from ..ops.pallas.chunk_attention import masked_chunk_attention

    _, T, H, _ = q.shape
    S, r, nope = rows.shape[1], cfg.kv_lora_rank, cfg.qk_nope_dim
    block = math.gcd(S, _LATENT_BLOCK)
    w_k, w_v = _kvb_heads(cfg, kvb)
    latent = rows[0, :, :r]

    def body(j, kv):
        rows_j = jax.lax.dynamic_slice_in_dim(latent, j * block, block, 0)
        return tuple(jax.lax.dynamic_update_slice_in_dim(
            a, jnp.einsum("sr,rhn->hsn", rows_j, w).astype(a.dtype),
            j * block, 1) for a, w in zip(kv, (w_k, w_v)))

    with jax.named_scope("mla_expand"):
        k_nope, v = jax.lax.fori_loop(
            0, -(-jnp.asarray(live, jnp.int32) // block), body,
            (jnp.zeros((H, S, nope), rows.dtype),
             jnp.zeros((H, S, cfg.v_head_dim), rows.dtype)))
    if allowed is None:
        allowed = _seen_by(cfg, positions, jnp.arange(S))
    heads = q[0].transpose(1, 0, 2)                         # [H, T, n + r]
    out = masked_chunk_attention(
        heads[..., :nope], k_nope, v, allowed[0], live, _softmax_scale(cfg),
        shared=(heads[..., nope:], rows[0, :, r:r + cfg.qk_rope_dim]),
        impl="kernel")
    return out.transpose(1, 0, 2)[None].astype(rows.dtype)


# places of a block table a prompt's chunk scores at once. A chunk is a
# prefill_chunk of queries, so a block is cheap to hold whatever its size;
# what it costs is the places past the chunk's last position that ride in its
# last block: on a v5e, pythia-1.4b's chunks of 128 over tables of 2,048 read
# out_tok_s 2,421 with the table read whole, 2,592 with blocks of 512 and
# 2,634 with 256 (PERF.md section 6, PR 39)
_PAGE_BLOCK = 256


def _attend_table_rows(cfg: GPTConfig, q, pools, layer, tables, positions,
                       live):
    """:func:`_masked_attention` of ``q`` [F, T, H, Dh] at absolute
    ``positions`` [F, T] over the places the tables name in cache layer
    ``layer`` of ``pools`` = (k_pages, v_pages), place = position. ``live``
    (traced): only places below it can matter. A table of at most two blocks
    of ``_PAGE_BLOCK`` places is read whole; a wider one a block of pages at a
    time up to ``live``, under a running softmax as :func:`_gqa_attention`
    keeps it, so that what a request has not filled is neither read nor
    scored: float32 scores, probabilities rounded to the values' type,
    [F, T, H, Dh] in the values' type."""
    ps, W = pools[0].shape[3], tables.shape[1]
    pages = math.gcd(W, max(1, _PAGE_BLOCK // ps))      # pages a block
    if W <= 2 * pages:
        with jax.named_scope("kv_read"):
            k_c, v_c = (_table_rows(pool, layer, tables) for pool in pools)
        return _masked_attention(cfg, q, k_c, v_c, positions)
    F, T, H, Dh = q.shape
    block = pages * ps
    qf = q.astype(jnp.float32)
    scale = _softmax_scale(cfg)
    t_idx = positions[:, None, :, None]                      # [F, 1, T, 1]

    def body(j, carry):
        m, l, acc = carry
        with jax.named_scope("kv_read"):
            k_j, v_j = (_table_rows(pool, layer, jax.lax.dynamic_slice_in_dim(
                tables, j * pages, pages, 1)) for pool in pools)
        s = jnp.einsum("fthd,fhsd->fhts", qf,
                       k_j.astype(jnp.float32)) * scale
        s = jnp.where(j * block + jnp.arange(block) <= t_idx, s,
                      jnp.float32(-1e30))
        m_new = jnp.maximum(m, s.max(axis=-1))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[..., None])
        acc = acc * alpha[..., None] + jnp.einsum(
            "fhts,fhsd->fhtd", p.astype(v_j.dtype), v_j,
            preferred_element_type=jnp.float32)
        return m_new, alpha * l + p.sum(axis=-1), acc

    lead = (F, H, T)
    _, l, acc = jax.lax.fori_loop(
        0, -(-jnp.asarray(live, jnp.int32) // block), body,
        (jnp.full(lead, -1e30, jnp.float32), jnp.zeros(lead, jnp.float32),
         jnp.zeros(lead + (Dh,), jnp.float32)))
    return (acc / l[..., None]).astype(pools[0].dtype).transpose(0, 2, 1, 3)


def _table_rows(pool, layer, tables):
    """Every place the tables name in cache layer ``layer`` of a dense pool
    [L, H, P, ps, Dh], in position order: [F, H, table width x ps, Dh].
    Layer, head and page all named, as :func:`_write_prompt_pages` names
    them."""
    H, ps, Dh = pool.shape[1], pool.shape[3], pool.shape[4]
    F, W = tables.shape
    rows = pool[layer, jnp.arange(H)[None, :, None], tables[:, None, :]]
    return rows.reshape(F, H, W * ps, Dh)


def _write_prompt_pages(pools, layer, rows, tables, lengths, starts,
                        chunk=None):
    """Write F prompt rows' keys and values ``rows`` ([F, H, S, Dh] each,
    position = place) into cache layer ``layer`` of the dense pool stacks
    ``pools`` ([L, H, P, ps, Dh] each), a page's worth at a time: piece ``j``
    of row ``f`` (gcd(S, ps) positions, so it never straddles a page) is one
    [piece, Dh] block a head at ``[layer, h, tables[f, page], offset]``,
    read, merged and written back where it lies. Positions at or past a row's
    length, or below its start (pages it only borrows), keep what the pool
    held; a piece with none to write (padding, an empty row, a slot past the
    table) names page P and is dropped. THE writer of prompts into dense
    pools: :func:`paged_prefill_step` calls it from its layer loop as each
    layer computes its rows, :func:`write_prompt_kv_batch` from a loop over
    the cache layers of a dense cache already filled.

    Every index is explicit, the head too (:func:`_token_rows` says why: with
    the heads in the window the TPU compiler lays the whole stack out
    head-minor and copies it, 3.8 GB twice at Ouro's pool, compile-only). And
    whole blocks, not one scatter of F x H x S rows of Dh: the TPU writes a
    scattered window at a time, and a prompt batch has hundreds of thousands
    of rows (``pythia-1.4b-serve.batch-decode`` fell from 535 to 336
    tokens/s with the rows scattered: my chip run, PR 32).

    ``chunk`` = (pos, align): ``rows`` are a chunk of the prompts, place
    ``s`` holding position ``pos + s``, ``pos`` a traced multiple of
    ``align``; a piece is then gcd(S, ps, align) positions, and ``lengths``
    and ``starts`` still count from position 0."""
    F, H, S, Dh = rows[0].shape
    L, _, P, ps, _ = pools[0].shape
    width = math.gcd(S, ps) if chunk is None else math.gcd(S, ps, chunk[1])
    pieces = S // width
    at = jnp.arange(pieces) * width                                # [pieces]
    if chunk is not None:
        at = at + chunk[0]
    pos = at[:, None] + jnp.arange(width)[None, :]          # [pieces, width]
    valid = ((pos[None] >= starts[:, None, None])
             & (pos[None] < lengths[:, None, None]))     # [F, pieces, width]
    page = jnp.take_along_axis(tables, (at // ps)[None, :], axis=1)
    page = jnp.where(valid.any(-1), page, P)                   # [F, pieces]
    where = (layer, jnp.arange(H)[None, None, :], page[:, :, None],
             ((at % ps) // width)[None, :, None])
    out = []
    for pool, side in zip(pools, rows):
        new = side.reshape(F, H, pieces, width, Dh).transpose(0, 2, 1, 3, 4)
        blocks = pool.reshape(L, H, P, ps // width, width, Dh)
        old = blocks.at[where].get(mode="fill", fill_value=0)
        merged = jnp.where(valid[:, :, None, :, None], new, old)
        out.append(blocks.at[where].set(merged, mode="drop")
                   .reshape(pool.shape))
    return tuple(out)


def _write_prompt_rows(pools, ring: bool, layer, rows, tables, lengths,
                       starts, slots):
    """Prompt rows into cache layer ``layer`` of ``pools`` = (k_pages,
    v_pages[, k_ring, v_ring]): the slots' rings where the layer is a window
    layer (``ring``), else the pages."""
    if ring:
        return pools[:-2] + _write_ring(pools[-2:], layer, rows, slots,
                                        lengths)
    return _write_prompt_pages(pools[:2], layer, rows, tables, lengths,
                               starts) + pools[2:]


def _write_ring(rings, layer, rows, slots, lengths, pos0=None):
    """Write F prompt rows' keys and values ``rows`` ([F, H, S, Dh] each,
    position = place) into cache layer ``layer`` of the ring stacks ``rings``
    ([L, H, slots, R, Dh] each): row ``f``'s last ``R`` positions under
    ``lengths[f]`` go to the ring of decode slot ``slots[f]``, position ``t``
    at ring row ``t mod R``. A ring row that no position of a shorter prompt
    reaches gets row 0's and is never read: a step reads the rows whose
    position lies under the length. A row of length 0 names no slot and is
    dropped. Every index explicit, as :func:`_token_rows`.

    ``pos0`` (traced): ``rows`` are a chunk, place ``s`` holding position
    ``pos0 + s``; a ring row whose newest position under the length lies
    before the chunk keeps what an earlier chunk wrote there."""
    F, H, S, _ = rows[0].shape
    n_slots, R = rings[0].shape[2:4]
    r = jnp.arange(R)
    if pos0 is not None:
        last = jnp.minimum(lengths, pos0 + S)[:, None] - 1
        held = last - (last - r[None, :]) % R - pos0               # [F, R]
        fresh = (held >= 0)[:, None, :, None]
        held = jnp.clip(held, 0, S - 1)
    else:
        last = lengths[:, None] - 1
        held = jnp.clip(last - (last - r[None, :]) % R, 0, S - 1)  # [F, R]
    slot = jnp.where(lengths > 0, slots, n_slots)
    where = (layer, jnp.arange(H)[None, :, None], slot[:, None, None],
             r[None, None, :])
    if pos0 is not None:
        return tuple(
            ring.at[where].set(jnp.where(
                fresh, jnp.take_along_axis(side, held[:, None, :, None],
                                           axis=2),
                ring.at[where].get(mode="fill", fill_value=0)), mode="drop")
            for ring, side in zip(rings, rows))
    return tuple(
        ring.at[where].set(jnp.take_along_axis(
            side, held[:, None, :, None], axis=2), mode="drop")
        for ring, side in zip(rings, rows))


def _ring_append(ring, layer, row, lengths):
    """One new token a decode slot into cache layer ``layer`` of a ring
    stack [L, H, slots, R, Dh]: slot ``b``'s ``row[b]`` [H, Dh] at ring row
    ``lengths[b] mod R``. A slot that holds no request (length 0) keeps what
    it had: a ring has no sink row, and a prompt may already lie there."""
    B, H, _ = row.shape
    if B != ring.shape[2]:
        raise ValueError(f"a ring holds {ring.shape[2]} decode slots, the "
                         f"step has {B} rows")
    at = (layer, jnp.arange(H)[None, :], jnp.arange(B)[:, None],
          (lengths % ring.shape[3])[:, None])
    keep = (lengths > 0)[:, None, None]
    return ring.at[at].set(jnp.where(keep, row.astype(ring.dtype), ring[at]))


def gqa_pages_per_step(cfg: GPTConfig, page_size: int, pages_per_seq: int,
                       dtype) -> int:
    """Pages of a request a grid step of ``paged_decode_gqa`` takes over
    block tables ``pages_per_seq`` wide, at the config's heads and its cache
    of ``dtype`` (``decode_attention.gqa_pages_per_step``: what
    :func:`gqa_work` groups its list by); 0 for a config without key-value
    heads or without a layer in pages."""
    from ..ops.pallas.decode_attention import gqa_pages_per_step as pages

    if cfg.attn_kind != "gqa" or not paged_layers(cfg)[0]:
        return 0
    return pages(cfg.n_kv_head, page_size, cfg.head_dim,
                 cache_dtype(cfg, dtype), pages_per_seq, False)


def gqa_work(cfg: GPTConfig, paged_cache, tables, lengths):
    """The work lists of a decode step over pages and rings, built once a
    step for every layer (:func:`paged_work`), each in groups of as many
    pages as a grid step of ``paged_decode_gqa`` takes at these shapes
    (``decode_attention.gqa_pages_per_step``, which the kernel asks too):
    ``full`` over the block tables; ``ring`` (None without window layers) the
    table that reads slot ``b``'s ring as pages ``b R / ps ..`` of a pool
    [.., slots R / ps, ps, Dh] and the list over the ring's live rows, at
    most ``R`` a slot."""
    from ..ops.pallas.decode_attention import (gqa_pages_per_step,
                                               paged_work_list)

    pool = paged_cache["k_pages"]
    G, _, ps, Dh = pool.shape[1:]
    lens = lengths + 1

    def listed(lens, tables, ring):
        return paged_work_list(lens, tables, ps, gqa_pages_per_step(
            G, ps, Dh, pool.dtype, tables.shape[1], ring))

    work = {"full": listed(lens, tables, False), "ring": None}
    if RING_KEYS[0] in paged_cache:
        n_slots, R = paged_cache[RING_KEYS[0]].shape[2:4]
        ring_tables = _ring_tables(n_slots, R, ps)
        work["ring"] = (ring_tables, listed(
            jnp.minimum(lens, R), ring_tables, True)._replace(lens=lens))
    return work


def _ring_tables(n_slots: int, ring_rows: int, page_size: int):
    """The table that reads slot ``b``'s ring of ``ring_rows`` rows as pages
    ``b R / ps ..`` of a pool [.., slots R / ps, ps, width]."""
    per = ring_rows // page_size
    return (jnp.arange(n_slots, dtype=jnp.int32)[:, None] * per
            + jnp.arange(per, dtype=jnp.int32))


def mla_pages_per_step(cfg: GPTConfig, page_size: int, pages_per_seq: int,
                       dtype) -> int:
    """Pages of a request a grid step of ``paged_decode_mla`` takes over
    block tables ``pages_per_seq`` wide, at the width of the config's latent
    rows in pages and its cache of ``dtype``
    (``decode_attention.mla_pages_per_step``: what :func:`mla_work` groups
    its list by); 0 for a config without latent attention or without a layer
    in pages."""
    from ..ops.pallas.decode_attention import mla_pages_per_step as pages

    if cfg.attn_kind != "mla" or not paged_layers(cfg)[0]:
        return 0
    return pages(page_size, cache_row(cfg)[2], cache_dtype(cfg, dtype),
                 pages_per_seq, False)


def mla_work(paged_cache, tables, lengths):
    """:func:`gqa_work` for latent layers: the live groups of pages a decode
    step's ``paged_decode_mla`` calls walk, built once a step for every
    layer of a kind, each in groups of as many pages as a grid step takes
    at its shapes (``decode_attention.mla_pages_per_step``, which the kernel
    asks too): ``full`` over the block tables (under a selection too: the
    list names pages, the mask rows); ``ring`` (None without window layers)
    the ring's table and the list over its live rows, at most ``R`` a
    slot."""
    from ..ops.pallas.decode_attention import (mla_pages_per_step,
                                               paged_work_list)

    lens = lengths + 1
    pool = paged_cache["k_pages"]
    ps = pool.shape[-2]

    def listed(pool, lens, tables, ring):   # a ring is read as pages of ps
        return paged_work_list(lens, tables, ps, mla_pages_per_step(
            ps, pool.shape[-1], pool.dtype, tables.shape[1], ring))

    work = {"full": listed(pool, lens, tables, False), "ring": None}
    if RING_KEYS[0] in paged_cache:
        rings = paged_cache[RING_KEYS[0]]
        n_slots, R = rings.shape[2:4]
        ring_tables = _ring_tables(n_slots, R, ps)
        work["ring"] = (ring_tables, listed(
            rings, jnp.minimum(lens, R), ring_tables, True)._replace(
                lens=lens))
    return work


def append_and_attend_gqa(cfg: GPTConfig, pools, layer, q, k_, v, tables,
                          lengths, work, impl=None):
    """:func:`append_and_attend` with fewer key-value heads, for one kind of
    layer (``cfg`` its :func:`kind_view`), ``layer`` counted among the cache
    layers of its kind: a layer without a window appends to its tail page
    and attends over its pages; a window layer appends to its slot's ring
    and attends over the ring's rows that lie inside the window, the ring
    read as the slot's pages. Both through
    ``ops/pallas/decode_attention.paged_decode_gqa``: a key-value head's page
    against its group of queries. ``pools``: (k_pages, v_pages[, k_ring,
    v_ring]); ``work``: :func:`gqa_work`. Returns (attn [B, 1, H, Dh],
    pools)."""
    from ..ops.pallas.decode_attention import paged_decode_gqa

    ps = pools[0].shape[3]
    # a float32 query (``stream_float32``) meets bf16 rows in two passes
    # inside the kernel, as the latent kernel's does
    qk = q if q.dtype == jnp.float32 else q.astype(pools[0].dtype)
    if cfg.attn_window:
        with jax.named_scope("kv_write"):
            rings = tuple(_ring_append(ring, layer, t[:, 0], lengths)
                          for ring, t in zip(pools[-2:], (k_, v)))
        L, G, n_slots, R, Dh = rings[0].shape
        ring_tables, ring_work = work["ring"]
        attn = paged_decode_gqa(
            qk, *(a.reshape(L, G, n_slots * (R // ps), ps, Dh)
                  for a in rings), lengths + 1, ring_tables,
            softmax_scale=_softmax_scale(cfg), impl=impl, layer=layer,
            work=ring_work, ring=(R, cfg.attn_window), out_dtype=q.dtype)
        return attn, pools[:-2] + rings
    page = jnp.take_along_axis(tables, (lengths // ps)[:, None],
                               axis=1)[:, 0]
    with jax.named_scope("kv_write"):
        at = _token_rows(layer, pools[0].shape[1], page, lengths % ps)
        pages = tuple(pool.at[at].set(t[:, 0].astype(pool.dtype))
                      for pool, t in zip(pools[:2], (k_, v)))
    attn = paged_decode_gqa(qk, *pages, lengths + 1, tables,
                            softmax_scale=_softmax_scale(cfg), impl=impl,
                            layer=layer, work=work["full"],
                            out_dtype=q.dtype)
    return attn, pages + pools[2:]


def _pool_passes(cfg: GPTConfig, params, x, paged_cache, positions,
                 attend_at, mix_at=None):
    """The passes of a forward that carries the page pool: every block over
    ``attend_at(the layer's kind_view, pools, cache layer)`` and, where it
    has a state-space mixer, ``mix_at(pools, state layer)``, the cache layer
    counted among those of the run's cache kind (pages or rings:
    :meth:`LayerRun.cache_layer`), the state layer among the states
    (:meth:`LayerRun.state_layer`); the pool handed from layer to layer,
    stack to stack and pass to pass, past a layer without a mixer. A layer
    with both writes its pages and its state, which are different arrays of
    the carry.
    Returns the stream after the final norm, the new paged cache, the marks
    of :func:`_passes` and the experts the routed layers chose, [routed
    layers, B, T, k] (None without any)."""
    def one_pass(x, pools, u, _):
        def step(run, x, pools, layer_w, i, _):
            kcfg = kind_view(cfg, run.kind)
            x, carried, chosen = _block_on(
                kcfg, x, layer_w, positions,
                attend_at(kcfg, pools, run.cache_layer(i, u))
                if run.attends else None,
                mix=mix_at(pools, run.state_layer(i)) if run.mixes else None,
                mixer=run.mixer, ffn=run.ffn)
            if run.attends and run.mixes:   # pages of the one, states of
                carried = carried[0][:-2] + carried[1][-2:]     # the other
            return x, carried if run.mixer else pools, (None, chosen)

        with jax.named_scope("blocks"):
            x, pools, _, chosen, marks = _scan_stacks(cfg, params, x, pools,
                                                      step)
        return x, pools, chosen, marks

    x, pools, chosen, marks = _passes(cfg, params, x,
                                      paged_pools(paged_cache), one_pass)
    return x, _as_cache(paged_cache, pools), marks, chosen


def routing_of(cfg: GPTConfig, chosen, active):
    """What a serving step says of its routed layers, from the experts they
    chose ``chosen`` [routed layers, B, 1, k] and which rows hold a request
    ``active`` [B]: ``chosen`` as int32 [B, n_layer, k] (a dense layer's row
    is -1), and the counts [4] of the active rows' assignments: all of them,
    those that met an expert held here, the held experts that met any (summed
    over the layers) and the most one held expert met in one layer."""
    first, count = cfg.held_experts
    chosen = chosen[:, :, 0].transpose(1, 0, 2)             # [B, routed, k]
    local = chosen - first
    mine = (local >= 0) & (local < count) & active[:, None, None]
    by_layer = jnp.where(mine, local, count).transpose(1, 0, 2).reshape(
        chosen.shape[1], -1)
    met = jax.vmap(lambda e: jnp.zeros((count + 1,), jnp.int32).at[e].add(
        1))(by_layer)[:, :count]                            # [routed, count]
    counts = jnp.stack([active.sum() * chosen.shape[1] * chosen.shape[2],
                        mine.sum(), (met > 0).sum(), met.max()])
    routed = np.concatenate([np.arange(r.first, r.first + r.count)
                             for r in layer_runs(cfg) if r.ffn == "routed"])
    if routed[0] + len(routed) != cfg.n_layer:  # other layers among them
        by_layer = jnp.full((chosen.shape[0], cfg.n_layer, chosen.shape[2]),
                            -1, jnp.int32).at[:, routed].set(
                                chosen.astype(jnp.int32))
        return by_layer, counts.astype(jnp.int32)
    dense = jnp.full((chosen.shape[0], cfg.n_layer - chosen.shape[1],
                      chosen.shape[2]), -1, jnp.int32)
    return (jnp.concatenate([dense, chosen.astype(jnp.int32)], axis=1),
            counts.astype(jnp.int32))


def paged_decode_step(cfg: GPTConfig, params, input_ids: jnp.ndarray,
                      paged_cache: Dict[str, jnp.ndarray],
                      block_tables: jnp.ndarray, lengths: jnp.ndarray,
                      impl: Optional[str] = None,
                      return_states: bool = False,
                      return_routing: bool = False):
    """One decode step over the paged cache: ``input_ids`` [B] (or [B, 1]) new
    tokens, one per slot, each appended at its row's own ``lengths[b]``.
    Returns (logits [B, V], new paged_cache) and, with ``return_states``,
    the new tokens' :func:`_states` [B, boundaries, D] third; with
    ``return_routing``, last, :func:`routing_of` of a model that routes
    (rows of length 0 hold no request), None of one that does not.

    The continuous-batching hot path: B is the FIXED decode slot count, so
    one compiled program serves every step regardless of which requests
    occupy the slots; inactive slots (lengths 0, table row all page-0) write
    to the reserved sink page and produce ignored logits. Supports the dense
    and the quantized ({"q"/"q4","s"}) layer stacks like
    :func:`forward_with_cache`, and dense OR quantized KV pools
    (``init_paged_cache(kv_bits=...)`` — recognized by the scale stacks);
    alibi/local-attention configs are not yet paged.

    How the pool flows: the whole stacks [L, H, P, ps, Dh] are a CARRY of
    the layer loop, and of the loop over the passes where the stack runs
    more than once, never a scanned input or stacked output. Layer ``i`` of
    pass ``u`` scatters its B new tokens into ``pool[n_layer * u + i]`` and
    the kernel reads that cache layer's pages through the block table, both
    addressed inside the carried array (:func:`append_and_attend`); the
    weights are the layer scan's input once a pass, never stacked per pass.
    A caller that donates the cache (the serving engine's decode programs
    do) gets a step that holds one pool and moves none of it; one that does
    not pays one copy of it."""
    if cfg.alibi or cfg.local_attention_period > 1:
        raise ValueError("paged decode does not support alibi/local-window "
                         "attention yet (the paged kernel has no bias input)")
    ids = jnp.asarray(input_ids)
    if ids.ndim == 1:
        ids = ids[:, None]
    lengths = jnp.asarray(lengths, jnp.int32)
    positions = lengths[:, None]    # [B, 1] — each row at its OWN position
    x0 = _embed(cfg, params, ids, positions)
    x0 = (x0.astype(jnp.float32) if cfg.stream_float32
          else _compute_input(cfg, params, x0))
    work = (mla_work(paged_cache, block_tables, lengths)
            if cfg.attn_kind == "mla"
            else gqa_work(cfg, paged_cache, block_tables, lengths)
            if cfg.attn_kind == "gqa"
            else paged_work(paged_cache, block_tables, lengths))
    mix_at = None
    if cfg.ssm is not None:     # rows of length 0 hold no request
        from ..ops.pallas.ssm_decode import live_slots

        active = lengths > 0
        live = live_slots(active)

        def mix_at(pools, layer):
            return _mix_decode_slots(cfg, pools, layer, active, impl, live)
    x, new_cache, marks, chosen = _pool_passes(
        cfg, params, maybe_shard(x0, P(BATCH, None, None)), paged_cache,
        positions, lambda kcfg, pools, layer: _attend_pages(
            kcfg, pools, layer, block_tables, lengths, impl, x0.dtype, work,
            _pool_names(paged_cache)),
        mix_at)
    head = params["wte"] if cfg.tie_embeddings else params["lm_head"]
    if _meets_bf16(x, head):    # a float32 stream: float32 logits, two passes
        with jax.named_scope("head_loss"):
            logits = _times(cfg, _two_pass(x, lambda a: jnp.einsum(
                "btd,vd->btv", a, head, preferred_element_type=jnp.float32),
                rows_axis=1), "head")
    else:
        logits = _head(cfg, params, x)
    out = (logits[:, 0, :], new_cache)
    if return_states:
        out += (_states(cfg, x0, marks)[:, :, 0],)
    if return_routing:
        out += (None if chosen is None
                else routing_of(cfg, chosen, lengths > 0),)
    return out


def paged_prefill_step(cfg: GPTConfig, params, input_ids: jnp.ndarray,
                       paged_cache: Dict[str, jnp.ndarray],
                       block_tables: jnp.ndarray, lengths: jnp.ndarray,
                       starts: jnp.ndarray, slots=None, chunk=None):
    """Whole prompts straight into pages: ``input_ids`` [F, S], row ``f``
    holding ``lengths[f]`` real tokens from position 0 (the rest padding; a
    row of length 0 writes nothing), each row's keys and values scattered
    into the pages ``block_tables[f]`` names as its layer computes them, the
    pool carried through the layers and the passes as a decode step carries
    it. ``starts[f]`` skips the positions below it (pages the row only
    borrows). ``slots[f]``: the decode slot row ``f``'s request will hold,
    for a cache whose window layers keep a ring a slot. Returns (logits
    [F, V] of each row's last real token, new paged_cache, :func:`_states`
    [F, boundaries, S, D]).

    ``chunk`` = (pos, align): ``input_ids`` are the tokens at positions
    ``pos .. pos + S`` of prompts whose earlier chunks are in their pages
    already (``pos`` a traced multiple of ``align``, so one program serves
    every chunk of a prompt; ``lengths`` still the whole prompts'). Each
    layer writes the chunk's rows and reads the earlier ones back from the
    pages (:func:`_attend_prompt_pages`), plain attention only: the
    positions below ``starts[f]`` too, which it never writes, so they are
    whole pages that hold those rows already (a borrowed prefix). The head
    runs where some row's last token lies in the chunk; the logits of a
    chunk that ends before are zeros.

    For prompts of at most one prefill chunk this replaces the dense cache
    of every layer and the scatter after it (:func:`forward_with_cache`,
    :func:`write_prompt_kv_batch`): at 192 cache layers that cache alone is
    2.0 GB for 10 rows of 128, beside a pool that fills the chip. Dense
    pools and dense weight stacks only."""
    if cfg.alibi or cfg.local_attention_period > 1:
        raise ValueError("paged prefill does not support alibi/local-window "
                         "attention (same bound as paged_decode_step)")
    if "k_scales" in paged_cache or _is_qleaf(
            _a_matrix(_stacks(cfg, params)[0][0])):
        raise ValueError("paged prefill writes dense pools from dense weight "
                         "stacks; quantized ones take forward_with_cache and "
                         "write_prompt_kv_batch")
    F, S = input_ids.shape
    lengths = jnp.asarray(lengths, jnp.int32)
    tables = jnp.asarray(block_tables, jnp.int32)
    starts = jnp.broadcast_to(jnp.asarray(starts, jnp.int32), (F,))
    positions = jnp.broadcast_to(jnp.arange(S)[None, :], (F, S))
    if chunk is not None:
        positions = positions + chunk[0]
    x0 = _embed(cfg, params, input_ids, positions)
    x0 = (x0.astype(jnp.float32) if cfg.stream_float32
          else _compute_input(cfg, params, x0))
    slots = None if slots is None else jnp.asarray(slots, jnp.int32)
    mix_at = None
    if cfg.ssm is not None:
        if chunk is not None:
            raise ValueError(
                "a chunk of a prompt carries a mixer's state through "
                "forward_with_cache(real=) and write_prompt_kv, not through "
                f"paged_prefill_step(chunk=): ssm={cfg.ssm!r}")

        def mix_at(pools, layer):
            return _mix_prompt_slots(cfg, pools, layer, lengths, slots)
    x, new_cache, marks, _ = _pool_passes(
        cfg, params, maybe_shard(x0, P(BATCH, None, None)), paged_cache,
        positions, lambda kcfg, pools, layer: _attend_prompt_pages(
            kcfg, pools, layer, tables, lengths, starts, positions, slots,
            chunk, _pool_names(paged_cache)), mix_at)

    def logits_at(last):
        return _head(cfg, params, _head_input(cfg, params, jnp.take_along_axis(
            x, last[:, None, None], axis=1)))[:, 0]

    if chunk is None:
        logits = logits_at(jnp.maximum(lengths - 1, 0))
    else:
        last = jnp.clip(lengths - 1 - chunk[0], 0, S - 1)
        out = jax.eval_shape(logits_at, last)
        logits = jax.lax.cond(jnp.any(lengths <= chunk[0] + S),
                              lambda: logits_at(last),
                              lambda: jnp.zeros(out.shape, out.dtype))
    return logits, new_cache, _states(cfg, x0, marks)


# ------------------------------------------------- speculative verification
@jax.named_scope("attn")
def _paged_verify_sublayer(cfg: GPTConfig, x, w, k_pages, v_pages, tables,
                           lengths, impl=None, k_scales=None, v_scales=None):
    """Cached self-attention over the page pool for a ``W``-token
    speculation window per row (pre-LN + residual). x: [B, W, D]; window
    position ``i`` sits at absolute position ``lengths[b] + i`` and attends
    pool history + the window's causal prefix (the window K/V stay DENSE —
    nothing is written to the pool; the accepted prefix commits later via
    :func:`commit_window_kv`). Returns (x + attn_out, win_k, win_v) with
    win_k/win_v [B, W, H, Dh] post-rope in the compute dtype — exactly the
    values a sequential decode step would have appended."""
    from ..ops.pallas.decode_attention import paged_verify_attention

    B, W, D = x.shape
    H, Dh = cfg.n_head, cfg.head_dim
    h = layer_norm(x, w["ln1_scale"], w["ln1_bias"], cfg.layer_norm_eps)
    qkv = _wm(h, w["qkv_w"]) + w["qkv_b"]
    q, k_, v = jnp.split(qkv, 3, axis=-1)
    q = q.reshape(B, W, H, Dh)
    k_ = k_.reshape(B, W, H, Dh)
    v = v.reshape(B, W, H, Dh)
    positions = lengths[:, None] + jnp.arange(W)[None, :]   # [B, W]
    if cfg.rotary:
        rd = int(cfg.rotary_pct * Dh)
        rd -= rd % 2
        q = _rope(q, positions, rd, cfg.rotary_interleaved)
        k_ = _rope(k_, positions, rd, cfg.rotary_interleaved)
    scale = (cfg.attention_scale if cfg.attention_scale is not None
             else 1.0 / np.sqrt(Dh))
    quantized = k_scales is not None
    qdt = x.dtype if quantized else k_pages.dtype
    attn = paged_verify_attention(q.astype(qdt), k_pages, v_pages, lengths,
                                  tables, k_, v, softmax_scale=scale,
                                  impl=impl, k_scales=k_scales,
                                  v_scales=v_scales)
    attn = attn.reshape(B, W, D).astype(x.dtype)
    attn = _wm(attn, w["attn_out_w"]) + w["attn_out_b"]
    return x + attn, k_, v


def paged_verify_step(cfg: GPTConfig, params, window_ids: jnp.ndarray,
                      paged_cache: Dict[str, jnp.ndarray],
                      block_tables: jnp.ndarray, lengths: jnp.ndarray,
                      impl: Optional[str] = None
                      ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Score a speculation window — ``window_ids`` [B, W] per slot: the
    verified next input token followed by up to W-1 drafted tokens — in ONE
    dispatch over the paged cache. Returns (logits [B, W, V], win_k, win_v)
    where win_k/win_v [L, B, W, H, Dh] are the window's per-layer post-rope
    K/V in the compute dtype.

    The weight-bound speculative-decoding bet: every weight matrix is read
    ONCE for W positions, where W sequential :func:`paged_decode_step`
    dispatches read it W times — verifying k drafted tokens costs barely
    more than one token. The pool is READ-ONLY here: window K/V stay dense
    so the rejected suffix needs no undo, and :func:`commit_window_kv`
    afterwards appends exactly the accepted prefix with sequential-append
    semantics (what spec-off decode would have written, to XLA
    reduction-tiling noise — argmax-stable, gated at
    greedy_match_rate == 1.0). One caveat: over QUANTIZED pools the window
    attends its own in-window context at dense precision while spec-off
    would read those positions int8/int4-round-tripped from the pool —
    spec-on == spec-off there is quantization-tolerance-gated (measured
    1.0 on the tested configs, same bar as the kv8 serving rows), not
    reduction-noise-exact like dense pools. Same model
    support matrix as :func:`paged_decode_step` (dense/quantized weight
    stacks, dense/int8/int4 KV pools; alibi/local attention rejected)."""
    if cfg.alibi or cfg.local_attention_period > 1:
        raise ValueError("paged verification does not support alibi/"
                         "local-window attention yet (same bound as "
                         "paged_decode_step)")
    require_default_block(cfg, "paged_verify_step (speculative verification)")
    ids = jnp.asarray(window_ids)
    B, W = ids.shape
    lengths = jnp.asarray(lengths, jnp.int32)
    positions = lengths[:, None] + jnp.arange(W)[None, :]
    x = _embed(cfg, params, ids, positions)
    qkv_w = params["blocks"]["qkv_w"]
    quantized = _is_qleaf(qkv_w)
    kv_q = "k_scales" in paged_cache
    compute_dtype = (params["lnf_scale"].dtype if quantized else qkv_w.dtype)
    x = x.astype(compute_dtype)
    x = maybe_shard(x, P(BATCH, None, None))
    blocks = params["blocks"]

    def one_block(x, layer_w, kv):
        k_p, v_p = kv[0], kv[1]
        k_s, v_s = (kv[2], kv[3]) if kv_q else (None, None)
        y, wk, wv = _paged_verify_sublayer(
            cfg, x, layer_w, k_p, v_p, block_tables, lengths, impl=impl,
            k_scales=k_s, v_scales=v_s)
        mlp_in = x if cfg.parallel_residual else y
        return y + _mlp_delta(cfg, mlp_in, layer_w), (wk, wv)

    kv_xs = ((paged_cache["k_pages"], paged_cache["v_pages"],
              paged_cache["k_scales"], paged_cache["v_scales"]) if kv_q
             else (paged_cache["k_pages"], paged_cache["v_pages"]))
    with jax.named_scope("blocks"):
        if quantized:
            def body(carry, layer_in):
                x, i = carry
                layer_w = jax.tree_util.tree_map(
                    lambda a: jax.lax.dynamic_index_in_dim(a, i, 0,
                                                           keepdims=False),
                    blocks)
                x, win = one_block(x, layer_w, layer_in)
                return (x, i + 1), win

            (x, _), (win_k, win_v) = jax.lax.scan(body, (x, jnp.int32(0)), kv_xs)
        else:
            def body(carry, layer_in):
                x, i = carry
                x, win = one_block(x, layer_in[0], layer_in[1:])
                return (x, i + 1), win

            (x, _), (win_k, win_v) = jax.lax.scan(
                body, (x, jnp.int32(0)), (blocks,) + kv_xs)
    logits = _lm_logits(cfg, params, x)
    return logits, win_k, win_v


@jax.named_scope("kv_write")
def commit_window_kv(paged_cache: Dict[str, jnp.ndarray],
                     win_k: jnp.ndarray,  # [L, B, W, H, Dh]
                     win_v: jnp.ndarray,
                     block_tables: jnp.ndarray,   # [B, pages_per_seq]
                     lengths: jnp.ndarray,        # [B]: pool tokens pre-window
                     n_commit: jnp.ndarray,       # [B]: accepted writes (0..W)
                     ) -> Dict[str, jnp.ndarray]:
    """Append each row's ACCEPTED window prefix — ``n_commit[b]`` tokens at
    positions ``lengths[b] .. lengths[b] + n_commit[b] - 1`` — into the
    paged pool, exactly as ``n_commit[b]`` sequential decode steps would
    have: one :func:`_append_kv_token` per window step, so quantized page
    scales keep the monotone-per-lifetime semantics (opening offsets
    re-establish, mid-page grows requantize) and the committed pool state
    reproduces the spec-off path's (payloads bitwise given the same
    values; see :func:`_append_kv_token` for the last-ULP scale caveat).
    Window positions past the accepted frontier are NEVER written (their
    rows redirect to the reserved sink page 0) — rejected-suffix rollback
    is the absence of a write, not an undo."""
    kv_q = "k_scales" in paged_cache
    ps = paged_cache["k_pages"].shape[3]
    L, B, W, H, Dh = win_k.shape
    if L != paged_cache["k_pages"].shape[0]:
        raise ValueError(
            f"commit_window_kv does not support ut_steps > 1: the window "
            f"holds {L} layers, the pool {paged_cache['k_pages'].shape[0]} "
            "cache layers (a layer of every pass)")
    tables = jnp.asarray(block_tables, jnp.int32)
    lengths = jnp.asarray(lengths, jnp.int32)
    n_commit = jnp.asarray(n_commit, jnp.int32)
    bits = paged_cache_bits(paged_cache, Dh)

    def layer_commit(layer_in):
        if kv_q:
            k_p, v_p, k_s, v_s, wk, wv = layer_in
        else:
            k_p, v_p, wk, wv = layer_in
            k_s = v_s = None

        def step(carry, i):
            k_p, v_p, k_s, v_s = carry
            pos = lengths + i
            write = i < n_commit
            pidx = jnp.clip(pos // ps, 0, tables.shape[1] - 1)
            page = jnp.where(
                write, jnp.take_along_axis(tables, pidx[:, None],
                                           axis=1)[:, 0], 0)
            off = pos % ps
            tok_k = wk[:, i].transpose(1, 0, 2)   # [H, B, Dh]
            tok_v = wv[:, i].transpose(1, 0, 2)
            if bits is None:
                dt = k_p.dtype
                k_p = k_p.at[:, page, off, :].set(tok_k.astype(dt))
                v_p = v_p.at[:, page, off, :].set(tok_v.astype(dt))
            else:
                k_p, k_s = _append_kv_token(k_p, k_s,
                                            tok_k.astype(jnp.float32),
                                            page, off, bits)
                v_p, v_s = _append_kv_token(v_p, v_s,
                                            tok_v.astype(jnp.float32),
                                            page, off, bits)
            return (k_p, v_p, k_s, v_s), None

        (k_p, v_p, k_s, v_s), _ = jax.lax.scan(
            step, (k_p, v_p, k_s, v_s), jnp.arange(W))
        return (k_p, v_p, k_s, v_s) if kv_q else (k_p, v_p)

    def body(_, layer_in):
        return None, layer_commit(layer_in)

    xs = ((paged_cache["k_pages"], paged_cache["v_pages"],
           paged_cache["k_scales"], paged_cache["v_scales"], win_k, win_v)
          if kv_q else
          (paged_cache["k_pages"], paged_cache["v_pages"], win_k, win_v))
    _, out = jax.lax.scan(body, None, xs)
    new_cache = {"k_pages": out[0], "v_pages": out[1]}
    if kv_q:
        new_cache["k_scales"] = out[2]
        new_cache["v_scales"] = out[3]
    return new_cache


def build(cfg_or_name) -> Tuple[Module, GPTConfig]:
    """Build a GPT :class:`Module` from a config or preset name."""
    cfg = PRESETS[cfg_or_name] if isinstance(cfg_or_name, str) else cfg_or_name

    def to_pipeline(num_stages: int, num_micro: int) -> Module:
        from . import gpt_pipe

        module, _ = gpt_pipe.build(cfg, num_stages, num_micro)
        return module

    def with_ltd_keep(keep: int, layer_ids) -> Module:
        return build(dataclasses.replace(
            cfg, random_ltd_keep=int(keep),
            random_ltd_layer_ids=tuple(layer_ids)))[0]

    return Module(
        init=functools.partial(init_params, cfg),
        apply=lambda params, batch, rngs=None, train=True, pld_theta=None:
            loss_fn(cfg, params, batch, rngs=rngs, train=train,
                    pld_theta=pld_theta),
        partition_specs=functools.partial(partition_specs, cfg),
        to_pipeline=to_pipeline,
        with_ltd_keep=with_ltd_keep,
        stream=lambda: GPTStream(cfg),
        gpt_config=cfg,
        grad_bucket_key="blocks",
    ), cfg


# (at the file's end: a line added above a call that a Pallas kernel's
# operands pass through shifts the locations its body carries,
# ``scripts/stablehlo_sums.py``)
def paged_pages_per_step(cfg: GPTConfig, page_size: int, pages_per_seq: int,
                         dtype, kv_bits=None, shards: int = 1) -> int:
    """Pages of a request a grid step of ``paged_decode`` takes over block
    tables ``pages_per_seq`` wide, at the heads one of ``shards`` tensor-
    parallel shards holds and a cache of ``dtype`` (quantized where
    ``kv_bits``): ``decode_attention.paged_pages_per_step``, what
    :func:`paged_work` groups its list by; 0 for a config whose layers read
    their pages through another kernel."""
    from ..ops.pallas.decode_attention import paged_pages_per_step as pages

    if cfg.attn_kind != "mha":
        return 0
    return pages(cfg.n_head // shards, page_size, cfg.head_dim,
                 jnp.int8 if kv_bits else cache_dtype(cfg, dtype),
                 pages_per_seq, bool(kv_bits))
