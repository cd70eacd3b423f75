"""Tensor-parallel serving programs: one ``ServingEngine`` replica spanning a
2-4 chip ``tp`` mesh.

The serving stack above the engine (scheduler, page allocator, speculation,
chaos machinery, fleet protocol) never sees the mesh: block tables, lengths
and token ids stay replicated host-level values, while the paged KV pools and
the weight stacks are sharded over attention heads / MLP features. This is
the AutoTP shape (reference ``module_inject/auto_tp.py``): column-split QKV +
row-split attention output, column-split MLP up + row-split MLP down, ONE
``psum`` per sublayer — attention and its out-projection partial-sum, MLP up
/act/down partial-sum — so a block costs two reduces (one fused reduce when
``parallel_residual`` folds both deltas into the same residual add).

Sharding layout (head-contiguous, so plain ``PartitionSpec``s do all the
work — the one host-side reshape is ``qkv_w [L,d,3d] -> [L,d,3,d]`` /
``qkv_b [L,3d] -> [L,3,d]`` so the fused QKV projection splits per-head
instead of across the q|k|v concat boundary):

====================  ======================  =========================
array                 shape                   spec
====================  ======================  =========================
qkv_w / qkv_b         [L,d,3,d] / [L,3,d]     P(..., "tp") (head cols)
attn_out_w            [L,d,d]                 P(None, "tp", None) (rows)
mlp_up_w / mlp_up_b   [L,d,f] / [L,f]         P(..., "tp") (cols)
mlp_down_w            [L,f,d]                 P(None, "tp", None) (rows)
k/v_pages             [L,H,P,ps,Dh]           P(None, "tp", ...) (heads)
k/v_scales            [L,H,P]                 P(None, "tp", None)
dense prefill cache   [L,B,H,S,Dh]            P(None, None, "tp", ...)
everything else       (ln/bias/embed/head)    replicated
====================  ======================  =========================

Attention is per-head independent (rope, pool append, paged attention), so
each shard runs the unmodified per-head math from ``models/gpt.py`` on its
local heads — the page-append/commit/scatter writers (`_append_kv_token`,
`commit_window_kv`, `write_prompt_kv_batch`) are reused VERBATIM inside
``shard_map`` (they read every extent from the sliced arrays, never from
``cfg.n_head``). Logits come out replicated (the lm head is replicated and
the final residual stream is post-psum identical on every shard), so argmax
/ acceptance logic needs no collective at all.

Collective-order discipline: every ``psum`` is issued UNCONDITIONALLY in the
block body — never under a ``lax.cond``/``while`` whose predicate could
diverge across shards (the quantized pool append's requantize ``cond`` is
collective-free, which is exactly why it is safe to reuse here). The dslint
rule ``serving/tp-collective-order`` (analysis/rules_collectives.py) checks
captured tp programs for violations of this invariant.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ...models import gpt as gpt_mod
from .model import ServedModel

TP_AXIS = "tp"


# ------------------------------------------------------------------ context
class TPContext(ServedModel):
    """A tensor-parallel serving replica's ``ServedModel``: the same answers,
    its steps the ``tp_*`` programs below over the mesh. Owns the dedicated
    1-axis ``("tp",)`` mesh (the serving replica's chips are its whole world
    — fleet-level placement picks WHICH chips via ``replica_env`` pinning),
    the partition specs for the reshaped weight tree and the paged/dense
    caches, and the captured jaxprs the ``serving/tp-collective-order``
    dslint rule audits."""

    def __init__(self, cfg, serving, devices=None):
        super().__init__(cfg, serving)
        self.tp = tp = int(serving.tp)
        if tp < 2:
            raise ValueError(f"TPContext needs tp >= 2, got {tp}")
        devices = list(devices) if devices is not None else jax.devices()
        if len(devices) < tp:
            raise ValueError(f"tp={tp} but only {len(devices)} devices")
        self.mesh = Mesh(np.asarray(devices[:tp]), (TP_AXIS,))
        # name -> ClosedJaxpr of the tp programs, populated by
        # capture_programs() (engine warmup) for the dslint audit
        self.captured: Dict[str, Any] = {}

    def check(self, batch_tokens: int) -> None:
        super().check(batch_tokens)
        cfg, tp = self.cfg, self.tp
        gpt_mod.require_default_block(cfg, "tp serving (serving/tp.py)")
        if cfg.n_head % tp:
            raise ValueError(
                f"tp={tp} must divide n_head={cfg.n_head} (head-sharded "
                f"attention)")
        if cfg.ffn_dim % tp:
            raise ValueError(
                f"tp={tp} must divide ffn_dim={cfg.ffn_dim} (col/row-split "
                f"MLP)")
        if cfg.alibi or cfg.local_attention_period > 1:
            raise ValueError("tp serving does not support alibi/local-window "
                             "attention (same bound as paged_decode_step)")

    # ----------------------------------------------------------- param tree
    def reshape_params(self, params):
        """Host-side relayout: split the fused QKV axes so every sharded
        axis is head/feature-contiguous. Idempotent on already-reshaped
        trees."""
        if any(jax.tree_util.tree_leaves(
                jax.tree_util.tree_map(gpt_mod._is_qleaf, params,
                                       is_leaf=gpt_mod._is_qleaf))):
            raise ValueError(
                "tp serving does not support quantized weight stacks yet "
                "(the int8/int4 Pallas matmuls are not head-sharded)")
        blocks = dict(params["blocks"])
        qkv_w = blocks["qkv_w"]
        if qkv_w.ndim == 3:  # [L, d, 3d] -> [L, d, 3, d]
            L, d, _ = qkv_w.shape
            blocks["qkv_w"] = qkv_w.reshape(L, d, 3, d)
            blocks["qkv_b"] = blocks["qkv_b"].reshape(L, 3, d)
        out = dict(params)
        out["blocks"] = blocks
        return out

    def cache_specs(self, paged_cache) -> Dict[str, P]:
        """Paged pool specs: heads sharded, everything else replicated."""
        return _tp_specs(paged_cache)[0]

    def _put(self, tree, specs):
        shardings = jax.tree_util.tree_map(
            lambda s: NamedSharding(self.mesh, s), specs,
            is_leaf=lambda x: isinstance(x, P))
        return jax.device_put(tree, shardings)

    def place_params(self, params):
        params = self.reshape_params(super().place_params(params))
        return self._put(params, _param_specs(params))

    def place_cache(self, paged_cache):
        return self._put(paged_cache, self.cache_specs(paged_cache))

    def dense_cache(self, rows: int, tokens: int):
        """The dense scratch on the head-sharded layout the programs expect."""
        heads = P(None, None, TP_AXIS, None, None)
        return self._put(super().dense_cache(rows, tokens),
                         {"k": heads, "v": heads, "pos": P()})

    # ------------------------------------------------------------ the steps
    def _no_states(self, rows: int, tokens: int):
        """A tp program's states: tp refuses a config that names any."""
        return jnp.zeros((rows, 0, tokens, self.cfg.d_model), self.dtype)

    def forward_with_cache(self, params, ids, cache, real=None, last=None):
        return tp_forward_with_cache(
            self.cfg, params, ids, cache, self.mesh,
            last=last) + (self._no_states(*ids.shape),)

    def write_prompt(self, paged, dense, table, length, start, slot=None):
        return tp_write_prompt_kv(paged, dense, table, length, start,
                                  self.mesh)

    def write_prompt_batch(self, paged, dense, tables, lengths, starts):
        return tp_write_prompt_kv_batch(paged, dense, tables, lengths, starts,
                                        self.mesh)

    def decode_step(self, params, toks, cache, tables, lengths, impl):
        none = jnp.zeros((0,), jnp.int32)
        return tp_paged_decode_step(
            self.cfg, params, toks, cache, tables, lengths, self.mesh,
            impl=impl) + (self._no_states(toks.shape[0], 1)[:, :, 0], none)

    def verify_step(self, params, toks, cache, tables, lengths, impl):
        return tp_paged_verify_step(self.cfg, params, toks, cache, tables,
                                    lengths, self.mesh, impl=impl)

    def commit_window(self, cache, win_k, win_v, tables, lengths, n):
        return tp_commit_window_kv(cache, win_k, win_v, tables, lengths, n,
                                   self.mesh)

    # ------------------------------------------------------------ dslint IO
    def capture_programs(self, engine) -> Dict[str, Any]:
        """Trace (never execute) the replica's tp decode/verify programs to
        jaxprs for the ``serving/tp-collective-order`` audit. Cheap: pure
        abstract tracing over ShapeDtypeStructs."""
        sds = lambda t: jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(jnp.shape(a), a.dtype), t)
        s = engine.serving
        B = engine.num_slots
        params, cache = sds(engine.params), sds(engine.paged_cache)
        ids = jax.ShapeDtypeStruct((B, 1), jnp.int32)
        win = jax.ShapeDtypeStruct((B, max(2, int(s.spec_k))), jnp.int32)
        tables = jax.ShapeDtypeStruct((B, s.pages_per_seq), jnp.int32)
        lengths = jax.ShapeDtypeStruct((B,), jnp.int32)
        impl = s.kernel_impl
        self.captured["tp_decode"] = jax.make_jaxpr(
            lambda p, c, i, t, le: tp_paged_decode_step(
                self.cfg, p, i, c, t, le, mesh=self.mesh, impl=impl)
        )(params, cache, ids, tables, lengths)
        self.captured["tp_verify"] = jax.make_jaxpr(
            lambda p, c, i, t, le: tp_paged_verify_step(
                self.cfg, p, i, c, t, le, mesh=self.mesh, impl=impl)
        )(params, cache, win, tables, lengths)
        return self.captured


# ------------------------------------------------- shard-local block bodies
def _local_qkv(cfg, x, w):
    """LN1 + head-sharded fused QKV projection. Returns q/k/v [B,T,H_loc,Dh]
    (bitwise the local-head slice of the unsharded projection: each output
    column contracts the same replicated d-axis)."""
    B, T, _ = x.shape
    Dh = cfg.head_dim
    h = gpt_mod.layer_norm(x, w["ln1_scale"], w["ln1_bias"],
                           cfg.layer_norm_eps)
    qkv = jnp.einsum("btd,dce->btce", h, w["qkv_w"]) + w["qkv_b"]
    H_loc = qkv.shape[-1] // Dh
    q = qkv[:, :, 0].reshape(B, T, H_loc, Dh)
    k_ = qkv[:, :, 1].reshape(B, T, H_loc, Dh)
    v = qkv[:, :, 2].reshape(B, T, H_loc, Dh)
    return h, q, k_, v


def _maybe_rope(cfg, q, k_, positions):
    if cfg.rotary:
        rd = int(cfg.rotary_pct * cfg.head_dim)
        rd -= rd % 2
        q = gpt_mod._rope(q, positions, rd, cfg.rotary_interleaved)
        k_ = gpt_mod._rope(k_, positions, rd, cfg.rotary_interleaved)
    return q, k_


def _softmax_scale(cfg):
    return (cfg.attention_scale if cfg.attention_scale is not None
            else 1.0 / np.sqrt(cfg.head_dim))


def _out_proj_partial(x_dtype, attn, w):
    """Row-split attention output projection: local heads contribute a
    PARTIAL [B,T,D] sum; caller psums and adds the replicated bias."""
    B, T = attn.shape[0], attn.shape[1]
    return jnp.einsum("bte,ed->btd",
                      attn.reshape(B, T, -1).astype(x_dtype),
                      w["attn_out_w"])


def _mlp_partial(cfg, x, w):
    """Col-split up / row-split down MLP: returns the PARTIAL [B,T,D] delta
    (no bias — added post-psum by the caller)."""
    h = gpt_mod.layer_norm(x, w["ln2_scale"], w["ln2_bias"],
                           cfg.layer_norm_eps)
    h = h @ w["mlp_up_w"] + w["mlp_up_b"]
    h = gpt_mod._act(cfg, h)
    return h @ w["mlp_down_w"]


def _attn_paged_local(cfg, x, w, pools, layer, tables, lengths, impl, work):
    """Shard-local single-token paged attention (gpt._paged_attn_sublayer
    over the local head slice): appends into layer ``layer`` of the local
    pool shards, in place in the loop that carries them
    (``gpt.append_and_attend``), and returns the PARTIAL out-projection, not
    the residual."""
    _, q, k_, v = _local_qkv(cfg, x, w)
    q, k_ = _maybe_rope(cfg, q, k_, lengths[:, None])
    attn, pools = gpt_mod.append_and_attend(
        pools, layer, q, k_, v, tables, lengths, _softmax_scale(cfg),
        impl=impl, q_dtype=x.dtype, work=work)
    return _out_proj_partial(x.dtype, attn, w), pools


def _attn_verify_local(cfg, x, w, k_pages, v_pages, tables, lengths, impl,
                       k_scales, v_scales):
    """Shard-local speculation-window attention (gpt._paged_verify_sublayer
    over the local head slice). Pool is read-only; returns the partial
    out-projection plus the local win_k/win_v [B, W, H_loc, Dh]."""
    from ...ops.pallas.decode_attention import paged_verify_attention

    _, q, k_, v = _local_qkv(cfg, x, w)
    W = x.shape[1]
    positions = lengths[:, None] + jnp.arange(W)[None, :]
    q, k_ = _maybe_rope(cfg, q, k_, positions)
    quantized = k_scales is not None
    qdt = x.dtype if quantized else k_pages.dtype
    attn = paged_verify_attention(q.astype(qdt), k_pages, v_pages, lengths,
                                  tables, k_, v,
                                  softmax_scale=_softmax_scale(cfg),
                                  impl=impl, k_scales=k_scales,
                                  v_scales=v_scales)
    return _out_proj_partial(x.dtype, attn, w), k_, v


def _attn_dense_local(cfg, x, w, k_cache, v_cache, pos):
    """Shard-local prefill attention over the dense cache slice
    [B, H_loc, S, Dh] (gpt.attn_with_cache's masked-softmax path — also
    what tp1 prefill compiles to, so per-head values match bitwise)."""
    S = k_cache.shape[2]
    B, T, _ = x.shape
    _, q, k_, v = _local_qkv(cfg, x, w)
    positions = pos + jnp.broadcast_to(jnp.arange(T)[None, :], (B, T))
    q, k_ = _maybe_rope(cfg, q, k_, positions)
    k_cache = lax.dynamic_update_slice(
        k_cache, k_.transpose(0, 2, 1, 3).astype(k_cache.dtype),
        (0, 0, pos, 0))
    v_cache = lax.dynamic_update_slice(
        v_cache, v.transpose(0, 2, 1, 3).astype(v_cache.dtype),
        (0, 0, pos, 0))
    logits = jnp.einsum("bthd,bhsd->bhts", q.astype(jnp.float32),
                        k_cache.astype(jnp.float32)) * _softmax_scale(cfg)
    s_idx = jnp.arange(S)[None, :]
    t_idx = positions[:, :, None]
    mask = s_idx <= t_idx  # [B, T, S]
    logits = jnp.where(mask[:, None, :, :], logits, jnp.float32(-1e30))
    probs = jax.nn.softmax(logits, axis=-1)
    attn = jnp.einsum("bhts,bhsd->bthd", probs.astype(v_cache.dtype), v_cache)
    return _out_proj_partial(x.dtype, attn, w), k_cache, v_cache


def _residual(cfg, x, attn_partial, mlp_partial_fn, w):
    """Close a block: psum the partial deltas and add replicated biases.

    ``parallel_residual`` (NeoX/GPT-J) reads the MLP off the pre-attention
    stream, so both partials fold into ONE psum; the sequential residual
    needs the attention psum to complete before LN2 reads the combined
    stream (two psums — the Megatron block shape)."""
    if cfg.parallel_residual:
        delta = lax.psum(attn_partial + mlp_partial_fn(x), TP_AXIS)
        return x + delta + w["attn_out_b"] + w["mlp_down_b"]
    y = x + lax.psum(attn_partial, TP_AXIS) + w["attn_out_b"]
    return y + lax.psum(mlp_partial_fn(y), TP_AXIS) + w["mlp_down_b"]


def _embed(cfg, params, ids, positions):
    x = jnp.take(params["wte"], ids, axis=0)
    if not cfg.rotary and not cfg.alibi:
        x = x + jnp.take(params["wpe"], positions + cfg.pos_offset, axis=0)
    if cfg.embed_layernorm:
        x = gpt_mod.layer_norm(x, params["emb_ln_scale"],
                               params["emb_ln_bias"], cfg.layer_norm_eps)
    return x.astype(params["blocks"]["qkv_w"].dtype)


def _head_logits(cfg, params, x):
    x = gpt_mod.layer_norm(x, params["lnf_scale"], params["lnf_bias"],
                           cfg.layer_norm_eps)
    head = params["wte"] if cfg.tie_embeddings else params["lm_head"]
    logits = jnp.einsum("btd,vd->btv", x, head.astype(x.dtype))
    if cfg.lm_head_bias and not cfg.tie_embeddings:
        logits = logits + params["lm_head_b"].astype(logits.dtype)
    return logits


def _tp_specs(paged_cache):
    cache_specs = {k: (P(None, TP_AXIS, None)
                       if k in ("k_scales", "v_scales")
                       else P(None, TP_AXIS, None, None, None))
                   for k in paged_cache}
    win_spec = P(None, None, None, TP_AXIS, None)
    return cache_specs, win_spec


def _param_specs(params):
    """PartitionSpecs for a :meth:`TPContext.reshape_params` tree (serving tp
    layout — distinct from the training-time ``gpt.partition_specs``, which
    splits the raw QKV concat and vocab-shards the embedding)."""
    block_specs = {
        "qkv_w": P(None, None, None, TP_AXIS),
        "qkv_b": P(None, None, TP_AXIS),
        "attn_out_w": P(None, TP_AXIS, None),
        "mlp_up_w": P(None, None, TP_AXIS),
        "mlp_up_b": P(None, TP_AXIS),
        "mlp_down_w": P(None, TP_AXIS, None),
    }
    specs = {}
    for key, leaf in params.items():
        if key == "blocks":
            specs["blocks"] = {
                k: block_specs.get(k, P(*([None] * jnp.ndim(leaf[k]))))
                for k in leaf}
        else:
            specs[key] = P(*([None] * jnp.ndim(leaf)))
    return specs


# ----------------------------------------------------------- full programs
def tp_paged_decode_step(cfg, params, input_ids, paged_cache, block_tables,
                         lengths, mesh: Mesh,
                         impl: Optional[str] = None
                         ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """tp-sharded :func:`gpt.paged_decode_step`: logits [B, V] replicated,
    pool shards updated in place on their own chips."""
    ids = jnp.asarray(input_ids)
    if ids.ndim == 1:
        ids = ids[:, None]
    lengths = jnp.asarray(lengths, jnp.int32)
    tables = jnp.asarray(block_tables, jnp.int32)
    cache_specs, _ = _tp_specs(paged_cache)
    pspecs = _param_specs(params)

    def body(params, paged, ids, tables, lengths):
        x = _embed(cfg, params, ids, lengths[:, None])
        work = gpt_mod.paged_work(paged, tables, lengths)

        def step(carry, layer_w):
            x, i, pools = carry
            partial, pools = _attn_paged_local(cfg, x, layer_w, pools, i,
                                               tables, lengths, impl, work)
            y = _residual(cfg, x, partial,
                          lambda h: _mlp_partial(cfg, h, layer_w), layer_w)
            return (y, i + 1, pools), None

        # the pool shards are a carry, not xs/ys: gpt.paged_decode_step
        (x, _, pools), _ = lax.scan(
            step, (x, jnp.int32(0), gpt_mod.paged_pools(paged)),
            params["blocks"])
        logits = _head_logits(cfg, params, x)
        return logits[:, 0, :], dict(zip(gpt_mod.POOL_KEYS, pools))

    fn = shard_map(body, mesh=mesh,
                   in_specs=(pspecs, cache_specs, P(), P(), P()),
                   out_specs=(P(), cache_specs),
                   check_vma=False)
    return fn(params, paged_cache, ids, tables, lengths)


def tp_paged_verify_step(cfg, params, window_ids, paged_cache, block_tables,
                         lengths, mesh: Mesh,
                         impl: Optional[str] = None
                         ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """tp-sharded :func:`gpt.paged_verify_step`: logits [B, W, V] replicated,
    win_k/win_v [L, B, W, H, Dh] sharded over the head axis (they feed
    straight into :func:`tp_commit_window_kv`, which is sharded the same
    way — the window K/V never leave their chips)."""
    ids = jnp.asarray(window_ids)
    lengths = jnp.asarray(lengths, jnp.int32)
    tables = jnp.asarray(block_tables, jnp.int32)
    cache_specs, win_spec = _tp_specs(paged_cache)
    pspecs = _param_specs(params)
    kv_q = "k_scales" in paged_cache

    def body(params, paged, ids, tables, lengths):
        W = ids.shape[1]
        positions = lengths[:, None] + jnp.arange(W)[None, :]
        x = _embed(cfg, params, ids, positions)

        def step(carry, layer_in):
            x, i = carry
            layer_w, kv = layer_in[0], layer_in[1:]
            k_s, v_s = (kv[2], kv[3]) if kv_q else (None, None)
            partial, wk, wv = _attn_verify_local(
                cfg, x, layer_w, kv[0], kv[1], tables, lengths, impl,
                k_s, v_s)
            y = _residual(cfg, x, partial,
                          lambda h: _mlp_partial(cfg, h, layer_w), layer_w)
            return (y, i + 1), (wk, wv)

        (x, _), (win_k, win_v) = lax.scan(
            step, (x, jnp.int32(0)),
            (params["blocks"],) + gpt_mod.paged_pools(paged))
        return _head_logits(cfg, params, x), win_k, win_v

    fn = shard_map(body, mesh=mesh,
                   in_specs=(pspecs, cache_specs, P(), P(), P()),
                   out_specs=(P(), win_spec, win_spec),
                   check_vma=False)
    return fn(params, paged_cache, ids, tables, lengths)


def tp_commit_window_kv(paged_cache, win_k, win_v, block_tables, lengths,
                        n_commit, mesh: Mesh) -> Dict[str, jnp.ndarray]:
    """Head-sharded :func:`gpt.commit_window_kv`: the accepted-prefix
    scatter is per-head independent and collective-free, so the unmodified
    writer runs on each shard's local pool + window slice."""
    cache_specs, win_spec = _tp_specs(paged_cache)
    fn = shard_map(gpt_mod.commit_window_kv, mesh=mesh,
                   in_specs=(cache_specs, win_spec, win_spec, P(), P(), P()),
                   out_specs=cache_specs,
                   check_vma=False)
    return fn(paged_cache, win_k, win_v,
              jnp.asarray(block_tables, jnp.int32),
              jnp.asarray(lengths, jnp.int32),
              jnp.asarray(n_commit, jnp.int32))


def tp_write_prompt_kv_batch(paged_cache, dense_cache, block_tables, lengths,
                             starts, mesh: Mesh) -> Dict[str, jnp.ndarray]:
    """Head-sharded :func:`gpt.write_prompt_kv_batch` (prefill-to-pool
    scatter, including the quantized per-page absmax path — all per-head,
    collective-free)."""
    cache_specs, _ = _tp_specs(paged_cache)
    dspec = {"k": P(None, None, TP_AXIS, None, None),
             "v": P(None, None, TP_AXIS, None, None)}

    def body(paged, dense, tables, lengths, starts):
        return gpt_mod.write_prompt_kv_batch(paged, dense, tables, lengths,
                                             starts=starts)

    fn = shard_map(body, mesh=mesh,
                   in_specs=(cache_specs, dspec, P(), P(), P()),
                   out_specs=cache_specs,
                   check_vma=False)
    dense = {"k": dense_cache["k"], "v": dense_cache["v"]}
    return fn(paged_cache, dense,
              jnp.asarray(block_tables, jnp.int32),
              jnp.asarray(lengths, jnp.int32),
              jnp.asarray(starts, jnp.int32))


def tp_write_prompt_kv(paged_cache, dense_cache, block_table, length, start,
                       mesh: Mesh, row: int = 0) -> Dict[str, jnp.ndarray]:
    """Single-request :func:`tp_write_prompt_kv_batch` over ``dense_cache``
    row ``row`` (mirrors :func:`gpt.write_prompt_kv`)."""
    one = {"k": dense_cache["k"][:, row:row + 1],
           "v": dense_cache["v"][:, row:row + 1]}
    return tp_write_prompt_kv_batch(
        paged_cache, one, jnp.asarray(block_table, jnp.int32)[None],
        jnp.asarray(length, jnp.int32)[None],
        jnp.asarray(start, jnp.int32)[None], mesh)


def tp_forward_with_cache(cfg, params, input_ids, cache, mesh: Mesh,
                          last=None
                          ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """tp-sharded :func:`gpt.forward_with_cache` (the prefill program):
    dense cache sharded over heads, logits replicated: [B, T, V], or
    (``last`` [B]) [B, V] of position ``last[b]`` of row ``b``, the head
    left out where every row's is negative."""
    ids = jnp.asarray(input_ids)
    pspecs = _param_specs(params)
    cspec = P(None, None, TP_AXIS, None, None)
    last = () if last is None else (jnp.asarray(last, jnp.int32),)

    def body(params, ids, k_cache, v_cache, pos, *last):
        B, T = ids.shape
        positions = pos + jnp.broadcast_to(jnp.arange(T)[None, :], (B, T))
        x = _embed(cfg, params, ids, positions)

        def step(carry, layer_in):
            x, i = carry
            layer_w, k_c, v_c = layer_in
            partial, k_c, v_c = _attn_dense_local(cfg, x, layer_w,
                                                  k_c, v_c, pos)
            y = _residual(cfg, x, partial,
                          lambda h: _mlp_partial(cfg, h, layer_w), layer_w)
            return (y, i + 1), (k_c, v_c)

        (x, _), (new_k, new_v) = lax.scan(
            step, (x, jnp.int32(0)), (params["blocks"], k_cache, v_cache))
        return gpt_mod.prompt_logits(cfg, params, x, *last,
                                     head=_head_logits), new_k, new_v

    fn = shard_map(body, mesh=mesh,
                   in_specs=(pspecs, P(), cspec, cspec, P()) + (P(),) * len(
                       last),
                   out_specs=(P(), cspec, cspec),
                   check_vma=False)
    logits, new_k, new_v = fn(params, ids, cache["k"], cache["v"],
                              cache["pos"], *last)
    return logits, {"k": new_k, "v": new_v,
                    "pos": cache["pos"] + ids.shape[1]}
