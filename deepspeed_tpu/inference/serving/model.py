"""What a serving engine and its scheduler know of the model they serve.

One object an engine, ``ServingEngine.model``, built at construction: beside
``tp.py`` (the same steps over a mesh) the only place of ``inference/serving``
that calls ``models/gpt`` for the served model. It answers four kinds of
question: what the cache is (:meth:`ServedModel.place`), what the model
refuses (:meth:`refuse`, :meth:`check`), the steps the engine's programs are
made of (``forward_with_cache`` ... ``commit_window``) and what a dispatch
counts (:meth:`decode_counts`, :meth:`program_counts`). A new served family,
or a kernel whose grid walks the cache another way, changes these answers:
the engine and the scheduler take no model fact of their own.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Dict, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from ...models import gpt as gpt_mod
from ...profiling import trace

# Token-expert pairs (tokens x ``moe_k``) of one admission dispatch up to
# which a config with a delta-rule mixer AND routed layers has run on the
# chip. Its ``[2, 512]`` batch at 8 experts a token (8,192 pairs) did not
# return for some prompts and the cause is not found (PERF.md section 7, PR
# 55); another routed config's [1, 1024] x 8 returns, so the limit is this
# combination's. More is refused at construction: a cell would hang the chip.
KDA_ROUTED_PAIRS = 4096


@dataclasses.dataclass(frozen=True)
class DecodeFacts:
    """What the model's half of a ``serve.decode`` span is computed from:
    constants of a model over one engine's pages (``ServedModel.facts``)."""

    page_size: int
    cache_layers: int = 0   # key and value layers a decode step walks
    attn_window: int = 0    # of the layers that keep a ring a slot
    # bytes of a slot's states and windows over the mixers that keep one
    state_bytes: int = 0
    state_layers: int = 0   # how many do (Mamba-2, KDA, power retention)
    # the pages of a request a grid step of the model's decode kernel takes
    # over the block tables: ``paged_decode_gqa`` (fewer key-value heads),
    # ``paged_decode_mla`` (latent layers that read pages), ``paged_decode``
    # (a key head a query head); 0 for a model whose kernel is another
    gqa_pages_per_step: int = 0
    mla_pages_per_step: int = 0
    paged_pages_per_step: int = 0
    index_layers: int = 0   # layers in pages that read a learned selection
    index_topk: int = 0     # of their rows; the rows a selection keeps

    def counts(self, held: np.ndarray, steps: int) -> Dict[str, int]:
        """Of a decode dispatch of ``steps`` steps over active slots whose
        caches hold ``held`` tokens: the rows of keys a step reads in a full
        and in a window layer; the page tiles the kernel's groups fetch for
        the first step's token (``trace.GQA_STATS``, ``MLA_STATS``,
        ``PAGED_STATS``); ``SELECT_STATS`` (step ``j`` sees a slot's rows with
        its new ones); ``STATE_STATS`` (a state read and written a step)."""
        stats, slots = {"cache_layers": self.cache_layers}, len(held)
        live = int(held.sum()) if self.attn_window or self.state_bytes else 0
        if self.attn_window:
            stats.update(
                kv_rows_full=live,
                kv_rows_window=int(np.minimum(held, self.attn_window).sum()))
        for kind, g in (("gqa", self.gqa_pages_per_step),
                        ("mla", self.mla_pages_per_step),
                        ("paged", self.paged_pages_per_step)):
            if g:
                stats.update({
                    f"{kind}_group_tiles": g * int(
                        (-(-(held // self.page_size + 1) // g)).sum()),
                    f"{kind}_pages_per_step": g})
        if self.index_layers:
            seen = held[None, :] + 1 + np.arange(steps)[:, None]
            stats.update(
                index_rows=self.index_layers * int(seen.sum()),
                selected_rows=self.index_layers * int(
                    np.minimum(seen, self.index_topk).sum()))
        if self.state_bytes:
            stats.update(
                state_slots=slots,
                state_bytes=2 * self.state_bytes * slots * steps,
                state_layers=self.state_layers,
                kv_rows=self.cache_layers * (
                    steps * live + slots * steps * (steps + 1) // 2))
        return stats


class ServedModel:
    """A GPT config served from one device (see the module's docstring)."""

    def __init__(self, cfg: gpt_mod.GPTConfig, serving):
        self.cfg, self.serving = cfg, serving
        self.dtype = jnp.dtype({"bf16": "bfloat16", "fp32": "float32",
                                "fp16": "float16"}.get(serving.dtype,
                                                       serving.dtype))

    # ------------------------------------------------------ what it refuses
    def refuse(self, where: str) -> None:
        """Raise, by the field's name, for a model ``where`` does not carry:
        latent pages, routed layers, a state a slot, a layer of two attention
        sub-blocks with a routed branch across them (``moe_shortcut``, named
        first: ``gpt.KIND_FIELDS``)."""
        gpt_mod.require_default_block(self.cfg, where, gpt_mod.KIND_FIELDS)

    def check(self, batch_tokens: int) -> None:
        """Refuse what the model does not run under ``serving``'s options:
        an admission dispatch (of at most ``batch_tokens`` tokens) of more
        than ``KDA_ROUTED_PAIRS``, and the options that read or size a pool
        of keys and values a head (kv_bits, a quantized stack and
        verification refuse in models/gpt.py)."""
        cfg, s = self.cfg, self.serving
        pairs = batch_tokens * cfg.moe_k
        if (cfg.kda is not None and cfg.moe_experts
                and pairs > KDA_ROUTED_PAIRS):
            raise ValueError(
                f"prefill_chunk {s.prefill_chunk} lets an admission batch "
                f"hand the routed layers {pairs} token-expert pairs; with a "
                f"delta-rule mixer in the stack at most {KDA_ROUTED_PAIRS} "
                "have returned on the chip (PERF.md section 7, PR 55): lower "
                "prefill_chunk")
        for option, on in (("tp", int(s.tp or 1) > 1),
                           ("enable_prefix_cache", s.enable_prefix_cache),
                           ("page_fingerprints", s.page_fingerprints),
                           ("spec_drafter", bool(s.spec_drafter)),
                           ("role", s.role != "both")):
            if on:
                self.refuse(f"ServingConfig.{option}={getattr(s, option)!r}")

    # ---------------------------------------------------- what the cache is
    def place(self, params, num_pages: int, num_slots: int) -> Tuple:
        """(the weights in the engine's dtype, the paged cache), where the
        steps expect them; and what the engine's programs then know of the
        cache: ``rings`` (window layers keep a ring a decode slot or mixers a
        state: a prefill program names the slot), ``states`` (a state is
        what a chunk's last REAL token left: the dense chunk program is told
        how many are), ``prompt_to_pages`` (quantized pools or weights and tp
        keep the dense cache and the scatter after it) and ``chunk_to_pages``
        (a longer prompt's chunks too: gpt.chunks_to_pages)."""
        s = self.serving
        params = self.place_params(params)
        cache = gpt_mod.init_paged_cache(
            self.cfg, num_pages, s.page_size, self.dtype, kv_bits=s.kv_bits,
            ring_slots=num_slots)
        self.states = gpt_mod.SSM_KEYS[0] in cache
        self.rings = gpt_mod.RING_KEYS[0] in cache or self.states
        self.prompt_to_pages = (
            int(s.tp or 1) == 1 and not s.kv_bits
            and not gpt_mod._is_qleaf(gpt_mod._a_matrix(
                gpt_mod._stacks(self.cfg, params)[0][0])))
        self.chunk_to_pages = (self.prompt_to_pages
                               and gpt_mod.chunks_to_pages(self.cfg))
        return params, self.place_cache(cache)

    def place_params(self, params):
        def cast(x):
            if gpt_mod._is_qleaf(x):
                return x
            return (x.astype(self.dtype)
                    if jnp.issubdtype(x.dtype, jnp.floating) else x)

        return jax.tree_util.tree_map(cast, params,
                                      is_leaf=gpt_mod._is_qleaf)

    def place_cache(self, paged):
        """The paged cache where the steps expect it."""
        return paged

    def dense_cache(self, rows: int, tokens: int):
        """A prefill's contiguous scratch cache, where the steps expect it."""
        return gpt_mod.init_cache(self.cfg, rows, tokens, self.dtype)

    def capture_programs(self, engine) -> None:
        """Nothing to audit on one device (``TPContext.capture_programs``)."""

    def kv_bytes_per_token(self) -> float:
        """HBM bytes one cached token costs in THIS config's pools (payload
        + amortized per-page scales) — the honest equal-HBM-bytes axis of
        the dense-vs-quantized A/B. Rings and states cost a slot, not a token
        (:meth:`slot_bytes`); a model whose layers are all mixers reads 0."""
        s = self.serving
        return gpt_mod.paged_kv_bytes_per_token(self.cfg, s.kv_bits,
                                                s.page_size, self.dtype)

    def slot_bytes(self) -> int:
        """HBM bytes a decode slot costs whatever its request's length: the
        window layers' rings and the mixers' states and convolution windows.
        With :meth:`kv_bytes_per_token` the whole of the cache."""
        return (gpt_mod.ring_bytes_per_slot(self.cfg, self.serving.page_size,
                                            self.dtype)
                + gpt_mod.ssm_bytes_per_slot(self.cfg))

    def describe(self, num_pages: int, num_slots: int) -> str:
        """The construction log line: what the cache holds and costs."""
        s, cfg = self.serving, self.cfg
        paged, ringed = gpt_mod.paged_layers(cfg)
        rings = gpt_mod.ring_bytes_per_slot(cfg, s.page_size, self.dtype)
        return (f"serving: {self.kv_bytes_per_token():.0f} bytes a cached "
                f"token over {paged} cache layers, "
                f"{(num_pages - 1) * s.page_size} tokens in {num_pages} pages"
                + (f"; {ringed} window layers keep {rings} bytes a slot in "
                   "rings" if ringed else "")
                + (f"; {gpt_mod.ssm_layers(cfg)} mixers keep "
                   f"{gpt_mod.ssm_bytes_per_slot(cfg)} bytes a slot in "
                   f"states, {self.slot_bytes() * num_slots} bytes "
                   f"over {num_slots} slots" if self.states else ""))

    # ------------------------------------------------------------ the steps
    def forward_with_cache(self, params, ids, cache, real=None, last=None):
        """(logits, cache, states) of the dense-cache forward; ``real``: the
        chunk's real tokens, where mixers keep states; ``last`` [B]: the
        logits [B, V] of that position of each row alone, none computed
        where every row's is negative (``gpt.forward_with_cache``)."""
        return gpt_mod.forward_with_cache(self.cfg, params, ids, cache,
                                          return_states=True, real=real,
                                          last=last)

    def prefill_pages(self, params, ids, paged, tables, lengths, starts,
                      slots=None, chunk=None):
        """Prompts of at most one chunk into pages (and, row ``f`` into the
        ring of decode slot ``slots[f]``, where window layers keep rings):
        (each row's last real logits [F, V], pool, states). ``chunk``: (the
        position, the alignment) of a longer prompt's chunk."""
        if self.prompt_to_pages:
            return gpt_mod.paged_prefill_step(self.cfg, params, ids, paged,
                                              tables, lengths, starts, slots,
                                              chunk=chunk)
        cache = gpt_mod.init_cache(self.cfg, ids.shape[0], ids.shape[1],
                                   self.dtype)
        logits, cache, states = self.forward_with_cache(
            params, ids, cache, last=lengths - 1)   # no prompt, no head
        paged = self.write_prompt_batch(paged, cache, tables, lengths, starts)
        return logits, paged, states

    def write_prompt(self, paged, dense, table, length, start, slot=None):
        return gpt_mod.write_prompt_kv(paged, dense, table, length,
                                       start=start, cfg=self.cfg, slot=slot)

    def write_prompt_batch(self, paged, dense, tables, lengths, starts):
        return gpt_mod.write_prompt_kv_batch(paged, dense, tables, lengths,
                                             starts=starts)

    def decode_step(self, params, toks, cache, tables, lengths, impl):
        """(logits, cache, states, routing counts) of one decode step; the
        counts [4] of a routed model (``gpt.routing_of``; [5] where its
        router also scores zero-compute experts: ``trace.ROUTED_ZERO``), else
        [0]."""
        none = jnp.zeros((0,), jnp.int32)
        logits, cache, states, routing = gpt_mod.paged_decode_step(
            self.cfg, params, toks, cache, tables, lengths, impl=impl,
            return_states=True, return_routing=True)
        return logits, cache, states, (none if routing is None
                                       else routing[1])

    def verify_step(self, params, toks, cache, tables, lengths, impl):
        return gpt_mod.paged_verify_step(self.cfg, params, toks, cache,
                                         tables, lengths, impl=impl)

    def commit_window(self, cache, win_k, win_v, tables, lengths, n):
        return gpt_mod.commit_window_kv(cache, win_k, win_v, tables,
                                        lengths, n)

    # ---------------------------------------------- what a dispatch counts
    @functools.cached_property
    def facts(self) -> DecodeFacts:
        cfg, s, dtype = self.cfg, self.serving, self.dtype
        return DecodeFacts(
            page_size=s.page_size,
            cache_layers=gpt_mod.cache_layers(cfg),
            attn_window=gpt_mod.window_of(cfg),
            state_bytes=gpt_mod.ssm_bytes_per_slot(cfg),
            state_layers=gpt_mod.ssm_layers(cfg),
            gqa_pages_per_step=gpt_mod.gqa_pages_per_step(
                cfg, s.page_size, s.pages_per_seq, dtype),
            mla_pages_per_step=gpt_mod.mla_pages_per_step(
                cfg, s.page_size, s.pages_per_seq, dtype),
            paged_pages_per_step=gpt_mod.paged_pages_per_step(
                cfg, s.page_size, s.pages_per_seq, dtype, s.kv_bits,
                int(s.tp or 1)),
            index_layers=gpt_mod.index_layers(cfg),
            index_topk=gpt_mod.index_topk_of(cfg))

    def decode_counts(self, held: np.ndarray, steps: int) -> Dict[str, int]:
        """The model's half of a ``serve.decode`` span (``facts.counts``)."""
        return self.facts.counts(held, steps)

    @staticmethod
    def program_counts(kind: str, jaxpr) -> Dict[str, int]:
        """What a program of ``kind`` (``"decode"``, ``"prefill"``) says of
        itself once traced: the rows of ``trace.KERNEL_STATS`` that its kind
        of span says, where the program runs any such form at all."""
        out: Dict[str, int] = {}
        for stats, row in trace.KERNEL_STATS.items():
            if row.said_by == kind:
                counts = trace.kernel_stats(jaxpr, stats)
                if counts[stats[0]]:
                    out.update(counts)
        return out
