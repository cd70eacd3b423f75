"""Speculative decoding drafters (docs/SERVING.md "Speculative decoding").

Decode is weight-bound: a verification pass that scores ``k+1`` positions in
one dispatch (``models/gpt.paged_verify_step``) reads every weight matrix
ONCE where ``k+1`` sequential decode steps read it ``k+1`` times — so if a
cheap *drafter* can guess the next few greedy tokens, accepted guesses are
nearly free. This module is the host half of that bet:

- :class:`NGramDrafter` — self-drafting by suffix match over the request's
  OWN prompt + generated tokens. Zero extra HBM, zero device work; it wins
  exactly when generation is locally repetitive (code, templated text, the
  greedy loops small models fall into).
- :class:`DraftModelDrafter` — a small model (e.g. gpt2-125m drafting for a
  760m+ target) greedily proposing ``k`` tokens from its OWN dense KV cache.
  The cache lives outside the target's page pool; rejected drafts roll back
  by rewinding the cache position (stale entries past ``pos`` are masked and
  overwritten — no copy). Its HBM cost is priced into ``num_slots="auto"``
  by ``runtime/aot.speculation_hbm_bytes``.

Both sit behind one protocol the scheduler consumes::

    draft(slot, rid, prompt, tokens, k) -> np.ndarray  # <= k proposed tokens
    release(slot)                                      # slot evicted/reused
    kind                                               # accounting label

Drafters PROPOSE, the target DISPOSES: acceptance is longest-prefix greedy
agreement computed inside the verify program, so a drafter can be arbitrarily
wrong without ever changing outputs — the worst case is wasted verify
positions, which :class:`AdaptiveSpecK` bounds by collapsing ``k`` toward 1
when the accept rate is low.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Protocol, Sequence, Tuple

import numpy as np

from ...profiling import trace


class Drafter(Protocol):
    """The scheduler-facing drafter protocol (host-level; a drafter MAY own
    device state, the scheduler never sees it)."""

    kind: str

    def draft(self, slot: int, rid: int, prompt: np.ndarray,
              tokens: Sequence[int], k: int) -> np.ndarray:
        """Up to ``k`` proposed next tokens for the request in ``slot``
        whose verified context is ``prompt + tokens``. Fewer (or zero)
        proposals are fine — unfilled window positions are padded and
        simply fail verification."""
        ...

    def release(self, slot: int) -> None:
        """The slot was evicted/finished/preempted — drop any per-slot
        state (a later ``draft`` for the same slot may carry a new rid)."""
        ...


def spec_k_ladder(max_k: int) -> Tuple[int, ...]:
    """The bounded draft-length set: powers of two up to ``max_k``. Window
    sizes W = k+1 then step 2, 3, 5, 9, ... — unequal strides, so the
    ``serving/unbucketed-decode-shape`` rule never mistakes the verify
    program family for a creeping shape."""
    if max_k < 1:
        raise ValueError(f"spec_k must be >= 1, got {max_k}")
    out = []
    k = 1
    while k <= max_k:
        out.append(k)
        k *= 2
    return tuple(out)


class AdaptiveSpecK:
    """Accept-rate-driven draft length: speculation can never be a
    regression because ``k`` collapses toward the ladder floor (k=1, whose
    verify window costs barely more than a plain decode step in the
    weight-bound regime) whenever drafts stop being accepted, and climbs
    back when they land. EMA-smoothed; the EMA resets on every level change
    so a stale regime cannot echo."""

    def __init__(self, ladder: Sequence[int], adaptive: bool = True,
                 low: float = 0.35, high: float = 0.75, decay: float = 0.8):
        if not ladder:
            raise ValueError("empty spec-k ladder")
        self.ladder = tuple(int(k) for k in ladder)
        self.adaptive = bool(adaptive)
        self.low = float(low)
        self.high = float(high)
        self.decay = float(decay)
        self.level = len(self.ladder) - 1   # start optimistic, back off fast
        self.ema: Optional[float] = None

    @property
    def k(self) -> int:
        return self.ladder[self.level]

    def observe(self, offered: int, accepted: int) -> None:
        """One verification window's outcome: ``offered`` draft positions
        (k x active slots), ``accepted`` of them confirmed."""
        rate = accepted / max(offered, 1)
        self.ema = (rate if self.ema is None
                    else self.decay * self.ema + (1.0 - self.decay) * rate)
        if not self.adaptive or len(self.ladder) == 1:
            return
        if self.ema < self.low and self.level > 0:
            self.level -= 1
            self.ema = None
        elif self.ema > self.high and self.level < len(self.ladder) - 1:
            self.level += 1
            self.ema = None


# ------------------------------------------------------------------- n-gram
class NGramDrafter:
    """Suffix-match self-drafting (prompt-lookup decoding): find the most
    recent earlier occurrence of the context's trailing n-gram and propose
    the tokens that followed it. Tries the longest order first
    (``max_n .. min_n``); among matches prefers the most recent one with a
    full ``k`` tokens of continuation, falling back to the most recent
    match's shorter tail. Pure host work over the request's own tokens —
    the zero-cost drafter."""

    kind = "ngram"

    def __init__(self, max_n: int = 3, min_n: int = 1):
        if not (1 <= min_n <= max_n):
            raise ValueError(f"bad n-gram order range [{min_n}, {max_n}]")
        self.max_n = int(max_n)
        self.min_n = int(min_n)

    def draft(self, slot: int, rid: int, prompt: np.ndarray,
              tokens: Sequence[int], k: int) -> np.ndarray:
        del slot, rid
        ctx = np.concatenate([np.asarray(prompt, np.int64),
                              np.asarray(list(tokens), np.int64)])
        L = len(ctx)
        if k < 1 or L < 2:
            return np.empty(0, np.int32)   # empty/one-token history
        for n in range(min(self.max_n, L - 1), self.min_n - 1, -1):
            pat = ctx[L - n:]
            # windows starting at s hold ctx[s:s+n] == a match ENDING at
            # j = s + n; j == L is the query suffix itself, excluded
            wins = np.lib.stride_tricks.sliding_window_view(ctx, n)[:L - n]
            hit = np.flatnonzero((wins == pat).all(axis=1))
            if hit.size == 0:
                continue
            js = hit + n
            full = js[js + k <= L]
            # most recent occurrence with k tokens of continuation, else
            # the most recent occurrence's shorter tail (degenerate repeats
            # land here until the period covers k)
            j = int(full[-1]) if full.size else int(js[-1])
            return ctx[j:min(j + k, L)].astype(np.int32)
        return np.empty(0, np.int32)

    def release(self, slot: int) -> None:
        pass


# -------------------------------------------------------------- draft model
class DraftModelDrafter:
    """A small GPT proposing ``k`` greedy tokens from its own dense KV cache.

    Per-slot state: the draft model's contiguous cache plus the exact token
    list it has consumed. On every call the verified context is diffed
    against that list — accepted drafts are already cached (their KV was
    written when they were PROPOSED), rejected ones rewind by truncating the
    host list and resetting ``cache["pos"]`` (entries past ``pos`` are
    masked by the cached-attention validity mask and overwritten in place,
    so rollback costs nothing). The context delta then streams in
    power-of-two chunks (exact sizes — the persistent cache can't absorb
    the padding the target's prefill scatter drops), and ``k`` greedy steps
    propose the window.

    Compile discipline: feed programs per chunk bucket + ONE single-token
    step program, recorded in the serving engine's ``compile_log`` (kinds
    ``draft_feed``/``draft_step``) where the unbucketed-decode-shape rule
    audits them alongside the target's programs."""

    kind = "draft_model"

    def __init__(self, cfg, params, max_len: int, dtype="float32",
                 max_chunk: int = 64, compile_log: Optional[list] = None,
                 monitor=None):
        import jax
        import jax.numpy as jnp

        from ...models import gpt as gpt_mod
        from .buckets import default_buckets

        self.cfg = cfg
        self.max_len = int(max_len)
        self.dtype = jnp.dtype(dtype)
        self._jax = jax
        self._jnp = jnp
        self._gpt = gpt_mod

        def _cast(x):
            if gpt_mod._is_qleaf(x):
                return x
            return (x.astype(self.dtype)
                    if jnp.issubdtype(x.dtype, jnp.floating) else x)

        self.params = jax.tree_util.tree_map(_cast, params,
                                             is_leaf=gpt_mod._is_qleaf)
        self._buckets = default_buckets(1, max(int(max_chunk), 1))
        self._feed_fns: Dict[int, Any] = {}
        self._step_fn = None
        self._slots: Dict[int, Dict[str, Any]] = {}
        self.compile_log = compile_log
        self.monitor = monitor

    # ------------------------------------------------------------- programs
    def _log_compile(self, kind: str, shape) -> None:
        if self.compile_log is not None:
            from .buckets import record_compile

            record_compile(self.compile_log, self.monitor,
                           "Serving/compile_events", kind, shape)

    def _get_feed(self, chunk: int):
        if chunk not in self._feed_fns:
            self._log_compile("draft_feed", (1, chunk))
            jax, gpt_mod = self._jax, self._gpt

            def fn(params, ids, cache):
                return gpt_mod.forward_with_cache(self.cfg, params, ids,
                                                  cache)

            fn.__name__ = f"draft_feed_{chunk}"  # the program's trace name
            self._feed_fns[chunk] = jax.jit(fn, donate_argnums=(2,))
        return self._feed_fns[chunk]

    def _get_step(self):
        if self._step_fn is None:
            self._log_compile("draft_step", (1, 1))
            jax, jnp, gpt_mod = self._jax, self._jnp, self._gpt

            def fn(params, tok, cache):
                logits, cache = gpt_mod.forward_with_cache(
                    self.cfg, params, tok[None, None], cache)
                return jnp.argmax(logits[0, -1]).astype(jnp.int32), cache

            fn.__name__ = "draft_step"  # the program's trace name
            self._step_fn = jax.jit(fn, donate_argnums=(2,))
        return self._step_fn

    # ------------------------------------------------------------- protocol
    def draft(self, slot: int, rid: int, prompt: np.ndarray,
              tokens: Sequence[int], k: int) -> np.ndarray:
        jnp, gpt_mod = self._jnp, self._gpt
        ctx = [int(t) for t in np.asarray(prompt).tolist()] + \
              [int(t) for t in tokens]
        if k < 1 or len(ctx) + k > self.max_len:
            return np.empty(0, np.int32)   # window would outgrow the cache
        st = self._slots.get(slot)
        if st is None or st["rid"] != rid:
            st = {"rid": rid,
                  "cache": gpt_mod.init_cache(self.cfg, 1, self.max_len,
                                              self.dtype),
                  "fed": []}
            self._slots[slot] = st
        fed: List[int] = st["fed"]
        p = 0
        limit = min(len(fed), len(ctx) - 1)   # always re-feed >= 1 token so
        while p < limit and fed[p] == ctx[p]:  # the draft has fresh logits
            p += 1
        if p < len(fed):
            # rejected drafts (or a preemption replay): rewind — positions
            # past p are masked + overwritten, no device copy needed
            st["fed"] = fed = fed[:p]
            cache = dict(st["cache"])
            cache["pos"] = jnp.int32(p)
            st["cache"] = cache
        delta = ctx[p:]
        cache = st["cache"]
        logits = None
        # exact-size power-of-two pieces: the persistent cache advances by
        # the full fed shape, so padding would poison positions
        while delta:
            piece = 1
            for b in self._buckets:
                if b <= len(delta):
                    piece = b
            ids = np.asarray(delta[:piece], np.int32)[None]
            logits, cache = self._get_feed(piece)(self.params,
                                                  jnp.asarray(ids), cache)
            trace.fed(f"draft_feed_{piece}")
            delta = delta[piece:]
        st["fed"] = fed = fed + ctx[p:]
        nxt = int(jnp.argmax(logits[0, -1]))
        drafts = [nxt]
        step = self._get_step()
        for _ in range(k - 1):
            tok, cache = step(self.params, jnp.int32(drafts[-1]), cache)
            trace.fed("draft_step")
            drafts.append(int(tok))
        # the k-th draft was never fed — its KV is not in the cache
        st["fed"] = fed + drafts[:-1]
        st["cache"] = cache
        return np.asarray(drafts, np.int32)

    def release(self, slot: int) -> None:
        self._slots.pop(slot, None)


def make_drafter(engine, serving) -> Optional[Any]:
    """Build the configured drafter for a :class:`~.engine.ServingEngine`
    (``ServingConfig.spec_drafter``: None | "ngram" | "draft_model")."""
    kind = serving.spec_drafter
    if not kind:
        return None
    if kind == "ngram":
        return NGramDrafter(max_n=serving.spec_ngram)
    if kind == "draft_model":
        draft = getattr(engine, "draft", None)
        if draft is None:
            if not serving.spec_draft_model:
                raise ValueError(
                    "spec_drafter='draft_model' needs either "
                    "ServingEngine(draft=(cfg, params)) or "
                    "ServingConfig.spec_draft_model (a PRESETS name; "
                    "seed-0 init — pass real params for real acceptance)")
            import jax

            from ...models import gpt as gpt_mod

            dcfg = gpt_mod.PRESETS[serving.spec_draft_model]
            draft = (dcfg, gpt_mod.init_params(dcfg, jax.random.PRNGKey(0)))
        dcfg, dparams = draft
        return DraftModelDrafter(
            dcfg, dparams, max_len=serving.max_model_len,
            dtype=engine.dtype, max_chunk=serving.prefill_chunk,
            compile_log=engine.compile_log, monitor=engine.monitor)
    raise ValueError(f"unknown spec_drafter {kind!r} "
                     f"(None | 'ngram' | 'draft_model')")


__all__ = ["Drafter", "NGramDrafter", "DraftModelDrafter", "AdaptiveSpecK",
           "spec_k_ladder", "make_drafter"]
