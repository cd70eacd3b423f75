"""Family ``nemotron_h``: Nemotron-3-Nano's layers on the program's normal
path, ``deepspeed_tpu/models/gpt.py`` with a layer that is one sublayer by a
pattern (``GPTConfig.layer_pattern``: a Mamba-2 mixer of ``models/ssm.py``, a
routed feed-forward, attention with 2 key-value heads for 32 query heads and
nothing rotated), a sigmoid router that chooses by score plus bias over
ungated relu-squared experts of which this chip holds a share, said as data;
the page pool for the one kind of layer that caches rows and a state a decode
slot for the mixers; ``paged_decode_gqa``, ``ssm_decode``,
``moe/dropless.py``. ``reference/nemotron_h_ref.py`` has the equations and the
parameter tree; ``init_params`` here makes that tree.

``config(model)`` takes the ``model`` group of a configuration file in the
names ``nemotron_h_ref`` reads and refuses what the reference refuses. The
group's ``chunk_size`` (the scan's chunk, the program's alone),
``time_step_min`` / ``_max`` / ``_floor`` (the seeded ``dt_bias``),
``linear_out_float32``, ``stream_float32``, ``experts_two_pass`` and
``attention_float32`` (absent: false) are
``GPTConfig``'s. "Nothing rotated" is said as ``rotary=True, rotary_pct=0``:
no learned positions, and no dimension of a head turned.

The reference routes, so ``paged_decode_step`` returns the experts its step
chose third, int32 ``[slots, n_layer, k]`` (``benchmark/README.md``, the
``model family`` row): the row of an attention layer is -1, the row of a
mixer layer the readings of the state its step left in the slot, as the
reference takes them (``paged_decode_step`` below).

``init_params`` rounds every matrix to bf16 as it is drawn
(``gpt._normal_in_pieces``): N(0, 0.02), 0.02 / sqrt(2 n_layer) for the
projections into the stream; ``A_log = log(U[1, 16])``, ``dt_bias`` the
inverse softplus of a log-uniform draw in [time_step_min, time_step_max]
floored at time_step_floor, ``D`` and the gains ones, the router's bias
N(0, 0.02), small and not zero so that choice and gate differ
(``models/ssm.init_mixer``, ``gpt._init_kinds``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from deepspeed_tpu.models import gpt as _gpt
from deepspeed_tpu.models.gpt import init_cache  # noqa: F401

from ..lib.correct import SEQUENCES
from ..reference import nemotron_h_ref


def config(model: dict):
    nemotron_h_ref._check(model)
    if not hasattr(_gpt, "SsmMixer"):   # a program from before PR 40
        raise ValueError(
            "family nemotron_h needs a program whose GPTConfig says a layer "
            "of one sublayer by a pattern and a mixer's sizes (models/gpt.py:"
            " layer_pattern, ssm; models/ssm.py); this one has neither")
    mixer = _gpt.SsmMixer(
        heads=model["mamba_num_heads"], head_dim=model["mamba_head_dim"],
        state=model["ssm_state_size"], groups=model["n_groups"],
        conv=model["conv_kernel"], chunk=int(model.get("chunk_size", 128)),
        dt_min=float(model.get("time_step_min", 0.001)),
        dt_max=float(model.get("time_step_max", 0.1)),
        dt_floor=float(model.get("time_step_floor", 1e-4)))
    return _gpt.GPTConfig(
        vocab_size=model["vocab_size"], n_layer=model["n_layer"],
        n_head=model["n_head"], d_model=model["d_model"],
        max_seq_len=model["max_seq_len"], rotary=True, rotary_pct=0.0,
        tie_embeddings=False, activation="relu2",
        layer_norm_eps=model["rms_norm_eps"], norm="rmsnorm",
        mlp_gated=False, linear_bias=False,
        linear_out_float32=bool(model.get("linear_out_float32")),
        stream_float32=bool(model.get("stream_float32")),
        attn_kind="gqa", n_kv_head=model["n_kv_head"],
        head_width=model["head_dim"],
        layer_pattern=model["hybrid_pattern"], ssm=mixer,
        moe_experts=model["n_routed_experts"],
        moe_held=tuple(model["held_experts"]), moe_k=model["k"],
        moe_d_ff=model["moe_d_ff"], moe_shared_d_ff=model["shared_d_ff"],
        moe_scale=float(model["routed_scaling_factor"]), moe_norm_topk=True,
        moe_score="sigmoid", moe_score_bias=True,
        moe_two_pass=bool(model.get("experts_two_pass")),
        attn_float32=bool(model.get("attention_float32")),
        # tools/compile_only.py says which attention to lower
        use_flash=model.get("use_flash"))


def module(cfg):
    return _gpt.build(cfg)[0]


def init_params(cfg, key):
    return _gpt.init_params(cfg, key, dtype=jnp.bfloat16)


def paged_decode_step(cfg, params, tokens, cache, tables, lengths, impl=None):
    """(logits [slots, V], the cache, what the reference is handed [slots,
    n_layer, k] int32) of the program's own step over the comparison's
    slots, the first ``SEQUENCES`` rows: ``ssm_decode`` writing their states
    and windows as in the timed programs, ``paged_decode_gqa`` over the
    engine's pool. Rows past them come back zero.

    The third value, a layer a row: a routed layer's, the experts the step
    chose; an attention layer's, -1; a mixer layer's, the ``k`` readings of
    the state and the window the step LEFT for the slot
    (``nemotron_h_ref.read_state``: sums under seeded patterns of signs),
    float32 in int32's bits. The reference reads its own recurrence the same
    way at that position and holds the distance to ``STATE_TOL``: the
    comparison has no other way to the states, and under the seeding the
    configuration states the logits do not show them.

    The comparison makes this step and then has the engine decode the same
    token at the same position (``lib/correct.serve_whole``: "the row it
    writes is the row the next ``engine.decode`` writes again in place"):
    true of keys and values, not of a state, which would absorb the token
    twice. So the step runs on a copy of the comparison's slots' states and
    windows (a few slots: the whole stacks are 4.6 GB at 512) and the stacks
    go back as they came; the pool it writes as every family's does. Putting
    the slots back into the stacks after a step over all of them was tried
    first and is not done: the chip's compiler read the slots to put back
    from the donated stack AFTER the kernel had written it (the scheduled
    HLO: ``slice-start`` of the parameter behind the layer loop), so that
    every check sequence absorbed its token twice (my chip run, PERF.md PR
    40)."""
    rows = tokens.shape[0]
    n = min(SEQUENCES, rows)
    own = dict(cache, **{name: cache[name][:, :n] for name in _gpt.SSM_KEYS})
    logits, own, (chosen, _) = _gpt.paged_decode_step(
        cfg, params, tokens[:n], own, tables[:n], lengths[:n], impl=impl,
        return_routing=True)
    probes = nemotron_h_ref.state_probes(
        {"mamba_num_heads": cfg.ssm.heads, "mamba_head_dim": cfg.ssm.head_dim,
         "ssm_state_size": cfg.ssm.state, "n_groups": cfg.ssm.groups,
         "conv_kernel": cfg.ssm.conv, "k": cfg.moe_k})
    readings = jax.vmap(jax.vmap(
        lambda s, w: nemotron_h_ref.read_state(probes, s, w)))(
            *(own[name] for name in _gpt.SSM_KEYS))            # [L, n, k]
    mixers = [l for l, kind in enumerate(cfg.layer_pattern) if kind == "M"]
    chosen = chosen.at[:, jnp.asarray(mixers)].set(jnp.moveaxis(
        jax.lax.bitcast_convert_type(readings, jnp.int32), 0, 1))
    cache = dict(own, **{name: cache[name] for name in _gpt.SSM_KEYS})

    def padded(a):
        return jnp.zeros((rows,) + a.shape[1:], a.dtype).at[:n].set(a)
    return padded(logits), cache, padded(chosen)
