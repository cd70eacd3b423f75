"""Family ``falcon_h1``: Falcon-H1's layers on the program's normal path,
``deepspeed_tpu/models/gpt.py`` with a layer whose attention (4 key-value
heads for 20 query heads of 128, rotated over the whole head at base 1e11)
and Mamba-2 mixer (``models/ssm.py``) read the same normed input side by
side (``GPTConfig.ssm`` and no ``layer_pattern``), a dense gated MLP after
them and the muP multipliers as one value (``gpt.Multipliers``), said as
data; pages AND a state a decode slot in every layer; ``paged_decode_gqa``
and ``ssm_decode``. ``reference/falcon_h1_ref.py`` has the equations and the
parameter tree; ``init_params`` here makes that tree.

``config(model)`` takes the ``model`` group of a configuration file in the
names ``falcon_h1_ref`` reads and refuses what the reference refuses. The
group's ``chunk_size`` (the scan's chunk, the program's alone),
``time_step_min`` / ``_max`` / ``_floor`` (the seeded ``dt_bias``),
``linear_out_float32`` and ``stream_float32`` (absent: false) are
``GPTConfig``'s.

The reference holds every layer's state through the readings the step hands
over (its docstring says why no logit shows a state's precision), so
``paged_decode_step`` returns them third, int32 ``[slots, n_layer, k]``:
float32 in int32's bits, ``falcon_h1_ref.read_state`` of the state and the
window the step LEFT for the slot. The model has no router; the third value
is the comparison's one channel beside the logits.

``init_params`` is the seeded draw the configuration states under
``assumed``: the program's own tree (``gpt.init_params``: N(0, 0.02) rounded
to bf16 as drawn, 0.02 / sqrt(2 n_layer) for the projections into the stream;
``A_log = log(U[1, 16])``, ``dt_bias`` the inverse softplus of a log-uniform
draw in [time_step_min, time_step_max] floored at time_step_floor, ``D`` and
the gains ones), then every matrix divided by the multipliers that follow
it, so that the product of a matrix and its multipliers is what N(0, 0.02)
gives without them (at N(0, 0.02) the published multipliers silence whole
paths: ``key_multiplier`` 0.011 leaves the scores near 0, attention a running
mean), and the convolution's taps U(-1 / sqrt(K), 1 / sqrt(K)), what a
depthwise ``Conv1d`` starts from, under which the state carries a share of
``y`` that the logits show. No router follows, so nothing flips on it.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from deepspeed_tpu.models import gpt as _gpt
from deepspeed_tpu.models import ssm as _ssm
from deepspeed_tpu.models.gpt import init_cache  # noqa: F401

from ..lib.correct import SEQUENCES
from ..lib.manifest import ManifestError
from ..reference import falcon_h1_ref


def config(model: dict):
    falcon_h1_ref._check(model)
    if not hasattr(_gpt, "Multipliers"):   # a program from before PR 47
        raise ManifestError(
            "family falcon_h1 needs a program whose GPTConfig says a layer "
            "with attention and a state-space mixer side by side and the "
            "muP multipliers (models/gpt.py: ssm without layer_pattern, "
            "multipliers); this one has neither")
    mixer = _gpt.SsmMixer(
        heads=model["mamba_num_heads"], head_dim=model["mamba_head_dim"],
        state=model["ssm_state_size"], groups=model["n_groups"],
        conv=model["conv_kernel"], chunk=int(model.get("chunk_size", 128)),
        dt_min=float(model.get("time_step_min", 0.001)),
        dt_max=float(model.get("time_step_max", 0.1)),
        dt_floor=float(model.get("time_step_floor", 1e-4)))
    gate, down = model["mlp_multipliers"]
    return _gpt.GPTConfig(
        vocab_size=model["vocab_size"], n_layer=model["n_layer"],
        n_head=model["n_head"], d_model=model["d_model"], d_ff=model["d_ff"],
        max_seq_len=model["max_seq_len"], rotary=True, rotary_pct=1.0,
        rope_theta=float(model["rope_theta"]), rotary_float32=True,
        tie_embeddings=False, activation="silu",
        layer_norm_eps=model["rms_norm_eps"], norm="rmsnorm",
        mlp_gated=True, linear_bias=False,
        linear_out_float32=bool(model.get("linear_out_float32")),
        stream_float32=bool(model.get("stream_float32")),
        attn_kind="gqa", n_kv_head=model["n_kv_head"],
        head_width=model["head_dim"], ssm=mixer,
        multipliers=_gpt.Multipliers(
            embed=model["embedding_multiplier"],
            head=model["lm_head_multiplier"],
            attn_in=model["attention_in_multiplier"],
            key=model["key_multiplier"],
            attn_out=model["attention_out_multiplier"],
            ssm_in=model["ssm_in_multiplier"],
            ssm=tuple(model["ssm_multipliers"]),
            ssm_out=model["ssm_out_multiplier"],
            mlp_gate=gate, mlp_down=down),
        # tools/compile_only.py says which attention to lower
        use_flash=model.get("use_flash"))


def module(cfg):
    return _gpt.build(cfg)[0]


def init_params(cfg, key):
    """The seeded draw (module docstring): the program's tree, each matrix
    over the multipliers that follow it, the taps as a ``Conv1d``'s."""
    params = _gpt.init_params(cfg, key, dtype=jnp.bfloat16)
    m, mix = cfg.multipliers, cfg.ssm
    blocks = dict(params["blocks"])

    def over(leaf, by):
        return (leaf.astype(jnp.float32) / by).astype(leaf.dtype)

    keys = cfg.n_kv_head * cfg.head_dim
    columns = {
        "q_w": m.attn_in,
        "kv_w": jnp.repeat(jnp.asarray([m.attn_in * m.key, m.attn_in]),
                           keys, total_repeat_length=2 * keys),
        "attn_out_w": m.attn_out,
        "ssm_in_w": m.ssm_in * _ssm.segments(mix, m.ssm),
        "ssm_out_w": m.ssm_out, "mlp_gate_w": m.mlp_gate,
        "mlp_down_w": m.mlp_down}
    for name, by in columns.items():
        blocks[name] = over(blocks[name], by)
    taps = blocks["ssm_conv_w"]
    bound = 1.0 / math.sqrt(mix.conv)
    blocks["ssm_conv_w"] = jax.random.uniform(
        jax.random.fold_in(key, 0x7A95), taps.shape, jnp.float32, -bound,
        bound).astype(taps.dtype)
    return dict(params, blocks=blocks, wte=over(params["wte"], m.embed),
                lm_head=over(params["lm_head"], m.head))


def paged_decode_step(cfg, params, tokens, cache, tables, lengths, impl=None):
    """(logits [slots, V], the cache, every layer's state readings [slots,
    n_layer, k] int32) of the program's own step over the comparison's
    slots, the first ``SEQUENCES`` rows: ``ssm_decode`` writing their states
    and windows as in the timed programs, ``paged_decode_gqa`` over the
    engine's pool. Rows past them come back zero.

    The comparison makes this step and then has the engine decode the same
    token at the same position (``lib/correct.serve_whole``: "the row it
    writes is the row the next ``engine.decode`` writes again in place"):
    true of keys and values, not of a state, which would absorb the token
    twice. So the step runs on a copy of the comparison's slots' states and
    windows (a few slots of the stacks) and the stacks go back as they
    came, as ``families/nemotron_h.py``'s does and for its reasons; the
    pool it writes as every family's does."""
    rows = tokens.shape[0]
    n = min(SEQUENCES, rows)
    own = dict(cache, **{name: cache[name][:, :n] for name in _gpt.SSM_KEYS})
    logits, own = _gpt.paged_decode_step(
        cfg, params, tokens[:n], own, tables[:n], lengths[:n], impl=impl)
    probes = falcon_h1_ref.state_probes(
        {"mamba_num_heads": cfg.ssm.heads, "mamba_head_dim": cfg.ssm.head_dim,
         "ssm_state_size": cfg.ssm.state, "n_groups": cfg.ssm.groups,
         "conv_kernel": cfg.ssm.conv})
    readings = jax.vmap(jax.vmap(
        lambda s, w: falcon_h1_ref.read_state(probes, s, w)))(
            *(own[name] for name in _gpt.SSM_KEYS))            # [L, n, k]
    handed = jnp.moveaxis(
        jax.lax.bitcast_convert_type(readings, jnp.int32), 0, 1)
    cache = dict(own, **{name: cache[name] for name in _gpt.SSM_KEYS})

    def padded(a):
        return jnp.zeros((rows,) + a.shape[1:], a.dtype).at[:n].set(a)
    return padded(logits), cache, padded(handed)
