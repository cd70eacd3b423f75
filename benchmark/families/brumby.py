"""Family ``brumby``: Brumby-14B-Base's layers on the program's normal path,
``deepspeed_tpu/models/gpt.py`` with power retention
(``models/retention.py``: a normalised linear attention through a feature
map, 40 query heads over 8 states of 128, queries and keys normed a head and
rotated at base 1e6) as the mixer of EVERY layer (``GPTConfig.retention``) and
a dense gated MLP after it, said as data; a state a decode slot and no page
anywhere in the one cache tree (the pool has no layers);
``retention_decode``. ``reference/brumby_ref.py`` has the equations as the
quadratic form and the parameter tree; ``init_params`` here makes that tree.

``config(model)`` takes the ``model`` group of a configuration file in the
names ``brumby_ref`` reads and refuses what the reference refuses. The group's
``chunk_size`` (the prompt form's chunk, the program's alone),
``linear_out_float32`` and ``stream_float32`` (absent: false) are
``GPTConfig``'s.

The reference holds every layer's state through the readings the step hands
over (its docstring says why no logit shows a state's precision, nor a gate
applied on the wrong side of the write), so ``paged_decode_step`` returns
them third, int32 ``[slots, n_layer, k]``: float32 in int32's bits,
:func:`read_state` of the state the step LEFT for the slot, through the
program's own feature map. The model has no router; the third value is the
comparison's one channel beside the logits.

``init_params`` is the seeded draw the configuration states under
``assumed``: the program's own tree (``gpt.init_params``: N(0, 0.02) rounded
to bf16 as drawn, 0.02 / sqrt(2 n_layer) for the projections into the stream,
the gains ones). The gate's constant ``gate_offset`` is the configuration's,
not a leaf.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from deepspeed_tpu.models import gpt as _gpt
from deepspeed_tpu.models.gpt import init_cache  # noqa: F401

from ..lib.correct import SEQUENCES
from ..lib.manifest import ManifestError
from ..reference import brumby_ref


def config(model: dict):
    brumby_ref._check(model)
    if not hasattr(_gpt, "RetentionMixer"):   # a program from before PR 58
        raise ManifestError(
            "family brumby needs a program whose GPTConfig says a power "
            "retention mixer in every layer and a cache without pages "
            "(models/gpt.py: retention; models/retention.py); this one has "
            "neither")
    mixer = _gpt.RetentionMixer(
        heads=model["n_head"], kv_heads=model["n_kv_head"],
        head_dim=model["head_dim"], chunk=int(model.get("chunk_size", 128)),
        gate_offset=float(model["gate_offset"]))
    return _gpt.GPTConfig(
        vocab_size=model["vocab_size"], n_layer=model["n_layer"],
        n_head=model["n_head"], d_model=model["d_model"], d_ff=model["d_ff"],
        max_seq_len=model["max_seq_len"], rotary=True, rotary_pct=1.0,
        rope_theta=float(model["rope_theta"]), tie_embeddings=False,
        activation="silu", layer_norm_eps=model["rms_norm_eps"],
        norm="rmsnorm", mlp_gated=True, linear_bias=False,
        linear_out_float32=bool(model.get("linear_out_float32")),
        stream_float32=bool(model.get("stream_float32")), retention=mixer,
        # tools/compile_only.py says which attention to lower
        use_flash=model.get("use_flash"))


def module(cfg):
    return _gpt.build(cfg)[0]


def init_params(cfg, key):
    return _gpt.init_params(cfg, key, dtype=jnp.bfloat16)


def read_state(cfg, probes, state):
    """The ``k`` readings (``brumby_ref``, "a layer's row is its state's
    readings") of one slot's ``state`` as the program keeps it, ``[G, D / 2 +
    2, D, D]``: ``sum_g e_jg phi(a_j)^T [S_g | z_g] u_j`` through the
    program's own ``phi``, float32 [k]."""
    from deepspeed_tpu.models import retention

    exact = jax.lax.Precision.HIGHEST   # a served step's default is bf16
    S, z = retention.split_state(cfg.retention, state)
    f = retention.phi(jnp.asarray(probes["a"]))                 # [k, e, D]
    D = cfg.retention.head_dim
    of_s = jnp.einsum("jei,gevi,jv,jg->j", f, S, probes["value"][:, :D],
                      probes["head"], precision=exact)
    of_z = jnp.einsum("jei,gei,jg->j", f, z, probes["head"], precision=exact)
    return of_s + probes["value"][:, D] * of_z


def paged_decode_step(cfg, params, tokens, cache, tables, lengths, impl=None):
    """(logits [slots, V], the cache, every layer's state readings [slots,
    n_layer, k] int32) of the program's own step over the comparison's
    slots, the first ``SEQUENCES`` rows: ``retention_decode`` writing their
    states as in the timed programs. Rows past them come back zero.

    The comparison makes this step and then has the engine decode the same
    token at the same position (``lib/correct.serve_whole``): true of keys
    and values, not of a state, which would absorb the token twice. So the
    step runs on a copy of the comparison's slots' states (a few slots of the
    stack) and the stack goes back as it came, as ``families/falcon_h1.py``'s
    does and for its reasons."""
    rows = tokens.shape[0]
    n = min(SEQUENCES, rows)
    own = dict(cache, **{name: cache[name][:, :n] for name in _gpt.SSM_KEYS})
    logits, own = _gpt.paged_decode_step(
        cfg, params, tokens[:n], own, tables[:n], lengths[:n], impl=impl)
    probes = brumby_ref.state_probes(
        {"n_kv_head": cfg.retention.kv_heads,
         "head_dim": cfg.retention.head_dim})
    readings = jax.vmap(jax.vmap(lambda s: read_state(cfg, probes, s)))(
        own[_gpt.SSM_KEYS[0]])                                  # [L, n, k]
    handed = jnp.moveaxis(
        jax.lax.bitcast_convert_type(readings, jnp.int32), 0, 1)
    cache = dict(own, **{name: cache[name] for name in _gpt.SSM_KEYS})

    def padded(a):
        return jnp.zeros((rows,) + a.shape[1:], a.dtype).at[:n].set(a)
    return padded(logits), cache, padded(handed)
