"""``ops/pallas/retention_chunk``: the chunked form of a prompt's power
retention with a key-value head's state in VMEM, in interpret mode on the CPU
against the plain form it stands in for (``models/retention.scan_chunks``)
and against the benchmark's reference, which runs the mixer as the quadratic
form over a whole sequence and never builds the feature map
(``benchmark/reference/brumby_ref.py``); how ``mix_sequence`` chooses between
the two; the counter the choice brings (``trace.RETENTION_STATS``) and the two
metrics that read it and the scope. Small heads (8 and 16), seconds.
"""

import json
import os
import types

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark.families import brumby as family
from benchmark.lib import manifest
from benchmark.readers import prog_span_ratio
from benchmark.reference import brumby_ref as ref
from deepspeed_tpu.models import gpt as G
from deepspeed_tpu.models import retention
from deepspeed_tpu.ops.pallas import retention_chunk as rc
from deepspeed_tpu.profiling import trace
from served_contract import config_file

MODEL = config_file("tiny-brumby-serve")["model"]
CELL = "brumby-14b-serve.many-shot"

# (query heads, key-value heads, head_dim, chunk), rows' real tokens of T,
# the state the prompt starts from, and the dispatches it arrives in
CASES = {
    "a zero state, whole chunks": ((4, 2, 8, 8), [32], 32, "zero", 1),
    "a given state, T no whole chunks": ((4, 2, 8, 8), [21], 21, "given", 1),
    "a prompt shorter than a chunk": ((4, 2, 8, 16), [5], 5, "zero", 1),
    "a padded row beside one with no real token":
        ((4, 2, 8, 8), [19, 0], 24, "given", 1),
    "two rows, different real": ((4, 2, 8, 8), [24, 7], 24, "zero", 1),
    "groups of 5, heads of 16, from a given state":
        ((5, 1, 16, 8), [24], 24, "given", 1),
    "three dispatches, a zero state": ((4, 2, 8, 8), [60], 60, "zero", 3),
    "three dispatches, a given state, two rows":
        ((4, 2, 16, 8), [45, 29], 48, "given", 3),
}


def _mixer(sizes):
    H, G_, D, chunk = sizes
    model = dict(MODEL, n_head=H, n_kv_head=G_, head_dim=D, chunk_size=chunk,
                 n_layer=1)
    cfg = family.config(model)
    w = jax.tree_util.tree_map(
        lambda a: a[0].astype(jnp.float32),
        family.init_params(cfg, jax.random.PRNGKey(3))["blocks"])
    return model, cfg, w


def _mix(cfg, w, h, state, real, first, impl):
    """``mix_sequence`` as ``gpt`` calls it, positions from ``first``."""
    B, T, _ = h.shape
    window = None if state is None else jnp.zeros((B, 0, 0), jnp.float32)
    return retention.mix_sequence(
        cfg.retention, h, w, state, window, linear=G._wm,
        eps=cfg.layer_norm_eps, real=real,
        positions=first + jnp.broadcast_to(jnp.arange(T), (B, T)),
        rotate=G._mixer_rotate(cfg), impl=impl)[:2]


def _given_state(m, rows, key):
    """A state as a prompt leaves it: ``S`` and ``z`` of the same keys, the
    normaliser's block zero past its rows (``join_state``)."""
    k, v = jax.random.normal(key, (2, rows, 6, m.kv_heads, m.head_dim))
    fk = retention.phi(k)                               # [rows, 6, G, e, D]
    return retention.join_state(
        m, jnp.einsum("bsgei,bsgv->bgevi", fk, v), fk.sum(axis=1))


def _close(a, b, tol=1e-5):
    a, b = np.asarray(a), np.asarray(b)
    assert np.abs(a - b).max() <= tol * max(np.abs(b).max(), 1.0)


@pytest.mark.parametrize("case", list(CASES))
def test_the_kernel_is_the_chunked_form_and_the_quadratic_form(case):
    """Outputs at the real positions and the state the last real token left:
    the kernel's are ``scan_chunks``', in ``join_state``'s layout; from a
    zero state a row's outputs are the reference's quadratic form; a prompt
    in three dispatches is the prompt in one."""
    sizes, real, T, start, pieces = CASES[case]
    model, cfg, w = _mixer(sizes)
    m, B = cfg.retention, len(real)
    keys = jax.random.split(jax.random.PRNGKey(len(case)), 2)
    h = jax.random.normal(keys[0], (B, T, cfg.d_model))
    state = None if start == "zero" else _given_state(m, B, keys[1])
    real = jnp.asarray(real, jnp.int32)
    is_real = np.arange(T)[None, :, None] < np.asarray(real)[:, None, None]
    plain, plain_state = _mix(cfg, w, h, state, real, 0, "plain")
    out, new = _mix(cfg, w, h, state, real, 0, "kernel")
    _close(np.where(is_real, out, 0), np.where(is_real, plain, 0))
    _close(new, plain_state)
    assert new.shape == (B,) + m.state_shape()
    S, z = retention.split_state(m, new)
    assert (np.asarray(new) == np.asarray(retention.join_state(m, S, z))).all()
    if state is not None and int(real[-1]) == 0:
        assert (np.asarray(new[-1]) == np.asarray(state[-1])).all()
    if start == "zero":
        probes = ref.state_probes(model)
        for b in range(B):
            n = int(real[b])
            want = ref.mixer(model, h[b, :n], w, probes,
                             jnp.asarray([n - 1]))[0]
            _close(out[b, :n], want, 2e-5)
    if pieces == 1:
        return
    step, got = T // pieces, []
    for at in range(0, T, step):
        left = jnp.clip(real - at, 0, step)
        o, state = _mix(cfg, w, h[:, at:at + step], state, left, at, "kernel")
        got.append(o)
    _close(np.where(is_real, jnp.concatenate(got, axis=1), 0),
           np.where(is_real, out, 0))
    _close(state, new)


@pytest.mark.parametrize("sizes, T, takes", [
    ((40, 8, 128, 128), 2048, True), ((40, 8, 128, 128), 128, True),
    ((40, 8, 128, 128), 64, False), ((40, 8, 64, 128), 2048, False),
    ((4, 2, 8, 4), 32, False), ((72, 8, 128, 128), 2048, False)],
    ids=["brumby's chunk program", "one chunk", "a prompt of half a chunk",
         "heads of half the lanes", "tiny-brumby", "nine query heads a state"])
def test_the_tiles_take_whole_lanes_and_at_most_eight_query_heads(
        sizes, T, takes):
    H, G_, D, chunk = sizes
    assert rc._fits(D, min(chunk, -(-T // 8) * 8), H // G_) is takes


def test_no_impl_is_the_plain_form_off_the_chip_and_for_other_shapes(
        monkeypatch):
    """``impl=None``: off the TPU the plain program, whatever the shape; on
    one (the backend's name patched: nothing runs) the kernel where the tiles
    take the shape and the plain form where they do not."""
    _, cfg, w = _mixer((4, 2, 8, 8))
    h = jnp.zeros((1, 16, cfg.d_model))

    def stats(cfg, w, h):
        return trace.kernel_stats(jax.make_jaxpr(
            lambda h: _mix(cfg, w, h, None, None, 0, None))(h),
            trace.RETENTION_STATS)

    assert stats(cfg, w, h) == {"retention_scans": 1, "retention_kernel": 0}
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert stats(cfg, w, h) == {"retention_scans": 1, "retention_kernel": 0}
    _, cfg, w = _mixer((2, 1, 128, 128))
    h = jnp.zeros((1, 256, cfg.d_model))
    assert stats(cfg, w, h) == {"retention_scans": 1, "retention_kernel": 1}
    with pytest.raises(ValueError, match="impl must be"):
        _mix(cfg, w, h, None, None, 0, "gather")


@pytest.mark.parametrize("impl, kernel", [("kernel", 1), ("plain", 0)])
def test_retention_stats_count_a_program_s_chunked_forms(impl, kernel):
    """``trace.RETENTION_STATS``: one chunked form a layer, a scan's body
    once a trip; the kernel by its name, the plain form by its program's."""
    _, cfg, w = _mixer((4, 2, 8, 8))

    def layers(h):
        def body(h, _):
            return h + _mix(cfg, w, h, None, None, 0, impl)[0], None
        return jax.lax.scan(body, h, None, length=3)[0]

    jaxpr = jax.make_jaxpr(layers)(jnp.zeros((1, 16, cfg.d_model)))
    assert trace.RETENTION_STATS == ("retention_scans", "retention_kernel")
    assert trace.kernel_stats(jaxpr, trace.RETENTION_STATS) == {
        "retention_scans": 3, "retention_kernel": 3 * kernel}


def test_the_prefill_spans_of_a_retention_model_carry_the_stats():
    """The fused, batch and chunk spans of an engine whose config sets
    ``retention`` say ``RETENTION_STATS`` (one chunked form a layer, the
    plain one off the chip); another model's spans say what they said."""
    from deepspeed_tpu.inference.serving import ServingConfig, ServingEngine

    def spans(cfg, params, prompts):
        engine = ServingEngine(cfg, params, ServingConfig(
            num_slots=3, num_pages=12, page_size=16, max_model_len=128,
            prefill_chunk=32, dtype="float32"))
        since = len(trace.recorded())
        tables = np.arange(1, 9, dtype=np.int32).reshape(1, 8)
        engine.prefill_many([(slot, np.arange(1, n + 1), tables[0])
                             for slot, n in enumerate(prompts)])
        return {e.name: e.counts for e in trace.recorded()[since:]
                if e.name.startswith("engine.prefill.")
                and "real_tokens" in (e.counts or {})}

    cfg = family.config(MODEL)
    got = spans(cfg, family.init_params(cfg, jax.random.PRNGKey(0)),
                [70, 9, 20])
    assert sorted(got) == ["engine.prefill.batch", "engine.prefill.chunk"]
    for stats in got.values():
        assert {k: stats[k] for k in trace.RETENTION_STATS} == {
            "retention_scans": cfg.n_layer, "retention_kernel": 0}
    got.update(spans(cfg, family.init_params(cfg, jax.random.PRNGKey(0)),
                     [20]))
    assert got["engine.prefill.fused"]["retention_scans"] == cfg.n_layer
    plain = G.GPTConfig(vocab_size=256, n_layer=2, n_head=4, d_model=32,
                        max_seq_len=128)
    for stats in spans(plain, G.init_params(plain, jax.random.PRNGKey(0)),
                       [70, 9, 20]).values():
        assert not set(trace.RETENTION_STATS) & set(stats)


# --------------------------------------------------- the two metric files
NEW = {"retention_scan_ms_per_ktok": ("prog_scope_per", "device_trace"),
       "retention_kernel_pct": ("prog_span_ratio", "program_counter")}


@pytest.mark.parametrize("name", list(NEW))
def test_a_new_metric_names_a_reader_and_is_listed_for_many_shot_alone(name):
    metric = manifest.load_metric(name)
    reader, source = NEW[name]
    assert (metric["reader"], metric["source"]) == (reader, source)
    assert hasattr(manifest.plugin("readers", reader), "read")
    with open(os.path.join(manifest.CHECKOUT, "BENCHMARK.json")) as f:
        listed = [e for e in json.load(f)["per_layer"] if e["name"] == name]
    assert listed == [{
        "name": name, "unit": metric["unit"], "better": metric["better"],
        "source": source, "layer": metric["layer"], "moves": "out_tok_s",
        "workloads": [CELL]}]
    assert name in manifest.load_cell(CELL)["per_layer"]
    params = metric["params"]
    if reader == "prog_scope_per":
        # the accepted metric's parameters, one scope further in
        accepted = manifest.load_metric("retention_prefill_ms_per_ktok")
        assert params == dict(accepted["params"], scope="retention_scan")
        assert "retention_scan" in trace.MODEL_SCOPES
    else:
        assert params == {"span": trace.ENGINE_PREFILL_CHUNK,
                          "of": "retention_kernel", "over": "retention_scans"}
        assert (params["over"], params["of"]) == trace.RETENTION_STATS


def test_the_ratio_reads_100_where_every_chunked_form_is_the_kernel(
        monkeypatch):
    params = manifest.load_metric("retention_kernel_pct")["params"]
    span = types.SimpleNamespace(stats={
        "real_tokens": 2048, "retention_scans": 5, "retention_kernel": 5})
    pt = types.SimpleNamespace(named=lambda name: {
        trace.ENGINE_PREFILL_CHUNK: [span, span]}.get(name, []))
    monkeypatch.setattr(prog_span_ratio.program_trace, "of", lambda ctx: pt)
    assert prog_span_ratio.read(None, params) == 100.0
    span.stats["retention_kernel"] = 0
    assert prog_span_ratio.read(None, params) == 0.0
    span.stats = {"real_tokens": 2048}      # the parent's span: no stat
    assert prog_span_ratio.read(None, params) is None
