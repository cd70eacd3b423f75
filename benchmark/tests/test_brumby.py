"""The Brumby files: the reference's quadratic form against a second, naive
writing of the same mixer in numpy as a RECURRENCE over the symmetric feature
map (loops over tokens and heads; the reference builds neither), the
readings of a state against that recurrence's own state, the retention
kernel's cost, the readers on a run with nothing to read and on a hand-made
one. (The reference's counts at the published widths and the configuration's
arithmetic are ``tests/test_brumby_model.py``'s; the family through ``run.py``
is ``test_rehearsal.py``'s: it runs ``tiny-brumby-serve.tiny-closed`` as every
rehearsal cell.)"""

import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark.lib import kernel_cost_retention, manifest
from benchmark.lib.peaks import device_peaks
from benchmark.reference import brumby_ref as ref

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _config(name):
    with open(os.path.join(ROOT, "configs", f"{name}.json")) as f:
        return json.load(f)


TINY = _config("tiny-brumby-serve")["model"]


def _symmetric(a):
    """The symmetric second power of ``a`` [D]: squares, and sqrt 2 times
    each product of two different coordinates; D (D + 1) / 2 wide."""
    i, j = np.triu_indices(len(a))
    return np.where(i == j, 1.0, np.sqrt(2.0)) * a[i] * a[j]


def test_the_quadratic_form_is_the_recurrence_over_the_symmetric_map():
    """``mixer`` (scores, a running sum of log gates, a quotient) against the
    state ``S_t = g_t S_{t-1} + phi(k_t) v_t^T`` and the normaliser ``z_t``
    kept token by token a key-value head, 36 wide at a head of 8; and the
    readings of the last position against ``phi(a)^T [S | z] u`` of that
    state."""
    rng = np.random.default_rng(0)
    t, d = 13, TINY["d_model"]
    H, G, D = TINY["n_head"], TINY["n_kv_head"], TINY["head_dim"]
    h = rng.standard_normal((t, d)).astype(np.float32)
    w = {"retention_q_w": rng.standard_normal((d, H * D)) * 0.3,
         "retention_kv_w": rng.standard_normal((d, 2 * G * D)) * 0.3,
         "retention_gate_w": rng.standard_normal((d, G)) * 0.3,
         "retention_q_norm_scale": 1 + 0.1 * rng.standard_normal(D),
         "retention_k_norm_scale": 1 + 0.1 * rng.standard_normal(D),
         "retention_out_w": rng.standard_normal((H * D, d)) * 0.3}
    w = {k: v.astype(np.float32) for k, v in w.items()}
    probes = ref.state_probes(TINY)
    with jax.default_matmul_precision("highest"):
        got, readings, sizes = ref.mixer(
            TINY, jnp.asarray(h), {k: jnp.asarray(v) for k, v in w.items()},
            probes, jnp.asarray([t - 1]))

    def normed(a, gain):
        return a / np.sqrt((a * a).mean(-1, keepdims=True)
                           + TINY["rms_norm_eps"]) * gain

    def rotated(a):     # [t, heads, D]
        half = D // 2
        freq = 1.0 / TINY["rope_theta"] ** (np.arange(half) / half)
        ang = np.arange(t)[:, None] * freq
        c, s = np.cos(ang)[:, None], np.sin(ang)[:, None]
        return np.concatenate([a[..., :half] * c - a[..., half:] * s,
                               a[..., half:] * c + a[..., :half] * s], -1)

    q = rotated(normed((h @ w["retention_q_w"]).reshape(t, H, D),
                       w["retention_q_norm_scale"]))
    kv = (h @ w["retention_kv_w"]).reshape(t, 2, G, D)
    k = rotated(normed(kv[:, 0], w["retention_k_norm_scale"]))
    v = kv[:, 1]
    gate = 1.0 / (1.0 + np.exp(-(h @ w["retention_gate_w"]
                                 + TINY["gate_offset"])))
    F = D * (D + 1) // 2
    S, z = np.zeros((G, F, D)), np.zeros((G, F))
    o = np.zeros((t, H, D))
    for s in range(t):
        for g in range(G):
            f = _symmetric(k[s, g])
            S[g] = gate[s, g] * S[g] + f[:, None] * v[s, g][None, :]
            z[g] = gate[s, g] * z[g] + f
        for i in range(H):
            f = _symmetric(q[s, i])
            o[s, i] = (f @ S[i // (H // G)]) / (f @ z[i // (H // G)])
    want = o.reshape(t, H * D) @ w["retention_out_w"]
    assert np.abs(np.asarray(got) - want).max() < 2e-4 * np.abs(want).max()
    own = np.zeros(ref.READINGS)
    for j in range(ref.READINGS):
        f = _symmetric(probes["a"][j])
        for g in range(G):
            own[j] += probes["head"][j, g] * (
                f @ S[g] @ probes["value"][j, :D]
                + probes["value"][j, D] * (f @ z[g]))
    assert np.abs(np.asarray(readings)[0] - own).max() < 1e-4 * np.abs(
        own).max()
    assert (np.asarray(sizes) > 0).all()


def test_the_retention_kernel_is_bound_by_its_bytes():
    cost = kernel_cost_retention.retention_decode(40, 8, 5, 128)
    peaks = device_peaks("TPU v5 lite")
    assert kernel_cost_retention.features(128) == 8256
    # 40 slots x 8 heads x 8256 x 129 float32, read and written
    assert abs(cost.bytes - 2 * 40 * 34_080_768) < 0.002 * cost.bytes
    assert cost.bound(peaks) == "bytes" and cost.flops / cost.bytes < 2
    assert 3.2e-3 < cost.floor_s(peaks) < 3.4e-3


class _Nothing:
    trace = None
    model = {}
    device_kind = "TPU v5 lite"


@pytest.mark.parametrize("reader, params", [
    ("prog_roofline_retention", {"kernel": "retention_decode"})])
def test_a_new_reader_reads_nothing_where_nothing_is(reader, params,
                                                     monkeypatch):
    from benchmark.lib import program_trace

    monkeypatch.setattr(program_trace, "of", lambda ctx: None)
    assert manifest.plugin("readers", reader).read(_Nothing(), params) is None


class _Span:
    def __init__(self, **stats):
        self.stats = stats


class _Trace:
    reduced = object()

    def __init__(self, spans):
        self.spans = spans

    def named(self, name):
        return self.spans


def _ctx():
    ctx = _Nothing()
    ctx.cell = {"config_file": _config("brumby-14b-serve")}
    ctx.model = ctx.cell["config_file"]["model"]
    ctx.count = lambda name: getattr(ref, name)
    return ctx


def test_the_roofline_reader_counts_the_live_slots_states(monkeypatch):
    """A trace with the kernel and the program's counts: five layers, each
    live slot's state read and written once a step; a parent's spans, without
    the counts, give nothing; so does another family's model."""
    from benchmark.lib import program_trace
    from benchmark.readers import prog_roofline_retention as reader

    ctx = _ctx()
    monkeypatch.setattr(reader, "_time_and_calls",
                        lambda pt, pattern: (0.2, 40))
    spans = [_Span(steps=4, active=38, state_slots=38, live_kv_tokens=9),
             _Span(steps=4, active=40, state_slots=40, live_kv_tokens=9)]
    monkeypatch.setattr(program_trace, "of", lambda c: _Trace(spans))
    got = reader.read(ctx, {"kernel": "retention_decode"})
    one = kernel_cost_retention.retention_decode(1.0, 8, 5, 128).bytes
    peaks = device_peaks("TPU v5 lite")
    want = 100 * 5 * 4 * (38 + 40) * one / peaks.hbm_bytes_per_s / 0.2
    assert abs(got - want) < 1e-6 and 60 < got < 70
    monkeypatch.setattr(program_trace, "of", lambda c: _Trace(
        [_Span(steps=4, active=38, live_kv_tokens=9)]))
    assert reader.read(ctx, {"kernel": "retention_decode"}) is None
    monkeypatch.setattr(program_trace, "of", lambda c: _Trace(spans))
    ctx.model = _config("kimi-linear-48b-serve")["model"]
    assert reader.read(ctx, {"kernel": "retention_decode"}) is None


def test_the_state_bandwidth_reader_counts_the_retention_states(monkeypatch):
    from benchmark.lib import program_trace
    from benchmark.readers import decode_state_bw_util

    class Reduced:
        busy_in_span = {"decode": 0.06}

    ctx = _ctx()
    ctx.trace = Reduced()
    spans = [_Span(steps=2, active=40, state_slots=40,
                   live_kv_tokens=200_000)]
    monkeypatch.setattr(program_trace, "of", lambda c: _Trace(spans))
    got = decode_state_bw_util.read(ctx, {})
    need = 2 * ref.decode_step_bytes(ctx.model, 0, state_slots=40, active=40)
    peaks = device_peaks("TPU v5 lite")
    assert abs(got - 100 * need / peaks.hbm_bytes_per_s / 0.06) < 1e-9
    # 4.86 GB of weights and head, 13.63 of states; no token caches a row
    assert 18.4e9 < need / 2 < 18.6e9
    assert ref.decode_step_bytes(ctx.model, 10**9) == \
        ref.decode_step_bytes(ctx.model, 0)

