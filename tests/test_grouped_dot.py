"""``ops/pallas/grouped_dot``: the grouped product over a stack of matrices as
a kernel of ours (interpret mode here), held to ``jax.lax.ragged_dot``; its
work list; and ``moe/dropless.held_experts_ffn`` through it on a DeepSeek-, a
Laguna- and a Nemotron-shaped layer."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.models import gpt as G
from deepspeed_tpu.moe import dropless
from deepspeed_tpu.ops.pallas import grouped_dot as gd

TILE = gd._ROW_TILE


def _operands(m, k, n, groups, dtype, seed=0):
    rng = np.random.default_rng(seed)
    a = jnp.asarray(rng.standard_normal((m, k)), dtype)
    w = jnp.asarray(rng.standard_normal((groups, k, n)) / np.sqrt(k), dtype)
    return a, w


def _tiles_of(sizes, tm):
    """Row tiles the non-empty groups have a row in, group by group."""
    ends = np.cumsum(sizes)
    return [(g, t) for g, (lo, hi) in enumerate(zip(ends - sizes, ends))
            if hi > lo for t in range(lo // tm, (hi - 1) // tm + 1)]


# name: (rows, sizes): what the routed cells' products meet
LAYOUTS = {
    "empty-groups-between-full-ones": (256, [0, 100, 0, 0, 60, 0, 96, 0]),
    "longer-than-a-tile-and-a-single-row": (
        512, [1, 3 * TILE + 5, 1, 0, 40]),
    "rows-past-the-last-group": (384, [30, 0, 70, 9]),
    "every-other-layers-groups-empty": (256, [0] * 8 + [16] * 8 + [0] * 16),
    "no-rows-at-all": (128, [0, 0, 0]),
    "fewer-rows-than-a-tile": (40, [0, 11, 0, 20, 2]),
    "rows-no-multiple-of-the-tile": (300, [128, 0, 128, 10]),
    "a-group-ends-on-a-tile-boundary": (256, [128, 64, 64]),
}


@pytest.mark.parametrize("dtype,out", [
    (jnp.bfloat16, jnp.float32), (jnp.bfloat16, jnp.bfloat16),
    (jnp.float32, jnp.float32), (jnp.bfloat16, None)],
    ids=["bf16-to-float32", "bf16-to-bf16", "float32", "bf16-to-its-own"])
@pytest.mark.parametrize("layout", sorted(LAYOUTS))
def test_kernel_meets_ragged_dot(layout, dtype, out):
    m, sizes = LAYOUTS[layout]
    a, w = _operands(m, 64, 256, len(sizes), dtype)
    sizes = jnp.asarray(sizes, jnp.int32)
    want = jax.lax.ragged_dot(a, w, sizes, preferred_element_type=out)
    got = jax.jit(functools.partial(
        gd.grouped_dot, preferred_element_type=out, impl="kernel"))(
            a, w, sizes)
    assert got.shape == want.shape and got.dtype == want.dtype
    rows = int(sizes.sum())         # the rows past them hold nothing to read
    tol = 1e-5 if got.dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got[:rows], np.float32),
                               np.asarray(want[:rows], np.float32),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("layout", sorted(LAYOUTS))
def test_work_list_is_the_tiles_of_the_groups_with_rows(layout):
    """An item a (group, row tile) pair in which the group has rows, in
    group order: none for an empty group, one for a group inside a tile, so
    a matrix is one run of items and is streamed once."""
    m, sizes = LAYOUTS[layout]
    tm = gd._plan(m, 64, 256, jnp.bfloat16, jnp.bfloat16, jnp.float32)[0]
    padded = -(-m // tm) * tm
    work = gd.grouped_work_list(jnp.asarray(sizes, jnp.int32), padded, tm)
    want = _tiles_of(np.asarray(sizes), tm)
    n = int(work.n_items)
    assert n == len(want)
    assert list(zip(np.asarray(work.groups[:n]).tolist(),
                    np.asarray(work.tiles[:n]).tolist())) == want
    assert work.groups.shape[0] >= n            # the static end holds them
    hit = [g for g, s in enumerate(sizes) if s]
    following = np.asarray(work.following)
    for g, nxt in zip(hit, hit[1:] + [len(sizes)]):
        assert following[g] == nxt
    # a group inside one tile is one item: its matrix is visited once
    for g in hit:
        lo = sum(sizes[:g])
        if lo // tm == (lo + sizes[g] - 1) // tm:
            assert [x for x in want if x[0] == g] == [(g, lo // tm)]


def test_a_matrix_wider_than_vmem_is_streamed_in_strips(monkeypatch):
    """Past ``_W_VMEM_BYTES`` the matrix comes as whole-K strips of N, the
    widest multiple of 128 that divides it and fits every buffer; the strips are
    the grid's outer axis and the stream runs on from one into the next."""
    m, sizes = LAYOUTS["longer-than-a-tile-and-a-single-row"]
    a, w = _operands(m, 64, 768, len(sizes), jnp.bfloat16)
    monkeypatch.setattr(gd, "_W_VMEM_BYTES", (gd._AHEAD + 1) * 64 * 256 * 2)
    assert gd._plan(m, 64, 768, a.dtype, w.dtype, jnp.float32)[1] == 256
    sizes = jnp.asarray(sizes, jnp.int32)
    got = gd.grouped_dot(a, w, sizes, jnp.float32, impl="kernel")
    want = jax.lax.ragged_dot(a, w, sizes, preferred_element_type=jnp.float32)
    rows = int(sizes.sum())
    np.testing.assert_allclose(np.asarray(got[:rows]),
                               np.asarray(want[:rows]), rtol=1e-5, atol=1e-5)
    monkeypatch.setattr(gd, "_W_VMEM_BYTES", 64 * 128 * 2)   # not one strip
    assert gd._plan(m, 64, 768, a.dtype, w.dtype, jnp.float32) is None


def test_the_tiles_come_from_the_shapes_and_types_of_the_call():
    """The three cells' matrices are whole in VMEM, every buffer; the limit asked
    of the compiler covers them; operands of two types have no tiles and
    keep ``ragged_dot`` under "auto"; off the TPU "auto" is ``ragged_dot``
    whatever the shapes."""
    for k, n in ((3072, 2048), (2048, 3072), (5120, 1536), (1536, 5120),
                 (2048, 512), (512, 2048)):
        tm, tn, vmem = gd._plan(6144, k, n, jnp.bfloat16, jnp.bfloat16,
                                jnp.float32)
        assert (tm, tn) == (TILE, n)
        assert (gd._AHEAD + 1) * k * n * 2 < vmem < 100 * 1024 * 1024
    assert gd._plan(48, 3072, 2048, jnp.bfloat16, jnp.bfloat16,
                    jnp.float32)[0] == 48
    assert gd._plan(256, 64, 128, jnp.float32, jnp.bfloat16,
                    jnp.float32) is None
    # Nemotron's expert as published, 1856 wide: no whole lanes, no copy
    assert gd._plan(6144, 2688, 1856, jnp.bfloat16, jnp.bfloat16,
                    jnp.float32) is None
    assert gd._plan(6144, 2688, 1920, jnp.bfloat16, jnp.bfloat16,
                    jnp.float32)[:2] == (TILE, 1920)
    a, w = _operands(256, 64, 128, 4, jnp.bfloat16)
    sizes = jnp.asarray([100, 0, 100, 0], jnp.int32)
    text = str(jax.make_jaxpr(lambda *x: gd.grouped_dot(*x))(a, w, sizes))
    assert "ragged_dot" in text and "pallas_call" not in text
    with pytest.raises(ValueError, match="impl must be"):
        gd.grouped_dot(a, w, sizes, impl="gather")
    with pytest.raises(ValueError, match="no tiles"):
        gd.grouped_dot(a.astype(jnp.float32), w, sizes, impl="kernel")


# ---------------------------------------------------- held_experts_ffn
def _layer(seed, d, f, experts, held, k, gated, rows=None, width=None,
           layers=None, dtype=jnp.bfloat16):
    """Random matrices of ``held`` experts (of ``layers`` layers), laid out
    ``rows`` x ``width`` with zeros past ``d`` x ``f``."""
    rng = np.random.default_rng(seed)
    rows, width = rows or d, width or f
    lead = (held,) if layers is None else (layers, held)

    def mat(i, o, pad_i, pad_o):
        w = np.zeros(lead + (pad_i, pad_o), np.float32)
        w[..., :i, :o] = rng.standard_normal(lead + (i, o)) / np.sqrt(i)
        return jnp.asarray(w, dtype)

    up = mat(d, f, rows, width)
    return dict(gate=mat(d, f, rows, width) if gated else None, up=up,
                down=mat(f, d, width, rows),
                router=jnp.asarray(rng.standard_normal((d, experts)),
                                   jnp.float32),
                bias=jnp.asarray(0.02 * rng.standard_normal(experts),
                                 jnp.float32))


def _relu2(x):
    return jnp.square(jax.nn.relu(x))


# what the three routed configurations ask of the layer, at tiny sizes
KINDS = {
    # 8 of 16 experts held, gated SiLU, groups of the router, gates x 16
    "deepseek": dict(d=128, f=128, experts=16, held=(0, 8), k=3, gated=True,
                     route=dict(n_groups=4, topk_groups=2, scale=16.0)),
    # all experts held, gated SiLU, renormalised gates x 2.5
    "laguna": dict(d=128, f=128, experts=16, held=(0, 16), k=4, gated=True,
                   route=dict(norm_topk=True, scale=2.5)),
    # half held, ungated relu^2, sigmoid scores with a choice bias, the
    # matrices laid out taller and wider than the stream
    "nemotron": dict(d=40, f=24, experts=16, held=(0, 8), k=3, gated=False,
                     rows=128, width=128, act=_relu2,
                     route=dict(norm_topk=True, scale=2.5, score="sigmoid")),
}


def _ffn(kind, h, w, impl, split=None, layer=None, out=None):
    c = KINDS[kind]
    how = dict(c["route"])
    if how.get("score") == "sigmoid":
        how["bias"] = w["bias"]
    chosen, gates = dropless.route(
        h.astype(jnp.float32) @ w["router"], c["k"], **how)
    return dropless.held_experts_ffn(
        h, chosen, gates, w["gate"], w["up"], w["down"], c["held"],
        c.get("act", jax.nn.silu), layer=layer, split=split, out=out,
        impl=impl)


@pytest.mark.parametrize("two_pass", [False, True],
                         ids=["one-pass", "two-pass"])
@pytest.mark.parametrize("stacked", [False, True],
                         ids=["one-layer", "traced-layer-of-the-stack"])
@pytest.mark.parametrize("kind", sorted(KINDS))
def test_held_experts_ffn_kernel_equals_ragged(kind, stacked, two_pass):
    """The layer through the kernel is the layer through ``ragged_dot``: the
    stack read where it lies under a traced ``layer``, the two bf16 pieces
    of a float32 row side by side in its group, matrices taller than the
    stream, gated or not."""
    c = KINDS[kind]
    w = _layer(3, c["d"], c["f"], c["experts"], c["held"][1], c["k"],
               c["gated"], c.get("rows"), c.get("width"),
               layers=3 if stacked else None)
    h = jnp.asarray(np.random.default_rng(4).standard_normal((37, c["d"])),
                    jnp.float32 if two_pass else jnp.bfloat16)
    split = G.split_bf16 if two_pass else None
    out = jnp.float32 if two_pass else None

    def run(impl):
        if stacked:
            return jax.jit(lambda layer: _ffn(kind, h, w, impl, split, layer,
                                              out))(jnp.int32(1))
        return _ffn(kind, h, w, impl, split, None, out)

    got, want = run("kernel"), run("ragged")
    assert got.shape == (37, c["d"]) and got.dtype == want.dtype
    assert float(jnp.abs(want.astype(jnp.float32)).max()) > 1e-2
    # float32 sums in another order, at the scale of the layer's output
    tol = 1e-5 if two_pass else 2e-2
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), rtol=tol,
        atol=tol * float(jnp.abs(want.astype(jnp.float32)).max()))


def test_two_pieces_in_one_group_are_two_pass():
    """``split=split_bf16`` through the kernel against ``gpt._two_pass``
    expert by expert: a float32 row meets its expert's bf16 matrix at 16
    bits of mantissa, and one pass (the row rounded to bf16) does not."""
    c = KINDS["laguna"]
    w = _layer(5, c["d"], c["f"], c["experts"], c["held"][1], c["k"], True)
    h = jnp.asarray(np.random.default_rng(6).standard_normal((24, c["d"])),
                    jnp.float32)
    got = _ffn("laguna", h, w, "kernel", split=G.split_bf16, out=jnp.float32)
    chosen, gates = dropless.route(h @ w["router"], c["k"], **c["route"])

    def product(a, m):
        return G._two_pass(a, lambda x: jnp.dot(
            x, m, preferred_element_type=jnp.float32))

    want = np.zeros((24, c["d"]), np.float32)
    for n in range(24):
        for e, gate in zip(np.asarray(chosen[n]), np.asarray(gates[n])):
            x = h[n][None]
            mid = (jax.nn.silu(product(x, w["gate"][e]))
                   * product(x, w["up"][e]))
            want[n] += gate * np.asarray(product(mid, w["down"][e]))[0]
    assert np.abs(np.asarray(got) - want).max() < 2e-5
    one = _ffn("laguna", h.astype(jnp.bfloat16), w, "kernel",
               out=jnp.float32)
    assert np.abs(np.asarray(one) - want).max() > 1e-3
