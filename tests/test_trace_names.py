"""Names on the device trace and spans on the host (``profiling/trace.py``):
every Pallas kernel lowers under its name, every hot program is jitted under
its name and lands in the program table, ``phase_of`` reads recorded
``op_name`` strings, ``program_scopes`` recovers the scopes of the ``tiny``
train step, and a scheduler run under ``jax.profiler.trace`` yields the span
vocabulary with its counts. All on the CPU: a name is in the jaxpr whatever
the backend."""

import contextlib
import glob
import os
import types

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.models import gpt as G
from deepspeed_tpu.profiling import trace


# ------------------------------------------------------------ kernel names
def _flash(grad):
    from deepspeed_tpu.ops.pallas.flash_attention import flash_attention

    q = jnp.zeros((1, 128, 2, 64), jnp.float32)
    if grad:
        return jax.make_jaxpr(jax.grad(
            lambda q: flash_attention(q, q, q).sum()))(q)
    return jax.make_jaxpr(lambda q: flash_attention(q, q, q))(q)


def _blocksparse(grad):
    from deepspeed_tpu.ops.pallas.blocksparse_attention import (
        blocksparse_attention)

    q = jnp.zeros((1, 256, 2, 64), jnp.float32)
    layout = np.tril(np.ones((2, 2, 2), np.int32))

    def f(q):
        return blocksparse_attention(q, q, q, layout, 128).sum()

    return jax.make_jaxpr(jax.grad(f) if grad else f)(q)


def _decode():
    from deepspeed_tpu.ops.pallas.decode_attention import decode_attention

    q = jnp.zeros((2, 1, 2, 64), jnp.float32)
    kv = jnp.zeros((2, 2, 128, 64), jnp.float32)
    return jax.make_jaxpr(lambda q, kv: decode_attention(
        q, kv, kv, jnp.int32(5)))(q, kv)


def _pool(quantized):
    pages = jnp.zeros((2, 4, 8, 64), jnp.int8 if quantized else jnp.float32)
    scales = jnp.ones((2, 4), jnp.float32) if quantized else None
    return (pages, scales, jnp.array([5, 9], jnp.int32),
            jnp.zeros((2, 2), jnp.int32))


def _paged_decode(quantized):
    from deepspeed_tpu.ops.pallas.decode_attention import (
        paged_decode_attention)

    pages, scales, lengths, tables = _pool(quantized)
    q = jnp.zeros((2, 1, 2, 64), jnp.float32)
    return jax.make_jaxpr(lambda q, p: paged_decode_attention(
        q, p, p, lengths, tables, impl="kernel", k_scales=scales,
        v_scales=scales))(q, pages)


def _paged_decode_mla():
    from deepspeed_tpu.ops.pallas.decode_attention import paged_decode_mla

    pool = jnp.zeros((1, 4, 8, 128), jnp.float32)
    q = jnp.zeros((2, 1, 4, 128), jnp.float32)
    return jax.make_jaxpr(lambda q, p: paged_decode_mla(
        q, p, jnp.array([5, 9], jnp.int32), jnp.zeros((2, 2), jnp.int32),
        rank=64, softmax_scale=1.0, impl="kernel"))(q, pool)


def _paged_decode_mla_kind(**how):
    """The latent kernel over a ring (``ring``) or under a selection
    (``allowed``, or ``selected`` rows a caller gathered)."""
    from deepspeed_tpu.ops.pallas.decode_attention import paged_decode_mla

    pool = jnp.zeros((1, 4, 8, 128), jnp.float32)
    q = jnp.zeros((2, 1, 4, 128), jnp.float32)
    return jax.make_jaxpr(lambda q, p: paged_decode_mla(
        q, p, jnp.array([5, 9], jnp.int32), jnp.zeros((2, 2), jnp.int32),
        rank=64, softmax_scale=1.0, impl="kernel", **how))(q, pool)


def _masked_chunk_attn():
    from deepspeed_tpu.ops.pallas.chunk_attention import (
        masked_chunk_attention)

    q = jnp.zeros((4, 16, 32), jnp.float32)
    k = jnp.zeros((4, 64, 32), jnp.float32)
    return jax.make_jaxpr(lambda q, k: masked_chunk_attention(
        q, k, k, jnp.ones((16, 64), bool), 40, 1.0, impl="kernel"))(q, k)


def _index_scores():
    from deepspeed_tpu.ops.pallas.index_scores import index_scores

    pool = jnp.zeros((1, 1, 4, 8, 16), jnp.float32)
    return jax.make_jaxpr(lambda q, w, p: index_scores(
        q, w, p, jnp.array([5, 9], jnp.int32), jnp.zeros((2, 2), jnp.int32),
        0))(jnp.zeros((2, 1, 4, 16)), jnp.zeros((2, 1, 4)), pool)


def _paged_verify():
    from deepspeed_tpu.ops.pallas.decode_attention import (
        paged_verify_attention)

    pages, _, lengths, tables = _pool(False)
    q = jnp.zeros((2, 4, 2, 64), jnp.float32)
    return jax.make_jaxpr(lambda q, p: paged_verify_attention(
        q, p, p, lengths, tables, q, q, impl="kernel"))(q, pages)


def _int_matmul(bits):
    from deepspeed_tpu.ops.pallas import int8_matmul as M

    x = jnp.zeros((8, 256), jnp.float32)
    s2d = jnp.ones((256, 4), jnp.float32)
    if bits == 8:
        return jax.make_jaxpr(lambda x, q: M._int8_matmul_kernel_call(
            x, q, s2d, 128, 256, 512, x.dtype))(
                x, jnp.zeros((256, 512), jnp.int8))
    return jax.make_jaxpr(lambda x, q: M._int4_matmul_kernel_call(
        x, q, s2d, 128, 256, 256, x.dtype))(
            x, jnp.zeros((256, 256), jnp.int8))


def _dequant_matmul(monkeypatch):
    from deepspeed_tpu.comm.quantized import quantize_blockwise
    from deepspeed_tpu.ops.pallas.dequant_matmul import dequant_matmul

    monkeypatch.setenv("DS_TPU_PALLAS_INTERPRET", "1")   # the Pallas path
    x = jnp.zeros((8, 256), jnp.float32)
    q, s, z = quantize_blockwise(jnp.ones((256, 512), jnp.float32), bits=8,
                                 block_size=256)
    return jax.make_jaxpr(lambda x: dequant_matmul(x, q, s, z,
                                                   orig_size=512))(x)

def _paged_decode_gqa():
    from deepspeed_tpu.ops.pallas.decode_attention import paged_decode_gqa

    pool = jnp.zeros((2, 4, 8, 64), jnp.float32)
    q = jnp.zeros((2, 1, 6, 64), jnp.float32)
    return jax.make_jaxpr(lambda q, p: paged_decode_gqa(
        q, p, p, jnp.array([5, 9], jnp.int32), jnp.zeros((2, 2), jnp.int32),
        impl="kernel"))(q, pool)


def _ssm_decode():
    from deepspeed_tpu.ops.pallas.ssm_decode import ssm_decode

    state = jnp.zeros((2, 3, 4, 8, 16), jnp.float32)
    rows = jnp.zeros((3, 4, 8), jnp.float32)
    return jax.make_jaxpr(lambda s, x: ssm_decode(
        s, jnp.int32(1), x, x[:, :, 0], s[0, :, :2, 0], s[0, :, :2, 0],
        jnp.array([True, False, True]), impl="kernel"))(state, rows)


def _kda_decode():
    from deepspeed_tpu.ops.pallas.kda_decode import kda_decode

    state = jnp.zeros((2, 3, 2, 8, 16), jnp.float32)
    rows = jnp.zeros((3, 2, 16), jnp.float32)
    return jax.make_jaxpr(lambda s, x: kda_decode(
        s, jnp.int32(1), x, x, x[:, :, :8], x, x[:, :, 0],
        jnp.array([True, False, True]), impl="kernel"))(state, rows)


def _retention_decode():
    from deepspeed_tpu.ops.pallas.retention_decode import retention_decode

    state = jnp.zeros((2, 3, 2, 6, 8, 8), jnp.float32)
    rows = jnp.zeros((3, 4, 8), jnp.float32)
    return jax.make_jaxpr(lambda s, x: retention_decode(
        s, jnp.int32(1), x, x[:, :2], x[:, :2], x[:, :2, 0],
        jnp.array([True, False, True]), impl="kernel"))(state, rows)


def _retention_chunk():
    from deepspeed_tpu.models.retention import RetentionMixer
    from deepspeed_tpu.ops.pallas.retention_chunk import retention_chunk

    m = RetentionMixer(heads=4, kv_heads=2, head_dim=8, chunk=8)
    x = jnp.zeros((1, 16, 4, 8), jnp.float32)
    return jax.make_jaxpr(lambda x, s: retention_chunk(
        m, x, x[:, :, :2], x[:, :, :2], x[:, :, :2, 0], s, impl="kernel"))(
            x, jnp.zeros((1,) + m.state_shape(), jnp.float32))


def _grouped_dot():
    from deepspeed_tpu.ops.pallas.grouped_dot import grouped_dot

    a = jnp.zeros((32, 16), jnp.float32)
    return jax.make_jaxpr(lambda a, w: grouped_dot(
        a, w, jnp.array([20, 0, 12], jnp.int32), impl="kernel"))(
            a, jnp.zeros((3, 16, 128), jnp.float32))


KERNELS = {
    "grouped_dot": lambda mp: _grouped_dot(),
    "ssm_decode": lambda mp: _ssm_decode(),
    "kda_decode": lambda mp: _kda_decode(),
    "retention_decode": lambda mp: _retention_decode(),
    "retention_chunk": lambda mp: _retention_chunk(),
    "flash_fwd": lambda mp: _flash(False),
    "flash_bwd_delta": lambda mp: _flash(True),
    "flash_bwd_dq": lambda mp: _flash(True),
    "flash_bwd_dkv": lambda mp: _flash(True),
    "decode_attn": lambda mp: _decode(),
    "paged_decode": lambda mp: _paged_decode(False),
    "paged_decode_q": lambda mp: _paged_decode(True),
    "paged_decode_mla": lambda mp: _paged_decode_mla(),
    "paged_decode_mla_ring": lambda mp: _paged_decode_mla_kind(ring=(16, 9)),
    "paged_decode_mla_select": lambda mp: _paged_decode_mla_kind(
        allowed=jnp.ones((2, 16), jnp.int32)),
    "paged_decode_gqa": lambda mp: _paged_decode_gqa(),
    "masked_chunk_attn": lambda mp: _masked_chunk_attn(),
    "index_scores": lambda mp: _index_scores(),
    "paged_verify": lambda mp: _paged_verify(),
    "blocksparse_fwd": lambda mp: _blocksparse(False),
    "blocksparse_bwd_dq": lambda mp: _blocksparse(True),
    "blocksparse_bwd_dkv": lambda mp: _blocksparse(True),
    "dequant_matmul": _dequant_matmul,
    "int8_matmul": lambda mp: _int_matmul(8),
    "int4_matmul": lambda mp: _int_matmul(4),
}


def _pallas_calls(jaxpr) -> list:
    """Every ``pallas_call`` equation of a jaxpr, nested ones too."""
    calls = []

    def walk(jp):
        for eqn in jp.eqns:
            if eqn.primitive.name == "pallas_call":
                calls.append(eqn)
            for v in eqn.params.values():
                for sub in (v if isinstance(v, (list, tuple)) else (v,)):
                    inner = getattr(sub, "jaxpr", sub)
                    if hasattr(inner, "eqns"):
                        walk(inner)

    walk(jaxpr.jaxpr)
    return calls


def _pallas_names(jaxpr) -> set:
    """The ``name`` of every ``pallas_call`` in a jaxpr."""
    return {eqn.params["name"] for eqn in _pallas_calls(jaxpr)}


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_pallas_call_carries_its_name(name, monkeypatch):
    names = _pallas_names(KERNELS[name](monkeypatch))
    assert name in names, names
    # the quantized pool's kernel is told apart from the dense one's
    if name.startswith("paged_decode"):
        assert names == {name}


@pytest.mark.parametrize("sizes", [(8, 128, 128, 2), (4, 8, 16, 2)],
                         ids=["whole tiles", "a head at a time"])
def test_ssm_decode_is_one_kernel_under_ssm_update(sizes):
    """By either walk of ``ssm_decode._plan`` a mixer's decode step holds ONE
    ``pallas_call``, named ``ssm_decode``, under scope ``ssm_update``:
    ``ssm_decode_kernel_ms`` and ``prog_roofline_ssm`` match ``^ssm_decode``,
    and a second call under another name would fall out of the roofline's
    time and flatter it."""
    from deepspeed_tpu.models import ssm
    from deepspeed_tpu.ops.pallas import ssm_decode as SD

    H, P, N, G = sizes
    assert (SD._plan(H, P, N, G) is None) == (P == 8)
    m = ssm.SsmMixer(heads=H, head_dim=P, state=N, groups=G)
    w = jax.tree.map(lambda a: a[0], ssm.init_mixer(
        m, jax.random.PRNGKey(0), 1, 32,
        lambda k, shape, std: jax.random.normal(k, shape) * std, 0.02, 0.02))
    jaxpr = jax.make_jaxpr(lambda h, s, win: ssm.mix_token(
        m, h, w, s, win, jnp.int32(1), jnp.array([True, False, True]),
        linear=lambda x, a, t: x @ a, eps=1e-5, impl="kernel"))(
            jnp.zeros((3, 1, 32)), jnp.zeros((2, 3) + m.state_shape()),
            jnp.zeros((2, 3) + m.window_shape()))
    calls = [(eqn.params["name"], str(eqn.source_info.name_stack))
             for eqn in _pallas_calls(jaxpr)]
    assert [name for name, _ in calls] == ["ssm_decode"]
    assert calls[0][1] == "ssm_update/ssm_decode", calls
    assert trace.phase_of("jit(decode_block_4)/while/body/blocks/while/body/"
                          "ssm/" + calls[0][1] + "/pallas_call") == (
                              "forward", "ssm_update")


def test_kda_decode_is_one_kernel_under_kda_update():
    """A KDA mixer's decode step holds ONE ``pallas_call``, named
    ``kda_decode``, under scope ``kda_update``: ``kda_decode_kernel_ms`` and
    ``prog_roofline_kda`` match ``^kda_decode``."""
    from deepspeed_tpu.models import kda

    m = kda.KdaMixer(heads=2, head_dim=8)
    w = jax.tree.map(lambda a: a[0], kda.init_mixer(
        m, jax.random.PRNGKey(0), 1, 32,
        lambda k, shape, std: jax.random.normal(k, shape) * std, 0.02, 0.02))
    jaxpr = jax.make_jaxpr(lambda h, s, win: kda.mix_token(
        m, h, w, s, win, jnp.int32(1), jnp.array([True, False, True]),
        linear=lambda x, a, t: x @ a, eps=1e-5, impl="kernel"))(
            jnp.zeros((3, 1, 32)), jnp.zeros((2, 3) + m.state_shape()),
            jnp.zeros((2, 3) + m.window_shape()))
    calls = [(eqn.params["name"], str(eqn.source_info.name_stack))
             for eqn in _pallas_calls(jaxpr)]
    assert calls == [("kda_decode", "kda_update/kda_decode")], calls
    assert trace.phase_of("jit(decode_block_4)/while/body/blocks/while/body/"
                          "kda/" + calls[0][1] + "/pallas_call") == (
                              "forward", "kda_update")


def test_retention_decode_is_one_kernel_under_retention_update():
    """A retention mixer's decode step holds ONE ``pallas_call``, named
    ``retention_decode``, under scope ``retention_update``:
    ``retention_decode_kernel_ms`` and ``prog_roofline_retention`` match
    ``^retention_decode``."""
    from deepspeed_tpu.models import gpt, retention

    m = retention.RetentionMixer(heads=4, kv_heads=2, head_dim=8)
    w = jax.tree.map(lambda a: a[0], retention.init_mixer(
        m, jax.random.PRNGKey(0), 1, 32,
        lambda k, shape, std: jax.random.normal(k, shape) * std, 0.02, 0.02))
    jaxpr = jax.make_jaxpr(lambda h, s, win: retention.mix_token(
        m, h, w, s, win, jnp.int32(1), jnp.array([True, False, True]),
        linear=lambda x, a, t: x @ a, eps=1e-6, impl="kernel",
        positions=jnp.zeros((3, 1), jnp.int32),
        rotate=gpt._mixer_rotate(gpt.PRESETS["tiny"])))(
            jnp.zeros((3, 1, 32)), jnp.zeros((2, 3) + m.state_shape()),
            jnp.zeros((2, 3) + m.window_shape()))
    calls = [(eqn.params["name"], str(eqn.source_info.name_stack))
             for eqn in _pallas_calls(jaxpr)]
    assert calls == [("retention_decode",
                      "retention_update/retention_decode")], calls
    assert trace.phase_of("jit(decode_block_4)/while/body/blocks/while/body/"
                          "retention/" + calls[0][1] + "/pallas_call") == (
                              "forward", "retention_update")


def test_every_pallas_call_site_is_named():
    """No ``pl.pallas_call`` under ops/pallas without a ``name=``, and the
    names are the ones this file checks."""
    import re

    root = os.path.join(os.path.dirname(trace.__file__), "..", "ops",
                        "pallas")
    found = set()
    for path in glob.glob(os.path.join(root, "*.py")):
        text = open(path).read()
        calls = [m.start() for m in re.finditer(r"pl\.pallas_call\(", text)]
        for at in calls:
            end = text.index(")(", at)
            # a kernel named by its use says each whole name, over lines
            m = re.search(r'name=(\(.+?\)|.+?),\n', text[at:end], re.S)
            assert m, f"{os.path.basename(path)}: unnamed pallas_call"
            found |= set(re.findall(r'"(\w+)"', m.group(1)))
    assert found == set(KERNELS)


# ---------------------------------------------------------------- phase_of
RECORDED = [
    ("jit(train_batch)/jvp(blocks)/while/body/closed_call/attn/flash_fwd/"
     "pallas_call", ("forward", "attn")),
    ("jit(train_batch)/jvp(blocks)/while/body/closed_call/mlp/add",
     ("forward", "mlp")),
    ("jit(train_batch)/jvp(embed)/jit(_take)/lt", ("forward", "embed")),
    ("jit(train_batch)/transpose(jvp(blocks))/jvp(blocks)/checkpoint/"
     "rematted_computation/attn/flash_fwd/pallas_call",
     ("recompute", "attn")),
    ("jit(train_batch)/transpose(jvp(blocks))/while/body/closed_call/"
     "checkpoint/rematted_computation/mlp/dot_general", ("recompute", "mlp")),
    ("jit(train_batch)/transpose(jvp(blocks))/jvp(blocks)/checkpoint/attn/"
     "flash_bwd_dq/pallas_call", ("backward", "attn")),
    ("jit(train_batch)/transpose(jvp(blocks))/while/body/broadcast_in_dim",
     ("backward", "blocks")),
    ("jit(train_batch)/transpose(jvp(head_loss))/jit(take_along_axis)/"
     "scatter-add", ("backward", "head_loss")),
    ("jit(train_batch)/jvp(head_loss)/abs", ("forward", "head_loss")),
    ("jit(train_batch)/grad_reduce/convert_element_type",
     ("backward", "grad_reduce")),
    ("jit(train_batch)/optimizer/grad_clip/min", ("optimizer", "grad_clip")),
    ("jit(train_batch)/optimizer/cond/branch_1_fun/sub",
     ("optimizer", "optimizer")),
    ("jit(decode_block_2)/blocks/while/body/attn/kv_write/scatter",
     ("forward", "kv_write")),
    ("jit(decode_block_4)/while/body/ut_loop/blocks/while/body/attn/"
     "paged_decode/pallas_call", ("forward", "attn")),
    ("jit(decode_block_4)/while/body/ut_loop/loop_norm/rsqrt",
     ("forward", "loop_norm")),
    ("jit(prefill_batch_128)/while/body/ut_loop/blocks/while/body/select_n",
     ("forward", "blocks")),
    ("jit(decode_block_4)/while/body/blocks/while/body/ssm/ssm_update/"
     "ssm_decode/pallas_call", ("forward", "ssm_update")),
    ("jit(decode_block_4)/while/body/blocks/while/body/ssm/ssm_conv/"
     "dynamic_update_slice", ("forward", "ssm_conv")),
    ("jit(decode_block_4)/while/body/blocks/while/body/ssm/rsqrt",
     ("forward", "ssm")),
    ("jit(prefill_batch_256)/blocks/while/body/ssm/ssm_scan/while/body/"
     "dot_general", ("forward", "ssm_scan")),
    ("jit(prefill_chunk_256)/blocks/while/body/ssm/ssm_in/dot_general",
     ("forward", "ssm_in")),
    ("jit(prefill_fused_128)/blocks/while/body/ssm/ssm_gate_norm/mul",
     ("forward", "ssm_gate_norm")),
    ("jit(prefill_fused_128)/blocks/while/body/ssm/ssm_out/dot_general",
     ("forward", "ssm_out")),
    ("jit(decode_block_4)/while/body/blocks/while/body/mlp/moe_router/"
     "logistic", ("forward", "moe_router")),
    ("jit(decode_block_2)/while/body/blocks/while/body/mlp/moe_experts/"
     "grouped_dot/pallas_call", ("forward", "moe_experts")),
    # a layer of two attention sub-blocks with a routed branch across them
    # (PR 63): the innermost scope is what an operation is read by
    ("jit(decode_block_4)/while/body/blocks/while/body/routed_branch/"
     "moe_zero/mul", ("forward", "moe_zero")),
    ("jit(decode_block_4)/while/body/blocks/while/body/routed_branch/"
     "moe_experts/grouped_dot/pallas_call", ("forward", "moe_experts")),
    ("jit(decode_block_4)/while/body/blocks/while/body/routed_branch/add",
     ("forward", "routed_branch")),
    ("jit(prefill_fused_512)/blocks/while/body/dense_ffn/dot_general",
     ("forward", "dense_ffn")),
    ("state['params']['blocks']['qkv_w']", ("other", None)),
    ("jit(train_batch)/transpose(jvp())/pad", ("backward", None)),
]


@pytest.mark.parametrize("op_name,want", RECORDED,
                         ids=[f"{w[0]}-{w[1]}-{i}"
                              for i, (_, w) in enumerate(RECORDED)])
def test_phase_of(op_name, want):
    assert trace.phase_of(op_name) == want
    assert want[0] in trace.PHASES


def test_parse_scopes_reads_instruction_names_and_op_names():
    text = '''
  %fusion.58 = bf16[8,128]{1,0} fusion(%p.1), kind=kLoop, calls=%fused.3, metadata={op_name="jit(train_batch)/jvp(blocks)/while/body/mlp/add" source_file="gpt.py" source_line=411}
  ROOT %flash_fwd.7 = (bf16[2,128,64]{2,1,0}, f32[2,128,128]{2,1,0}) custom-call(%a, %b), custom_call_target="tpu_custom_call", metadata={op_name="jit(train_batch)/jvp(blocks)/attn/flash_fwd/pallas_call"}
  %copy.3 = f32[4]{0} copy(%x)
'''
    got = trace.parse_scopes(text)
    assert got == {
        "fusion.58": "jit(train_batch)/jvp(blocks)/while/body/mlp/add",
        "flash_fwd.7":
            "jit(train_batch)/jvp(blocks)/attn/flash_fwd/pallas_call"}


# ------------------------------------------------------------ train engine
@pytest.fixture(scope="module")
def tiny_train():
    import deepspeed_tpu
    from deepspeed_tpu.models import build_gpt

    cfg = G.GPTConfig(vocab_size=128, d_model=32, n_layer=2, n_head=2,
                      max_seq_len=32, remat=True)
    module, _ = build_gpt(cfg)
    engine, _, _, _ = deepspeed_tpu.initialize(model=module, seed=0, config={
        "train_micro_batch_size_per_gpu": 1, "bf16": {"enabled": True},
        "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
        "zero_optimization": {"stage": 1}, "gradient_clipping": 1.0,
        "mesh": {"dp": 8}})
    rng = np.random.default_rng(0)
    batch = {"input_ids": rng.integers(0, 128, (8, 32), dtype=np.int32)}
    return engine, batch


def test_train_programs_carry_their_names(tiny_train):
    from deepspeed_tpu.comm.runtime_accounting import wire_ledger

    engine, batch = tiny_train
    engine.train_batch(batch)
    assert engine._train_batch_jit.__name__ == "train_batch"
    assert "train_batch" in trace.programs()
    wire_ledger.record("sentinel", 8, 2)
    before = {k: (r.count, r.logical_bytes, r.wire_bytes)
              for k, r in wire_ledger.records.items()}
    scopes = trace.program_scopes("train_batch")
    after = {k: (r.count, r.logical_bytes, r.wire_bytes)
             for k, r in wire_ledger.records.items()}
    assert after == before                # trace-time accounting untouched
    del wire_ledger.records["sentinel"]
    seen = {trace.phase_of(v) for v in scopes.values()}
    assert {s for _, s in seen} >= {"embed", "blocks", "attn", "mlp",
                                    "head_loss", "grad_clip", "optimizer"}
    assert {p for p, _ in seen} >= {"forward", "recompute", "backward",
                                    "optimizer"}
    assert trace.program_scopes("train_batch") is scopes     # computed once

    stacked = {"input_ids": np.stack([batch["input_ids"]] * 2)}
    engine.train_batches(stacked)
    assert engine._train_batches_jits[2].__name__ == "train_batches_k2"
    assert "train_batches_k2" in trace.programs()
    lowered = engine._train_batches_jits[2].lower(
        *trace._programs["train_batches_k2"][-1].args)
    assert "jit_train_batches_k2" in lowered.as_text()[:200]


def test_program_table_keeps_no_engine_alive():
    import gc

    def fn(x):
        return x + 1

    jitted = jax.jit(trace.named(fn, "short_lived"))
    trace.register_program("short_lived", jitted, (jnp.ones(3),))
    assert "short_lived" in trace.programs()
    assert trace.program_scopes("short_lived") is not None
    del jitted
    gc.collect()
    assert "short_lived" not in trace.programs()
    trace._programs.pop("short_lived")


def test_a_program_that_ran_in_a_session_outlives_its_engine(tmp_path):
    """Who reads a trace asks for the scopes of the program that ran in it,
    possibly after the engine went out of scope: inside a profiler session
    the table holds the dispatched function itself, until a newer program of
    the name registers; outside one it holds nothing."""
    import gc

    def build():
        def fn(x):
            with jax.named_scope("mlp"):
                return x * 3
        return jax.jit(trace.named(fn, "traced_once"))

    jitted = build()
    trace.register_program("traced_once", jitted, (jnp.ones(3),))
    trace.hold_if_traced("traced_once", jitted)          # no session: no hold
    assert trace._programs["traced_once"][-1].held is None
    with jax.profiler.trace(str(tmp_path)):
        trace.hold_if_traced("traced_once", jitted)
    del jitted
    gc.collect()
    assert "traced_once" in trace.programs()
    assert {trace.phase_of(v)[1] for v in trace.program_scopes(
        "traced_once").values()} >= {"mlp"}
    newer = build()
    trace.register_program("traced_once", newer, (jnp.ones(3),))
    gc.collect()
    assert len(trace._live("traced_once")) == 1          # the older one went
    trace._programs.pop("traced_once")


def test_a_name_registered_twice_answers_by_module_id(monkeypatch):
    """Two engines of one process both build a ``train_batch``. Without a
    trace's module id the newest answers; with one, the registration that
    compiles to that module, or nobody: instruction names coincide between
    programs, so a join with the wrong text would raise no error."""
    assert trace._varint(300) == b"\xac\x02" and trace._varint(5) == b"\x05"

    def first_of_two(x):
        with jax.named_scope("attn"):
            return x + 1

    def second_of_two(x):
        with jax.named_scope("mlp"):
            return x * 2

    one, two = jax.jit(first_of_two), jax.jit(second_of_two)
    for jitted in (one, two):
        trace.register_program("twice", jitted, (jnp.ones(3),))

    def scopes_found(**kw):
        return {trace.phase_of(v)[1] for v in trace.program_scopes(
            "twice", **kw).values()} - {None}

    assert scopes_found() == {"mlp"}                     # the newest
    # the CPU's trace prints no module id; stand in for one with what the
    # serialized executable of each program is known to hold, its own name
    monkeypatch.setattr(trace, "_varint", lambda n: {
        1: b"first_of_two", 2: b"second_of_two", 3: b"third"}[n])
    assert scopes_found(module_id=1) == {"attn"}
    assert scopes_found(module_id=2) == {"mlp"}
    with pytest.raises(LookupError, match="another program"):
        trace.program_scopes("twice", module_id=3)
    del two, jitted
    import gc
    gc.collect()
    assert scopes_found() == {"attn"}                    # the survivor
    trace._programs.pop("twice")


# ----------------------------------------------------- serving under trace
CFG = G.GPTConfig(vocab_size=64, d_model=32, n_layer=2, n_head=4,
                  max_seq_len=128)


@contextlib.contextmanager
def _session(trace_dir):
    """A profiler session that traces the host's annotations and no Python
    frames (the Python tracer hooks every call of the process)."""
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def _host_events(trace_dir):
    """[(name, start_ns, end_ns, stats)] of the program's spans."""
    path = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                  "*.xplane.pb"))[0]
    data = jax.profiler.ProfileData.from_file(path)
    out = []
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(trace.SPAN_PREFIXES):
                    out.append((ev.name, ev.start_ns,
                                ev.start_ns + ev.duration_ns,
                                dict(ev.stats)))
    return out


@pytest.fixture(scope="module", params=["chunks_to_pages", "dense_chunks"])
def traced_serving(request, tmp_path_factory):
    """A traced scheduler run over a plain engine, whose chunked prompts go
    into their pages chunk by chunk, and over the same engine held to the
    dense scratch cache and the scatter: the path that latent rows, key-value
    heads, quantized pools or weights and tp take."""
    from deepspeed_tpu.inference.serving import (Request, ServingConfig,
                                                 ServingEngine)

    params = G.init_params(CFG, jax.random.PRNGKey(0))
    engine = ServingEngine(CFG, params, ServingConfig(
        num_slots=3, page_size=8, max_model_len=64, prefill_chunk=16,
        dtype="float32", decode_block=2, max_queue=64))
    assert engine._chunk_to_pages
    engine._chunk_to_pages = request.param == "chunks_to_pages"
    sched = engine.make_scheduler()
    rng = np.random.default_rng(1)
    # two short prompts share the first cycle (the admission batch), one is
    # longer than a chunk (serial chunks; on the dense path the scratch cache
    # before them and the scatter after), one waits in queue
    reqs = [Request(prompt=rng.integers(1, 64, n).astype(np.int32),
                    max_new_tokens=m)
            for n, m in [(5, 4), (9, 3), (40, 5), (12, 2)]]
    out = str(tmp_path_factory.mktemp("serve_trace"))
    with _session(out):
        for r in reqs:
            sched.submit(r)
        sched.run_to_completion()
    return engine, reqs, _host_events(out)


def test_serving_programs_carry_their_names(traced_serving):
    engine, _, _ = traced_serving
    # one chunk program an engine, under one name: the dense one and the
    # scatter, or the one that writes its own pages and no scatter
    paged = engine._chunk_to_pages
    assert bool(engine._prefill_paged_fns) == paged
    assert bool(engine._prefill_fns) == (engine._scatter_fn is not None) \
        == (not paged)
    built = ([] if paged else [engine._scatter_fn]) + [
        fn for table in (engine._prefill_fns, engine._prefill_paged_fns,
                         engine._prefill_fused_fns,
                         engine._prefill_batch_fns, engine._decode_fns)
        for fn in table.values()]
    names = {fn.__name__ for fn in built}
    assert names >= {"prefill_chunk_16",
                     "prefill_batch_16", "prefill_fused_16",
                     "decode_block_1", "decode_block_2"}
    assert ("scatter" in names) == (not paged)
    assert not names & {"fn", "fused"}
    assert names <= set(trace.programs())
    assert engine._get_verify(3).__name__ == "verify_w3"
    scopes = trace.program_scopes("decode_block_2")
    found = {trace.phase_of(v)[1] for v in scopes.values()}
    assert found >= {"embed", "blocks", "attn", "mlp", "kv_write",
                     "head_loss"}
    text = engine._decode_fns[2].lower(
        *trace._programs["decode_block_2"][-1].args).as_text()
    assert "jit_decode_block_2" in text[:200]


def test_a_looped_stack_compiles_its_pass_scopes_into_every_program():
    """``ut_loop`` and ``loop_norm`` are in all four kinds of program of a
    model whose stack runs more than once, and in none of a model's that
    runs it once (whose programs keep the names they had)."""
    import dataclasses

    from deepspeed_tpu.inference.serving import ServingConfig, ServingEngine

    looped = dataclasses.replace(CFG, rotary=True, ut_steps=2, loop_norm=True)
    engine = ServingEngine(
        looped, G.init_params(looped, jax.random.PRNGKey(0)), ServingConfig(
            num_slots=2, page_size=8, max_model_len=32, prefill_chunk=16,
            dtype="float32", decode_block=2))
    sink = np.zeros(engine.serving.pages_per_seq, np.int32)
    short, long_ = np.ones(5, np.int32), np.ones(20, np.int32)
    engine.prefill(0, short, sink)
    engine.prefill_many([(0, short, sink), (1, short, sink)])
    engine.prefill(0, long_, sink)
    zeros = np.zeros(2, np.int32)
    engine.decode(zeros, np.zeros((2, len(sink)), np.int32), zeros,
                  np.zeros(2, bool), steps=2)
    for name in ("prefill_fused_16", "prefill_batch_16", "prefill_chunk_16",
                 "decode_block_2"):
        found = {part for v in trace.program_scopes(name).values()
                 for part in v.split("/")}
        assert {"ut_loop", "loop_norm"} <= found, name
    assert {"ut_loop", "loop_norm"} <= set(trace.MODEL_SCOPES)


def _gathers(jaxpr) -> list:
    """(shape, dtype) of every ``gather``'s result in a jaxpr, nested ones
    too."""
    found = []

    def walk(jp):
        for eqn in jp.eqns:
            if eqn.primitive.name == "gather":
                aval = eqn.outvars[0].aval
                found.append((tuple(aval.shape), str(aval.dtype)))
            for v in eqn.params.values():
                for sub in (v if isinstance(v, (list, tuple)) else (v,)):
                    inner = getattr(sub, "jaxpr", sub)
                    if hasattr(inner, "eqns"):
                        walk(inner)

    walk(jaxpr.jaxpr)
    return found


@pytest.mark.parametrize("impl", [None, "kernel"],
                         ids=["the plain form", "the kernels"])
def test_a_selecting_model_compiles_index_under_attn_full_in_every_program(
        impl):
    """``index`` (the indexer's projections, its scores and the top-k) lies
    inside ``attn_full`` in the decode and the prefill programs of a model
    whose full layers select their rows, beside ``attn_window``; its
    ``serve.decode`` span carries ``trace.SELECT_STATS`` and
    ``trace.INDEX_STATS``; no other model's programs or spans have either.
    With the kernels, the indexer's scores of the decode and the chunk
    programs are ``index_scores`` calls under ``index`` (what
    ``index_scores_kernel_ms`` and ``index_decode_ms`` read), every one of
    them counted as the kernel's, and the decode program gathers no slot's
    index keys through the table."""
    import dataclasses
    import json
    import os

    from benchmark.families import dots3_note
    from deepspeed_tpu.inference.serving import ServingConfig, ServingEngine

    with open(os.path.join(os.path.dirname(__file__), "..", "benchmark",
                           "configs", "tiny-dots3-note-serve.json")) as f:
        cfg = dots3_note.config(json.load(f)["model"])
    cfg = dataclasses.replace(cfg, use_flash=impl and True)
    engine = ServingEngine(
        cfg, G.init_params(cfg, jax.random.PRNGKey(0)), ServingConfig(
            num_slots=2, page_size=16, max_model_len=64, prefill_chunk=32,
            dtype="float32", decode_block=2, kernel_impl=impl))
    sink = np.zeros(engine.serving.pages_per_seq, np.int32)
    short, long_ = np.ones(20, np.int32), np.ones(40, np.int32)
    engine.prefill(0, short, sink)
    engine.prefill_many([(0, short, sink), (1, short, sink)])
    engine.prefill(0, long_, sink)
    zeros = np.zeros(2, np.int32)
    engine.decode(zeros, np.zeros((2, len(sink)), np.int32), zeros,
                  np.zeros(2, bool), steps=2)
    for name in ("prefill_fused_32", "prefill_batch_32", "prefill_chunk_32",
                 "decode_block_2"):
        paths = set(trace.program_scopes(name).values())
        found = {part for v in paths for part in v.split("/")}
        assert {"index", "attn_full", "attn_window"} <= found, name
        assert all("attn_full" in v.split("/") for v in paths
                   if "index" in v.split("/")), name
    assert "index" in trace.MODEL_SCOPES
    # two full layers' scores a step, two steps a dispatch
    assert trace.INDEX_STATS == ("index_products", "index_kernel")
    assert {k: engine.decode_grouped[k] for k in trace.INDEX_STATS} == {
        "index_products": 4, "index_kernel": 4 if impl else 0}
    slot_keys = 2 * len(sink) * 16 * cfg.attn_period[0].index_dim
    for name, fn in (("decode_block_2", engine._decode_fns[2]),
                     ("prefill_chunk_32", engine._prefill_paged_fns[32])):
        jaxpr = fn.trace(*trace._programs[name][-1].args).jaxpr
        ours = [str(eqn.source_info.name_stack)
                for eqn in _pallas_calls(jaxpr)
                if eqn.params["name"] == "index_scores"]
        assert ours == (2 * ["attn/attn_full/index/index_scores"]
                        if impl else []), (name, ours)
        if name == "decode_block_2":    # [slots, table places, Di] float32
            assert any(np.prod(shape) == slot_keys and dtype == "float32"
                       for shape, dtype in _gathers(jaxpr)) == (not impl)
    assert trace.phase_of(
        "jit(decode_block_2)/while/body/closed_call/blocks/while/body/"
        "closed_call/attn/attn_full/index/index_scores/pallas_call") == (
            "forward", "index")
    sched = engine.make_scheduler()
    sched.lengths[:] = [30, 5]
    stats = sched._decode_stats(1, [0, 1], np.asarray([True, True]))
    assert set(trace.SELECT_STATS) <= set(stats)
    assert (stats["index_rows"], stats["selected_rows"]) == (
        2 * (31 + 6), 2 * (16 + 6))
    sched.close()
    plain = ServingEngine(
        CFG, G.init_params(CFG, jax.random.PRNGKey(0)), ServingConfig(
            num_slots=2, page_size=8, max_model_len=32, prefill_chunk=16,
            dtype="float32"))
    plain.decode(zeros, np.zeros((2, 4), np.int32), zeros, np.zeros(2, bool))
    assert not plain.decode_grouped
    plain = plain.make_scheduler()
    assert not set(trace.SELECT_STATS) & set(
        plain._decode_stats(1, [0], np.asarray([True, False])))
    plain.close()


def test_scheduler_run_yields_the_span_vocabulary(traced_serving):
    engine, reqs, events = traced_serving
    names = {e[0] for e in events}
    assert names >= {
        trace.SERVE_STEP, trace.SERVE_HOUSEKEEPING, trace.SERVE_ADMIT_CLAIM,
        trace.SERVE_ADMIT_PREFILL, trace.SERVE_ADMIT_COMMIT,
        trace.SERVE_GROW, trace.SERVE_DECODE, trace.SERVE_COMMIT,
        trace.ENGINE_PREFILL_FUSED, trace.ENGINE_PREFILL_CHUNK,
        trace.ENGINE_PREFILL_BATCH,
        trace.ENGINE_PREFILL_SAMPLE, trace.ENGINE_DECODE_ENQUEUE,
        trace.ENGINE_DECODE_FETCH}, names
    # the scratch cache and the scatter are the dense path's alone
    dense = {trace.ENGINE_PREFILL_SCRATCH, trace.ENGINE_PREFILL_SCATTER}
    assert names & dense == (set() if engine._chunk_to_pages else dense)

    def stats(name):
        return [e[3] for e in events if e[0] == name]

    # counts at the boundary: what was padded, what was real
    padded = [s for n in (trace.ENGINE_PREFILL_FUSED,
                          trace.ENGINE_PREFILL_CHUNK,
                          trace.ENGINE_PREFILL_BATCH) for s in stats(n)]
    assert padded and all(0 < s["real_tokens"] <= s["padded_tokens"]
                          for s in padded)
    assert sum(s["real_tokens"] for s in padded) == sum(
        len(r.prompt) for r in reqs)
    (batch,) = stats(trace.ENGINE_PREFILL_BATCH)   # 5 and 9 in a bucket of 2
    assert (batch["real_tokens"], batch["padded_tokens"]) == (14, 32)
    # a chunk that wrote its own pages says so: all of its tokens or none
    chunks = stats(trace.ENGINE_PREFILL_CHUNK)          # 40 tokens: 16, 16, 8
    assert [s["padded_tokens"] for s in chunks] == [16, 16, 16]
    assert [s["paged_tokens"] for s in chunks] == (
        [16, 16, 16] if engine._chunk_to_pages else [0, 0, 0])
    # the head's product ran where a prompt ended, on its last real token:
    # in the last chunk alone, and on every row of the batch's bucket
    assert [s["head_tokens"] for s in chunks] == [0, 0, 1]
    assert batch["head_tokens"] == 2
    assert all(s["head_tokens"] == 1
               for s in stats(trace.ENGINE_PREFILL_FUSED))
    # every token a request holds is its prefill's sample or one slot's share
    # of a decode dispatch: the dispatches' steps x active cover the rest
    decodes = stats(trace.SERVE_DECODE)
    assert all(s["steps"] in (1, 2) and 1 <= s["active"] <= 3
               and s["live_kv_tokens"] > 0 for s in decodes)
    # how many cache layers a step walks, and how full the pool is: 2 layers
    # run once; 3 slots x 8 pages of 8 tokens (page 0, the sink, holds none)
    assert all(s["cache_layers"] == 2 and s["pool_tokens"] == 192
               and s["live_kv_tokens"] <= s["pool_tokens"] for s in decodes)
    # the pages the paged kernel's grid walks, of the 3 x 8 table slots it
    # would walk dead ones and all: they cover the tokens held and the new one
    assert all(s["table_slots"] == 24 and s["active"] <= s["live_pages"]
               <= s["table_slots"]
               and s["live_pages"] * 8 >= s["live_kv_tokens"] + s["active"]
               for s in decodes)
    held = sum(len(r.tokens) for r in reqs)
    assert held - len(reqs) <= sum(s["steps"] * s["active"] for s in decodes)
    # every request's first token is the input of one dispatch, taken from
    # the device where a prefill program left it there: all four, or all but
    # the prompt that kept the dense scratch cache (waited for where it ends)
    assert all(set(trace.FRESH_STATS) <= set(s)
               and s["fresh_on_device"] <= s["fresh"] <= s["active"]
               for s in decodes)
    assert sum(s["fresh"] for s in decodes) == len(reqs)
    assert sum(s["fresh_on_device"] for s in decodes) == (
        4 if engine._chunk_to_pages else 3)
    # a count nobody reads is not recorded (docs/TRACING.md names the readers)
    carried = {n: set().union(*(s.keys() for s in stats(n))) - {"_r"}
               for n in names}            # _r: the profiler's own step mark
    assert {n: k for n, k in carried.items() if k} == {
        trace.SERVE_STEP: {"step_num"},
        trace.SERVE_ADMIT_PREFILL: {"rids"},
        trace.SERVE_DECODE: {"steps", "active", "live_kv_tokens",
                             "cache_layers", "pool_tokens", "live_pages",
                             "table_slots", "fresh", "fresh_on_device",
                             *trace.PAGED_STATS},
        trace.ENGINE_PREFILL_FUSED: {"real_tokens", "padded_tokens",
                                     "head_tokens"},
        trace.ENGINE_PREFILL_CHUNK: {"real_tokens", "padded_tokens",
                                     "paged_tokens", "head_tokens"},
        trace.ENGINE_PREFILL_BATCH: {"real_tokens", "padded_tokens",
                                     "head_tokens"}}
    rids = " ".join(str(s["rids"]) for s in stats(trace.SERVE_ADMIT_PREFILL))
    assert sorted(int(x) for x in rids.split()) == sorted(
        r.rid for r in reqs)
    # an engine span lies inside the scheduler span that caused it
    steps = [(a, b) for n, a, b, _ in events if n == trace.SERVE_STEP]
    for n, a, b, _ in events:
        if n.startswith("engine."):
            assert any(s <= a and b <= e for s, e in steps), n
    # a staged step's decode is enqueued inside its admission, before the
    # wait for the first tokens, and its serve.decode only fetches
    cycles = [(a, b) for n, a, b, _ in events
              if n == trace.SERVE_ADMIT_PREFILL]
    enqueued = [(a, b) for n, a, b, _ in events
                if n == trace.ENGINE_DECODE_ENQUEUE]
    waits = [a for n, a, _, _ in events if n == trace.ENGINE_PREFILL_SAMPLE]
    staged = [(a, b) for a, b in enqueued
              if any(s <= a and b <= e for s, e in cycles)]
    assert len(staged) == len(cycles) == 2
    for (a, b), (s, e) in zip(staged, cycles):
        assert any(b <= w <= e for w in waits)


def test_a_zero_expert_router_says_a_fifth_count():
    """``trace.ROUTED_ZERO``: a decode program whose router also scores
    zero-compute experts returns five counts a step and its ``serve.decode``
    span says ``routed_zero`` beside ``ROUTING_STATS``, summed over the
    dispatch's steps; a program with four says the four names it said."""
    assert trace.ROUTED_ZERO == "routed_zero"
    assert {"dense_ffn", "routed_branch", "moe_zero"} <= set(
        trace.MODEL_SCOPES)
    five = trace.routing_stats(np.asarray([[24, 2, 2, 1, 9], [24, 1, 1, 1,
                                                              7]]))
    assert five == {"routed_total": 48, "routed_local": 3, "experts_hit": 3,
                    "expert_load_max": 1, "routed_zero": 16}
    four = trace.routing_stats(np.asarray([[24, 2, 2, 2], [24, 1, 1, 1]]))
    assert four == {"routed_total": 48, "routed_local": 3, "experts_hit": 3,
                    "expert_load_max": 2}
    with open(os.path.join(os.path.dirname(__file__), "..", "docs",
                           "TRACING.md")) as f:
        doc = f.read()
    for name in ("dense_ffn", "routed_branch", "moe_zero", "routed_zero"):
        assert f"`{name}`" in doc, name


def _counting(**facts):
    """An executor that only counts: a model's facts (``DecodeFacts``) and
    nothing to dispatch."""
    from deepspeed_tpu.inference.serving.model import DecodeFacts

    return types.SimpleNamespace(decode_counts=DecodeFacts(**facts).counts)


def test_a_gqa_decode_span_counts_the_tiles_its_groups_fetch():
    """``trace.GQA_STATS``: the scheduler of a model with fewer key-value
    heads says how many pages a grid step of its decode kernel takes and the
    page tiles its groups fetch for the live pages (``gqa_group_fill_pct`` =
    ``live_pages`` over ``gqa_group_tiles``); no other scheduler does."""
    from deepspeed_tpu.inference.serving.scheduler import (
        ContinuousBatchingScheduler)

    assert trace.GQA_STATS == ("gqa_group_tiles", "gqa_pages_per_step")
    stats = {}
    for g in (0, 1, 4):
        sched = ContinuousBatchingScheduler(
            executor=_counting(page_size=8, gqa_pages_per_step=g),
            num_slots=5, num_pages=64, page_size=8, pages_per_seq=16)
        sched.lengths[:] = [0, 7, 8, 40, 100]   # 1, 2, 6 and 13 pages live
        stats[g] = sched._decode_stats(
            1, [1, 2, 3, 4], np.asarray([False, True, True, True, True]))
    assert not set(trace.GQA_STATS) & set(stats[0])
    assert all(stats[g]["live_pages"] == 22 for g in stats)
    assert (stats[1]["gqa_group_tiles"], stats[1]["gqa_pages_per_step"]) == (
        22, 1)
    assert (stats[4]["gqa_group_tiles"], stats[4]["gqa_pages_per_step"]) == (
        4 + 4 + 8 + 16, 4)


def test_a_latent_decode_span_counts_the_tiles_its_groups_fetch():
    """``trace.MLA_STATS``: the scheduler of a model whose latent layers
    read pages says how many a grid step of ``paged_decode_mla`` takes and
    the page tiles its groups fetch for the live pages
    (``mla_group_fill_pct`` = ``live_pages`` over ``mla_group_tiles``); no
    other scheduler does, and the engine asks ``models/gpt`` for the number
    the kernel asks ``decode_attention`` for."""
    from deepspeed_tpu.inference.serving.scheduler import (
        ContinuousBatchingScheduler)
    from deepspeed_tpu.ops.pallas import decode_attention as DA

    assert trace.MLA_STATS == ("mla_group_tiles", "mla_pages_per_step")
    stats = {}
    for g in (0, 8):
        sched = ContinuousBatchingScheduler(
            executor=_counting(page_size=8, mla_pages_per_step=g),
            num_slots=5, num_pages=64, page_size=8, pages_per_seq=16)
        sched.lengths[:] = [0, 7, 8, 40, 100]   # 1, 2, 6 and 13 pages live
        stats[g] = sched._decode_stats(
            1, [1, 2, 3, 4], np.asarray([False, True, True, True, True]))
    assert not (set(trace.MLA_STATS) | set(trace.GQA_STATS)) & set(stats[0])
    assert not set(trace.GQA_STATS) & set(stats[8])
    assert (stats[8]["live_pages"], stats[8]["mla_group_tiles"],
            stats[8]["mla_pages_per_step"]) == (22, 8 + 8 + 8 + 16, 8)
    latent = G.GPTConfig(n_layer=2, n_head=4, d_model=64, attn_kind="mla",
                         q_lora_rank=32, kv_lora_rank=512, qk_rope_dim=64,
                         qk_nope_dim=16,
                         v_head_dim=16, norm="rmsnorm", linear_bias=False,
                         rotary=True)
    assert G.mla_pages_per_step(latent, 64, 48, jnp.bfloat16) == 8 == \
        DA.mla_pages_per_step(64, latent.latent_width, jnp.bfloat16, 48,
                              False)
    assert G.mla_pages_per_step(G.GPTConfig(n_layer=2, n_head=4, d_model=64),
                                64, 48, jnp.bfloat16) == 0


def test_a_paged_decode_span_counts_the_tiles_its_groups_fetch():
    """``trace.PAGED_STATS``: the scheduler of a model whose query heads each
    have a key head says how many pages a grid step of ``paged_decode``
    takes and the page tiles its groups fetch for the live pages
    (``paged_group_fill_pct`` = ``live_pages`` over ``paged_group_tiles``);
    no other scheduler does, and the engine asks ``models/gpt`` for the
    number the kernel asks ``decode_attention`` for: the shard's heads, a
    page a step over a quantized pool and for heads of 64."""
    from deepspeed_tpu.inference.serving.scheduler import (
        ContinuousBatchingScheduler)
    from deepspeed_tpu.ops.pallas import decode_attention as DA

    assert trace.PAGED_STATS == ("paged_group_tiles", "paged_pages_per_step")
    stats = {}
    for g in (0, 2):
        sched = ContinuousBatchingScheduler(
            executor=_counting(page_size=8, paged_pages_per_step=g),
            num_slots=5, num_pages=64, page_size=8, pages_per_seq=16)
        sched.lengths[:] = [0, 7, 8, 40, 100]   # 1, 2, 6 and 13 pages live
        stats[g] = sched._decode_stats(
            1, [1, 2, 3, 4], np.asarray([False, True, True, True, True]))
    others = set(trace.MLA_STATS) | set(trace.GQA_STATS)
    assert not (others | set(trace.PAGED_STATS)) & set(stats[0])
    assert not others & set(stats[2])
    assert (stats[2]["live_pages"], stats[2]["paged_group_tiles"],
            stats[2]["paged_pages_per_step"]) == (22, 2 + 2 + 6 + 14, 2)
    pythia = G.GPTConfig(n_layer=2, n_head=16, d_model=2048)
    assert pythia.head_dim == 128
    for width, shards, bits, g in ((32, 1, None, 2), (12, 1, None, 2),
                                   (32, 2, None, 4), (32, 1, 8, 1),
                                   (4, 1, None, 1)):
        assert G.paged_pages_per_step(pythia, 64, width, jnp.bfloat16, bits,
                                      shards) == g == DA.paged_pages_per_step(
            16 // shards, 64, 128, jnp.int8 if bits else jnp.bfloat16, width,
            bool(bits)), (width, shards, bits)
    assert G.paged_pages_per_step(       # heads of 64 keep the step a page
        G.GPTConfig(n_layer=2, n_head=4, d_model=256), 64, 32,
        jnp.bfloat16) == 1
    assert G.paged_pages_per_step(
        G.GPTConfig(n_layer=2, n_head=4, n_kv_head=2, d_model=512,
                    attn_kind="gqa", norm="rmsnorm", linear_bias=False,
                    rotary=True, rotary_interleaved=False, head_width=128),
        64, 32,
        jnp.bfloat16) == 0


def test_the_scratch_cache_has_a_span(traced_serving):
    """A chunked prompt's dense scratch cache is built under
    ``engine.prefill.scratch``, before its first chunk and inside its
    admission cycle: an idle gap that begins there has the program's name."""
    engine, reqs, events = traced_serving
    chunked = [r for r in reqs if len(r.prompt) > 16]
    scratch = sorted((a, b) for n, a, b, _ in events
                     if n == trace.ENGINE_PREFILL_SCRATCH)
    if engine._chunk_to_pages:      # no scratch cache: the chunks wrote pages
        assert not scratch and len(chunked) == 1
        return
    assert len(scratch) == len(chunked) == 1
    (a, b), = scratch
    cycles = [(s, e) for n, s, e, _ in events
              if n == trace.SERVE_ADMIT_PREFILL]
    assert any(s <= a and b <= e for s, e in cycles)
    first_chunk = min(s for n, s, _, _ in events
                      if n == trace.ENGINE_PREFILL_CHUNK)
    assert b <= first_chunk
    assert not [st for n, _, _, st in events
                if n == trace.ENGINE_PREFILL_SCRATCH and set(st) - {"_r"}]


def test_xla_compile_is_in_the_vocabulary():
    """``xla.compile`` is the record's alone (the profiler names its own
    compile events), so it is no annotation prefix; every other name is."""
    assert trace.XLA_COMPILE == "xla.compile"
    assert not trace.XLA_COMPILE.startswith(trace.SPAN_PREFIXES)
    spans = [v for k, v in vars(trace).items()
             if k.startswith(("SERVE_", "ENGINE_", "TRAIN_"))]
    assert trace.ENGINE_PREFILL_SCRATCH in spans and len(spans) == 21
    assert all(v.startswith(trace.SPAN_PREFIXES) for v in spans)


@pytest.mark.parametrize("name, value, counts", [
    ("DEVICE_STARVED", "device.starved", ("after", "by")),
    ("HOST_GC", "host.gc", ("generation",))])
def test_an_event_of_the_record_alone_is_no_annotation(name, value, counts):
    """``device.starved`` is known only once it is over and ``host.gc`` is
    the interpreter's: neither is ever written as an annotation, so no
    annotation prefix matches them, and ``trace.py`` names their counts."""
    assert getattr(trace, name) == value
    assert not value.startswith(trace.SPAN_PREFIXES)
    (line,) = [ln for ln in open(trace.__file__).read().splitlines()
               if ln.startswith(f"{name} = ")]
    assert line.split("#")[1].replace(",", " ").split() == list(counts)


@pytest.mark.parametrize("n, dispatches", [
    (2, [(2, 2)]), (3, [(3, 4)]), (5, [(4, 4), (1, 2)])])
def test_an_admission_cycles_batch_spans_add_up(n, dispatches, tmp_path,
                                                monkeypatch):
    """A span a dispatch: ``real_tokens`` those of its prompts (3 + j
    tokens prompt ``j``), ``padded_tokens`` what the program of its row
    bucket computed, 16 a row, ``head_tokens`` the rows its head ran on: one
    a row of the bucket."""
    from deepspeed_tpu.inference.serving import ServingConfig, ServingEngine
    from deepspeed_tpu.inference.serving import engine as engine_mod

    monkeypatch.setattr(engine_mod, "BATCH_TOKENS", 64)   # rows of 16: 2, 4
    engine = ServingEngine(
        CFG, G.init_params(CFG, jax.random.PRNGKey(0)), ServingConfig(
            num_slots=5, page_size=8, max_model_len=32, prefill_chunk=16,
            dtype="float32"))
    sink = np.zeros(engine.serving.pages_per_seq, np.int32)
    prompts = [np.ones(3 + j, np.int32) for j in range(n)]
    engine.prefill_many([(0, prompts[0], sink), (1, prompts[1], sink)])
    with _session(str(tmp_path)):
        engine.prefill_many([(j, p, sink) for j, p in enumerate(prompts)])
    spans = sorted((e for e in _host_events(str(tmp_path))
                    if e[0] == trace.ENGINE_PREFILL_BATCH),
                   key=lambda e: e[1])
    lengths = iter(len(p) for p in prompts)
    assert [(s["real_tokens"], s["padded_tokens"], s["head_tokens"])
            for _, _, _, s in spans] \
        == [(sum(next(lengths) for _ in range(rows)), 16 * bucket, bucket)
            for rows, bucket in dispatches]
    assert next(lengths, None) is None


def test_request_phases_are_ordered(traced_serving):
    _, reqs, _ = traced_serving
    for r in reqs:
        assert r.t_submit <= r.t_admit <= r.t_first_token <= r.t_done


def test_train_step_spans(tiny_train, tmp_path):
    engine, batch = tiny_train
    engine.train_batch(batch)              # compiled before the session
    step = engine.global_steps
    with _session(str(tmp_path)):
        engine.train_batch(batch)
    events = _host_events(str(tmp_path))
    names = [e[0] for e in events]
    for name in (trace.TRAIN_STEP, trace.TRAIN_PLACE_BATCH,
                 trace.TRAIN_DISPATCH, trace.TRAIN_SYNC, trace.TRAIN_POST):
        assert names.count(name) == 1, names
    (stats,) = [e[3] for e in events if e[0] == trace.TRAIN_STEP]
    assert stats["step_num"] == step and set(stats) <= {"step_num", "_r"}
    t0, t1 = [(a, b) for n, a, b, _ in events if n == trace.TRAIN_STEP][0]
    assert all(t0 <= a and b <= t1 for _, a, b, _ in events)


def test_counts_are_called_once_a_span_outside_a_session():
    """Outside a profiler session a span computes its counts all the same,
    once, where it is made: the record keeps them (it computed none before
    the package had a record, PR 36)."""
    calls = []

    def counts():
        calls.append(1)
        return {"steps": 2}

    with trace.span(trace.SERVE_DECODE, counts):
        assert calls == [1]
    assert calls == [1]
    assert trace.recorded()[-1].counts == {"steps": 2}


def test_one_tracing_mechanism():
    """The profiler's annotations appear in ``profiling/trace.py`` only."""
    import re

    pkg = os.path.dirname(os.path.dirname(trace.__file__))
    hits = []
    for path in glob.glob(os.path.join(pkg, "**", "*.py"), recursive=True):
        if re.search(r"\b(Step)?TraceAnnotation\b", open(path).read()):
            hits.append(os.path.relpath(path, pkg))
    assert hits == [os.path.join("profiling", "trace.py")]
