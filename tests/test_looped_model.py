"""A looped model on the normal path (``models/gpt.py`` with its block said as
data: RMSNorm, a SiLU-gated MLP, bias-free linears, a norm on each sublayer's
output, rotary over the whole head at base 1e6, the stack run ``ut_steps``
times with the final norm closing every pass) against the benchmark's plain
reference of those equations, ``benchmark/reference/ouro_ref.py``:
``served_contract.py`` bound to the family, and what is the family's own.

Seeded random weights at a small size (2 layers x 3 loops, d 64, 4 heads), in
float32 on the CPU. Tolerance ``TOL`` = 2e-5 on logits of size 1.5 and states
of size 1: both sides are float32, the program sums a dot in another order
than the reference's ``highest``-precision one-sequence forward, and six block
applications carry that on; what was read is 1.2e-6 at most. A wrong layer,
pass, page, position or norm moves a logit by 1e-2 or more.
"""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark.reference import ouro_ref
from deepspeed_tpu.models import gpt as G
from pages_by_hand import pages_by_hand
from served_contract import ServedFamilyContract, moved

TOL = ServedFamilyContract.TOL
MODEL = {"vocab_size": 96, "n_layer": 2, "n_head": 4, "d_model": 64,
         "d_ff": 160, "total_ut_steps": 3, "rope_theta": 1e6,
         "rms_norm_eps": 1e-6, **ouro_ref.COVERS}
CFG = G.GPTConfig(
    vocab_size=96, n_layer=2, n_head=4, d_model=64, d_ff=160, max_seq_len=128,
    rotary=True, rotary_pct=1.0, tie_embeddings=False, layer_norm_eps=1e-6,
    activation="silu", norm="rmsnorm", mlp_gated=True, linear_bias=False,
    post_norm=True, rope_theta=1e6, rotary_float32=True, ut_steps=3,
    loop_norm=True, state_layers=(1, 2), early_exit_threshold=1.0)
PROMPT, STEPS, PAGE = 12, 9, 8
TABLES = np.asarray([[5, 2, 7], [8, 1, 4]], np.int32)     # out of order


def _reference_trace(params, ids):
    """The reference's boundaries [n_seg + 1, T, d] and cached rows
    [cache layers, H, T, Dh] of one sequence, chained from its own states."""
    x = ouro_ref.embed(MODEL, params, ids)
    bounds, keys, values = [x], [], []
    for k in range(len(ouro_ref.segments(MODEL))):
        x, kk, vv = ouro_ref.segment(MODEL, params, k, x)
        bounds.append(x)
        keys.append(kk)
        values.append(vv)
    return (np.stack(bounds), np.concatenate(keys), np.concatenate(values))


def _commit(c):
    win = jnp.zeros((2, 2, 3, 4, 16))
    return G.commit_window_kv(G.init_paged_cache(CFG, 9, PAGE, jnp.float32),
                              win, win, jnp.asarray(TABLES),
                              jnp.zeros(2, jnp.int32), jnp.ones(2, jnp.int32))


def _quantized_stack(c):
    tiny = G.PRESETS["tiny"]
    params = G.quantize_for_inference(
        tiny, G.init_params(tiny, jax.random.PRNGKey(0)), group_size=64)
    looped = dataclasses.replace(tiny, ut_steps=2)
    return G.forward_with_cache(looped, params, jnp.zeros((1, 4), jnp.int32),
                                G.init_cache(looped, 1, 8, jnp.float32))


def _scans(jaxpr, found):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scan":
            found.append(eqn)
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else (v,)):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    _scans(inner, found)
    return found


class TestOuro(ServedFamilyContract):
    """The contract bound to a size of its own (2 layers x 3 loops), not to
    ``tiny-ouro-serve.json`` (2 loops): the boundaries and the cache layers
    below are counted by hand at this size."""
    REF, MODEL, CFG = ouro_ref, MODEL, CFG
    ENGINE = dict(ServedFamilyContract.ENGINE, page_size=PAGE)
    FORWARDS = {"2 x 21": (PROMPT + STEPS, 0)}
    NEW_FIELDS = {"norm": "rmsnorm", "mlp_gated": True, "linear_bias": False,
                  "post_norm": True, "rope_theta": 1e6,
                  "rotary_float32": True, "ut_steps": 2, "loop_norm": True,
                  "state_layers": (1,), "early_exit_threshold": 1.0}
    # prefix reuse, fingerprints, a drafter, a prefill role and page export
    # carry a looped stack: not in its row
    REFUSALS = {**ServedFamilyContract.REFUSALS,
                "commit_window_kv": _commit,
                "a quantized weight stack": _quantized_stack}
    REFUSES = {**dict.fromkeys(
        ("verify", "tp", "GPTStream", "gpt_pipe", "gpt_moe",
         "a quantized stack", "kv8 pool", "kv4 pool"), "norm="),
        "commit_window_kv": "ut_steps", "a quantized weight stack": "ut_steps"}
    test_the_engines_prefill_then_decode_equal_the_full_forward = None
    test_a_mixed_run_with_a_preemption_leaves_a_clean_audit = None
    test_a_float32_stream_over_bf16_weights_and_pages = None
    test_a_planted_fault_fails_the_comparison = None

    def the_tree(self, params):
        assert sorted(params) == ["blocks", "exit_gate_b", "exit_gate_w",
                                  "lm_head", "lnf_scale", "wte"]
        assert sorted(params["blocks"]) == [
            "attn_out_w", "ln1_scale", "ln2_scale", "mlp_down_w", "mlp_gate_w",
            "mlp_up_w", "post_attn_scale", "post_mlp_scale", "qkv_w"]
        assert params["exit_gate_w"].shape == (64, 1)
        assert params["blocks"]["mlp_gate_w"].shape == (2, 64, 160)
        n = sum(x.size for x in jax.tree_util.tree_leaves(params["blocks"]))
        assert n == CFG.n_layer * CFG.layer_params()

    def the_sizes(self):
        """``cache_layers`` = ut_steps x n_layer at every sizing site."""
        from deepspeed_tpu.runtime import aot

        assert G.cache_layers(CFG) == 6
        assert G.cache_layers(G.PRESETS["tiny"]) == 2
        assert G.init_cache(CFG, 2, 16)["k"].shape == (6, 2, 4, 16, 16)
        assert G.init_paged_cache(CFG, 9, PAGE)["v_pages"].shape == \
            (6, 4, 9, PAGE, 16)
        assert G.paged_kv_bytes_per_token(CFG) == 2 * 6 * 64 * 2
        assert G.paged_kv_bytes_per_token(CFG) == ouro_ref.kv_bytes_per_token(
            MODEL)
        # aot: the draft cache of a looped draft model
        spec = aot.speculation_hbm_bytes("tiny", draft_model=CFG, num_slots=2,
                                         max_model_len=32, spec_k=2)
        assert spec["parts"]["draft_cache"] == 2 * 6 * 2 * 4 * 32 * 16 * 2


@pytest.fixture(scope="module")
def served():
    """Prefill through ``forward_with_cache``, the rows scattered into pages
    that are out of order, then 9 ``paged_decode_step``s; beside it the
    reference's forward over the same tokens."""
    params = moved(G.init_params(CFG, jax.random.PRNGKey(0)))
    ids = np.random.default_rng(0).integers(
        0, 96, (2, PROMPT + STEPS)).astype(np.int32)
    want = np.stack([ouro_ref.logits(MODEL, params, row) for row in ids])
    cache = G.init_cache(CFG, 2, 16, jnp.float32)
    prefill, cache, pre_states = G.forward_with_cache(
        CFG, params, jnp.asarray(ids[:, :PROMPT]), cache, return_states=True)
    pool = G.write_prompt_kv_batch(
        G.init_paged_cache(CFG, 9, PAGE, jnp.float32), cache,
        jnp.asarray(TABLES), jnp.full((2,), PROMPT, jnp.int32))
    decoded, dec_states = [], []
    for step in range(STEPS):
        logits, pool, st = G.paged_decode_step(
            CFG, params, jnp.asarray(ids[:, PROMPT + step]), pool,
            jnp.asarray(TABLES), jnp.full((2,), PROMPT + step, jnp.int32),
            impl="gather", return_states=True)
        decoded.append(np.asarray(logits))
        dec_states.append(np.asarray(st))
    return dict(params=params, ids=ids, want=want,
                prefill=np.asarray(prefill), cache=cache, pool=pool,
                pre_states=np.asarray(pre_states), decoded=decoded,
                dec_states=dec_states)


def test_prefill_then_nine_paged_steps_equal_the_full_forward(served):
    """Logits, not tokens, at every position."""
    assert np.abs(served["prefill"]
                  - served["want"][:, :PROMPT]).max() < TOL
    for step, got in enumerate(served["decoded"]):
        assert np.abs(got - served["want"][:, PROMPT + step]).max() < TOL, step


def test_cache_layer_u_l_holds_what_the_reference_caches(served):
    """Cache layer ``n_layer * u + l``, in the order the forward applies the
    blocks: the dense cache after the prefill and the pool after the steps,
    read back through the block table."""
    T = PROMPT + STEPS
    pool_k = np.asarray(served["pool"]["k_pages"])
    pool_v = np.asarray(served["pool"]["v_pages"])
    assert pool_k.shape[0] == 6
    for row in range(2):
        _, want_k, want_v = _reference_trace(served["params"],
                                             served["ids"][row])
        dense = np.asarray(served["cache"]["k"])[:, row, :, :PROMPT]
        assert np.abs(dense - np.asarray(want_k)[:, :, :PROMPT]).max() < TOL
        for got, want in ((pool_k, want_k), (pool_v, want_v)):
            rows = got[:, :, TABLES[row]].reshape(6, 4, -1, 16)[:, :, :T]
            assert np.abs(rows - np.asarray(want)).max() < TOL


def test_the_states_output_equals_the_references_boundaries(served):
    """Embedding rows first, then after layers 1 and 2 of every pass, the 2nd
    after the pass's closing norm: 1 + 3 x 2 boundaries."""
    assert served["pre_states"].shape == (2, 7, PROMPT, 64)
    assert served["dec_states"][0].shape == (2, 7, 64)
    for row in range(2):
        want, _, _ = _reference_trace(served["params"], served["ids"][row])
        # the reference cuts where SEGMENT_BLOCKS says (one stretch a pass at
        # this depth); state_layers (1, 2) also reports the middle of a pass
        ends = np.asarray(want)
        got = served["pre_states"][row]
        assert np.array_equal(got[0], ends[0][:PROMPT])
        for u in range(3):
            assert np.abs(got[2 + 2 * u] - ends[1 + u][:PROMPT]).max() < TOL
        for step, st in enumerate(served["dec_states"]):
            for u in range(3):
                assert np.abs(st[row, 2 + 2 * u]
                              - ends[1 + u][PROMPT + step]).max() < TOL
    # the middle boundary: the stream after layer 1 of pass 0, by the model's
    # own one-layer-deep config
    one = dataclasses.replace(CFG, n_layer=1, ut_steps=1, state_layers=(),
                              loop_norm=False)
    params = served["params"]
    first = dict(params, blocks=jax.tree_util.tree_map(lambda a: a[:1],
                                                       params["blocks"]))
    cache = G.init_cache(one, 2, 16, jnp.float32)
    _, cache = G.forward_with_cache(
        one, first, jnp.asarray(served["ids"][:, :PROMPT]), cache)
    assert np.abs(np.asarray(cache["k"])[0] - np.asarray(
        served["cache"]["k"])[0]).max() < TOL


def test_prompts_go_straight_to_pages_as_the_dense_cache_would_put_them(
        served):
    """``paged_prefill_step``: the rows of a padded prompt batch written in
    the layer loop lie where a loop by hand puts the dense cache's, a row of
    length 0 writes nothing, ``starts`` skips borrowed positions, and the
    logits are each row's last real token's."""
    params, ids = served["params"], served["ids"]
    padded = np.zeros((3, 16), np.int32)
    padded[:2, :PROMPT] = ids[:, :PROMPT]
    tables = np.concatenate([TABLES, [[3, 6, 0]]]).astype(np.int32)
    lengths = jnp.asarray([PROMPT, PROMPT, 0], jnp.int32)
    starts = jnp.asarray([0, PAGE, 0], jnp.int32)
    logits, pool, states = G.paged_prefill_step(
        CFG, params, jnp.asarray(padded),
        G.init_paged_cache(CFG, 9, PAGE, jnp.float32), jnp.asarray(tables),
        lengths, starts)
    assert np.abs(np.asarray(logits)[:2]
                  - served["want"][:, PROMPT - 1]).max() < TOL
    assert np.abs(np.asarray(states)[:2, :, :PROMPT]
                  - served["pre_states"]).max() < TOL
    empty = np.zeros(pool["k_pages"].shape, np.float32)
    for side in ("k", "v"):
        want, _ = pages_by_hand(empty, served["cache"][side], TABLES,
                                [PROMPT, PROMPT], [0, PAGE])
        assert np.abs(np.asarray(pool[f"{side}_pages"]) - want).max() < TOL
    # row 1 borrows its first page (8), row 2 is empty: pages 8, 3, 6 and the
    # sink are as they were
    assert not np.asarray(pool["k_pages"])[:, :, [0, 3, 6, 8]].any()


def test_the_exit_gate_is_held_and_never_read(served):
    params = dict(served["params"])
    params["exit_gate_w"] = params["exit_gate_w"] + 100.0
    params["exit_gate_b"] = params["exit_gate_b"] - 100.0
    got = G.forward(CFG, params, jnp.asarray(served["ids"]), train=False)
    assert np.abs(np.asarray(got) - served["want"]).max() < TOL
    with pytest.raises(ValueError, match="early_exit_threshold"):
        dataclasses.replace(CFG, early_exit_threshold=0.9)
    with pytest.raises(ValueError, match="norm"):
        dataclasses.replace(CFG, norm="batchnorm")
    with pytest.raises(ValueError, match="state_layers"):
        dataclasses.replace(CFG, state_layers=(3,))


def test_the_decode_step_carries_one_pool_through_both_loops():
    """``make_jaxpr`` of the step: a scan over the 3 passes around a scan over
    the 2 layers; the pool [6, H, P, ps, Dh] is a carry of both, no input a
    scan slices and no output it stacks; the weights are the layer scan's
    input at their own [2, ...] and nothing holds them three times."""
    params = G.init_params(CFG, jax.random.PRNGKey(0))
    pool = G.init_paged_cache(CFG, 9, PAGE, jnp.float32)
    jaxpr = jax.make_jaxpr(lambda p, c: G.paged_decode_step(
        CFG, p, jnp.zeros(2, jnp.int32), c, jnp.asarray(TABLES),
        jnp.full((2,), 5, jnp.int32), impl="gather"))(params, pool)
    scans = _scans(jaxpr.jaxpr, [])
    assert sorted(e.params["length"] for e in scans) == [2, 3]
    pool_shape = pool["k_pages"].shape
    for eqn in scans:
        n_consts, n_carry = eqn.params["num_consts"], eqn.params["num_carry"]
        carried = [v.aval.shape for v in
                   eqn.invars[n_consts:n_consts + n_carry]]
        scanned = [v.aval.shape for v in eqn.invars[n_consts + n_carry:]]
        stacked = [v.aval.shape for v in eqn.outvars[n_carry:]]
        assert carried.count(pool_shape) == 2               # keys and values
        assert pool_shape not in scanned + stacked
        assert not [s for s in scanned + stacked if s and s[0] == 6]
        if eqn.params["length"] == 2:       # the layer scan reads the stack
            assert (2, 64, 192) in scanned
        else:                               # the pass scan scans nothing
            assert scanned == []
    every = [v.aval.shape for eqn in _scans(jaxpr.jaxpr, [])
             for sub in [eqn.params["jaxpr"].jaxpr]
             for e in sub.eqns for v in e.outvars]
    assert not [s for s in every if s[:2] == (3, 2) or s[:1] == (6,)
                and s != pool_shape]


def test_the_engine_serves_the_looped_model_as_the_reference_would():
    """Through ``ServingEngine`` and its scheduler: a short prompt (straight
    to pages), two that share an admission batch, one longer than a chunk
    (serial chunks, then the scatter); greedy tokens are the reference's."""
    from deepspeed_tpu.inference.serving import (Request, ServingConfig,
                                                 ServingEngine)

    params = moved(G.init_params(CFG, jax.random.PRNGKey(2)))
    engine = ServingEngine(CFG, params, ServingConfig(
        num_slots=3, page_size=PAGE, max_model_len=64, prefill_chunk=16,
        dtype="float32", decode_block=2))
    sched = engine.make_scheduler()
    rng = np.random.default_rng(2)
    reqs = [Request(prompt=rng.integers(1, 96, n).astype(np.int32),
                    max_new_tokens=m) for n, m in [(5, 4), (9, 3), (40, 5)]]
    for r in reqs:
        sched.submit(r)
    sched.run_to_completion()
    for r in reqs:
        seq = list(r.prompt)
        for tok in r.tokens:
            logits = np.asarray(ouro_ref.logits(
                MODEL, params, np.asarray(seq, np.int32), positions=[-1]))[0]
            top = np.sort(logits)[-2:]
            if top[1] - top[0] > 1e-4:        # not a tie
                assert tok == int(np.argmax(logits)), (len(r.prompt), seq)
            seq.append(tok)
    assert engine.decode_states.shape[1:] == (3, 7, 64)
    assert engine.prefill_states and all(
        s.shape[1] == 7 for s in engine.prefill_states)


MIXES = {
    "layernorm_gated_biased_looped_unclosed": dict(
        mlp_gated=True, ut_steps=2, activation="silu"),
    "rmsnorm_parallel_residual_closed": dict(
        norm="rmsnorm", rotary=True, rotary_pct=0.5, parallel_residual=True,
        ut_steps=2, loop_norm=True, post_norm=True),
    "bias_free_learned_positions": dict(
        linear_bias=False, rope_theta=5e5, state_layers=(2,)),
}


@pytest.mark.parametrize("mix", sorted(MIXES))
def test_any_mix_of_the_fields_runs_the_same_on_all_three_forwards(mix):
    """A config with a new field set runs right on every forward or raises:
    ``forward``, ``forward_with_cache`` and prefill-to-pages then
    ``paged_decode_step`` agree on the logits of every position."""
    cfg = dataclasses.replace(G.PRESETS["tiny"], **MIXES[mix])
    params = moved(G.init_params(cfg, jax.random.PRNGKey(1)), by=0.02)
    ids = np.random.default_rng(1).integers(0, 256, (2, 14)).astype(np.int32)
    want = np.asarray(G.forward(cfg, params, jnp.asarray(ids), train=False))
    cached, _ = G.forward_with_cache(cfg, params, jnp.asarray(ids),
                                     G.init_cache(cfg, 2, 16, jnp.float32))
    assert np.abs(np.asarray(cached) - want).max() < TOL
    padded = np.zeros((2, 16), np.int32)
    padded[:, :10] = ids[:, :10]
    logits, pool, _ = G.paged_prefill_step(
        cfg, params, jnp.asarray(padded),
        G.init_paged_cache(cfg, 9, PAGE, jnp.float32), jnp.asarray(TABLES),
        jnp.full((2,), 10, jnp.int32), jnp.zeros(2, jnp.int32))
    assert np.abs(np.asarray(logits) - want[:, 9]).max() < TOL
    for step in range(10, 14):
        logits, pool = G.paged_decode_step(
            cfg, params, jnp.asarray(ids[:, step]), pool, jnp.asarray(TABLES),
            jnp.full((2,), step, jnp.int32), impl="gather")
        assert np.abs(np.asarray(logits) - want[:, step]).max() < TOL
