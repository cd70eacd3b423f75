"""What every served architecture is held to, said once: the tests a family of
``benchmark/families`` passes against its plain reference of
``benchmark/reference``, at the size of its rehearsal configuration, in
float32 on the CPU. A family's test file binds it once,

    class TestLaguna(ServedFamilyContract):
        FAMILY, REF, CONFIG = family, ref, "tiny-laguna-serve"
        PATHS = {...}; FAULTS = {...}; NEW_FIELDS = {...}; REFUSES = {...}

and adds the tests that are its own (its kernels, its router, its sizes). A
file stays the unit ``--dist loadfile`` deals, so each binding is its own file
and this module collects nothing (no ``test_`` prefix).

The cases come from the class's tables through ``pytest_generate_tests`` in
``conftest.py`` (``TABLES``): a test that asks for ``path`` runs once for each
key of ``PATHS``, under that key as its id. A family that has no such table,
or that a shared test does not fit, sets that test to ``None`` in its class.

Engines are built once a class and shape (``engines``): a case takes the
slots and pages of a shared engine again, which
``test_a_slot_used_again_gives_what_a_fresh_engine_gives`` licenses (a prompt
from position 0 overwrites its pages, its ring and its state). A case that
patches the model's code builds nothing jitted it would share: a jitted
program keeps what it traced.
"""

import dataclasses
import functools
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.models import gpt as G

CONFIGS = os.path.join(os.path.dirname(__file__), "..", "benchmark",
                       "configs")
PAGES = G.POOL_KEYS[:2]     # the page pools: keys and values, or the latent


def config_file(name: str) -> dict:
    with open(os.path.join(CONFIGS, f"{name}.json")) as f:
        return json.load(f)


@jax.jit
def moved(params, seed=8, by=0.05):
    """Every leaf off its initial value, in float32: unit gains would hide a
    norm applied with another layer's gain, a zero bias a bias left out, and
    N(0, 0.02) router weights barely route."""
    leaves, tree = jax.tree_util.tree_flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(leaves))
    return jax.tree_util.tree_unflatten(tree, [
        x.astype(jnp.float32) + by * jax.random.normal(k, x.shape)
        for x, k in zip(leaves, keys)])


# the model's two serving steps as programs, traced once a configuration and
# shape: called eagerly, a step's scans are traced and compiled every call
prefill_step = jax.jit(G.paged_prefill_step, static_argnums=0)
decode_step = jax.jit(G.paged_decode_step, static_argnums=0,
                      static_argnames=("impl", "return_routing"))


def tables_of(engine, slots):
    """A block table that gives the ``j``-th of ``slots`` its own pages."""
    pps = engine.serving.pages_per_seq
    tables = np.zeros((engine.num_slots, pps), np.int32)
    for j, slot in enumerate(slots):
        tables[slot] = 1 + j * pps + np.arange(pps)
    return tables


def run_to_idle(engine, prompts, new_tokens, pages=None, after_step=None):
    """``prompts`` through the engine's scheduler, which may hand out only
    the first ``pages`` of the engine's pool (the sink among them), with
    ``after_step()`` after every step of it; the requests and the page
    audit."""
    from deepspeed_tpu.inference.serving.paging import PageAllocator
    from deepspeed_tpu.inference.serving.scheduler import Request

    sched = engine.make_scheduler()
    if pages is not None:
        sched.allocator = PageAllocator(pages)
    reqs = [Request(prompt=p, max_new_tokens=new_tokens) for p in prompts]
    for r in reqs:
        sched.submit(r)
    for _ in range(2000):
        if sched.idle:
            break
        sched.step()
        if after_step is not None:
            after_step()
    assert sched.idle
    audit = sched.audit()
    sched.close()
    return reqs, audit


# ------------------------------------------------------ the refusing paths
def _engine_with(**serving):
    return lambda c: c.new_engine(c.INIT(c.CFG, jax.random.PRNGKey(0)),
                                  num_slots=2, **serving)


def _verify(c):
    G.paged_verify_step(
        c.CFG, c.INIT(c.CFG, jax.random.PRNGKey(0)),
        jnp.zeros((2, 3), jnp.int32),
        G.init_paged_cache(c.CFG, 9, 8, jnp.float32, ring_slots=2),
        jnp.zeros((2, 4), jnp.int32), jnp.zeros(2, jnp.int32))


def _pipe(c):
    """Training through ``initialize`` too: a family's specs name no mesh
    axis (``partition_specs``: replicated), so a mesh that would shard it is
    what refuses; the pipelined and the expert model are the two
    ``initialize`` shards by layer and by expert."""
    from deepspeed_tpu.models import gpt_pipe

    gpt_pipe.build(c.CFG, 2, 2)


def _expert_model(c):
    from deepspeed_tpu.models import gpt_moe

    gpt_moe.build(gpt_moe.GPTMoEConfig(base=c.CFG, num_experts=2, moe_freq=1))


# path -> what takes it, given the family's class. Which of them refuse a
# family, and by which field's name, is the family's ``REFUSES``: a path that
# carries a family is not in its row
REFUSALS = {
    "tp": _engine_with(tp=2),
    "kv8 pool": _engine_with(kv_bits=8),
    "kv4 pool": _engine_with(kv_bits=4),
    "prefix cache": _engine_with(enable_prefix_cache=True),
    "page fingerprints": _engine_with(page_fingerprints=True),
    "a drafter": _engine_with(spec_drafter="ngram"),
    "a prefill role": _engine_with(role="prefill"),
    "page export": lambda c: _engine_with()(c).export_pages([1]),
    "verify": _verify,
    "a quantized stack": lambda c: G.quantize_for_inference(
        c.CFG, c.INIT(c.CFG, jax.random.PRNGKey(0))),
    "GPTStream": lambda c: G.GPTStream(c.CFG),
    "gpt_pipe": _pipe,
    "gpt_moe": _expert_model,
    "initialize over pipeline stages": _pipe,
}


def refuses(field: str, but=(), **others) -> dict:
    """A family's row: every path of ``REFUSALS`` refuses it by ``field``'s
    name, ``but`` those that carry it; ``others``: by another's."""
    return {**{p: field for p in REFUSALS if p not in but}, **others}


class ServedFamilyContract:
    # argument of a test -> the table whose keys are its cases
    TABLES = {"forward": "FORWARDS", "path": "PATHS", "fault": "FAULTS",
              "field": "NEW_FIELDS", "refusal": "REFUSES"}
    FAMILY = REF = None     # benchmark/families/<f>.py, reference/<f>_ref.py
    CONFIG = ""             # benchmark/configs/<CONFIG>.json: MODEL and CFG
    INIT = staticmethod(G.init_params)
    TOL = 2e-5              # on logits of size 1: float32 on both sides
    # the engine every case is served by, but for what the case overrides
    ENGINE = dict(num_slots=4, page_size=16, max_model_len=128,
                  prefill_chunk=32, dtype="float32", decode_block=2,
                  kernel_impl="kernel")
    FORWARDS = {"2 x 40": (40, 0)}  # case -> (length, seed) of two sequences
    PATHS = {}              # case -> prompt lengths, or (lengths, the
    #                         engine's overrides)
    STEPS = 8               # decode steps after a path's prefill
    MIXED = {}              # the mixed run's engine, as overrides
    PREEMPTED_AGAINST_REF = True    # the mixed run's tokens are also held
    #                         to the reference's (False: only to a roomy run's)
    FAULTS = {}             # case -> fault(monkeypatch) -> the faulty config,
    #                         or (config, the pool's type)
    FAULT_STEPS = 1         # decode steps a fault is carried through
    NEW_FIELDS = {}         # field the family added -> a value that is not
    #                         its default
    REFUSES = {}            # path of REFUSALS -> the field its refusal names
    REFUSALS = REFUSALS
    # the float32 stream over bf16 weights and the bf16 one, as overrides
    WIDE = dict(stream_float32=True, linear_out_float32=True)
    NARROW = dict(stream_float32=False)

    def __init_subclass__(cls, **kw):
        super().__init_subclass__(**kw)
        if cls.CONFIG:
            cls.MODEL = config_file(cls.CONFIG)["model"]
            cls.CFG = cls.FAMILY.config(cls.MODEL)

    # ----------------------------------------------------------- fixtures
    @classmethod
    def ids(cls, n, t, seed=0):
        return np.random.default_rng(seed).integers(
            0, cls.MODEL["vocab_size"], (n, t)).astype(np.int32)

    @classmethod
    def new_engine(cls, params, **serving):
        from deepspeed_tpu.inference.serving import (ServingConfig,
                                                     ServingEngine)

        return ServingEngine(cls.CFG, params,
                             ServingConfig(**{**cls.ENGINE, **serving}))

    @pytest.fixture(scope="class")
    def params(self):
        # drawn and moved in one program: drawn leaf by leaf it takes twice
        # as long
        return jax.jit(lambda key: moved(self.INIT(self.CFG, key)))(
            jax.random.PRNGKey(0))

    @pytest.fixture(scope="class")
    def engines(self, params):
        """``engines(**overrides)``: the class's one engine of that shape."""
        built = {}

        def engine(**serving):
            key = tuple(sorted(serving.items()))
            if key not in built:
                built[key] = self.new_engine(params, **serving)
            return built[key]
        return engine

    # ----------------------------------------------- what a family may add
    def the_tree(self, params):
        """The family's own stacks, leaves and shapes."""

    def the_sizes(self):
        """The family's own published sizes."""

    def check_engine(self, engine):
        """What an engine holds, before a path and after every step of the
        mixed run (a ring's shape, whatever its requests' lengths)."""

    def check_counts(self, assigned, held):
        """Of a step's ``assigned`` (token, expert) pairs, the ``held`` ones:
        those whose expert this chip holds."""
        if self.CFG.held_experts[1] == self.CFG.moe_experts:
            assert held == assigned
        else:                           # a share of the experts is held
            assert 0 < held <= assigned

    def check_state(self, params, ids, slot, own, left):
        """What the step after ``ids`` left beside its pages in ``slot`` of
        the cache ``left``, against what the reference names each layer at
        that position (``own`` [n_layer, ...]: a routed layer's experts, a
        mixer's readings; None where ``REF`` has no ``forward`` to say it).
        Here: a layer that does neither names nothing."""
        for r in G.layer_runs(self.CFG):
            if own is not None and not r.routes and not r.mixes:
                assert (own[r.first:r.first + r.count] == -1).all(), (
                    slot, r.first)

    def experts_of(self, own):
        """The experts in the rows ``own`` [n_layer, k] the reference names
        at a position (a family whose rows carry more unpacks them)."""
        return own

    def forward_case(self, forward, params):
        """(config, the reference's model, parameters) of a ``FORWARDS``
        case."""
        return self.CFG, self.MODEL, params

    # ---------------------------------------------------------- the tests
    def test_the_parameter_tree_is_the_references(self, params):
        """A stack a kind of layer under the names the reference places its
        layers by, the specs' tree the parameters', then the family's own."""
        runs = G.layer_runs(self.CFG)
        assert set(dict(G.stack_names(self.CFG))) <= set(params)
        if hasattr(self.REF, "place"):
            assert [self.REF.place(self.MODEL, l)
                    for l in range(self.CFG.n_layer)] == [
                (r.name, r.offset + i) for r in runs for i in range(r.count)]
        specs = G.partition_specs(self.CFG, None)
        assert jax.tree_util.tree_structure(
            jax.tree_util.tree_map(lambda _: 0, params)) == \
            jax.tree_util.tree_structure(jax.tree_util.tree_map(
                lambda _: 0, specs, is_leaf=lambda s: not isinstance(s, dict)))
        self.the_tree(params)

    def test_forward_logits_equal_the_references(self, forward, params):
        cfg, model, p = self.forward_case(forward, params)
        ids = self.ids(2, *self.FORWARDS[forward])
        got = np.asarray(G.forward(cfg, p, jnp.asarray(ids), train=False))
        want = np.stack([self.REF.logits(model, p, row) for row in ids])
        assert np.abs(got - want).max() < self.TOL

    def serve(self, engine, prompts, slots, steps):
        """Prefill ``prompts`` into ``slots`` in one admission cycle, then
        ``steps`` decode steps; returns the sequences so far, the logits, the
        experts and the counts of one more step, and the cache as that step
        left it (the engine's keeps what was there before it)."""
        tables = tables_of(engine, slots)
        first = engine.prefill_many([(slot, p, tables[slot])
                                     for slot, p in zip(slots, prompts)])
        n = engine.num_slots
        lengths, nxt = np.zeros(n, np.int32), np.zeros(n, np.int32)
        active = np.zeros(n, bool)
        seqs = {}
        for slot, p in zip(slots, prompts):
            lengths[slot], nxt[slot], active[slot] = len(p), first[slot], True
            seqs[slot] = list(p) + [int(first[slot])]
        for _ in range(steps):
            out = engine.decode(nxt.copy(), tables.copy(), lengths.copy(),
                                active, steps=1)
            lengths[active] += 1
            for slot in slots:
                nxt[slot] = out[0, slot]
                seqs[slot].append(int(out[0, slot]))
        logits, left, routing = decode_step(
            self.CFG, engine.params, jnp.asarray(nxt),
            dict(engine.paged_cache), jnp.asarray(tables),
            jnp.asarray(lengths), impl="kernel", return_routing=True)
        chosen, counts = routing if routing is not None else (None, None)
        return seqs, np.asarray(logits), chosen, counts, left

    def test_the_engines_prefill_then_decode_equal_the_full_forward(
            self, path, params, engines):
        """Logits, not tokens: each prefill path (a prompt of one chunk
        straight to pages, to its slot's ring and state; several that share
        the admission batch with padded rows; serial chunks through the dense
        cache and the scatter), then ``STEPS`` decode steps through the pages
        with the kernel (interpret mode here). The engine is the class's:
        every case after the first finds its slots used."""
        case = self.PATHS[path]
        lens, shape = case if isinstance(case, tuple) else (case, {})
        engine = engines(**shape)
        self.check_engine(engine)
        prompts = [row[:n] for row, n in zip(self.ids(len(lens), 80, seed=5),
                                             lens)]
        # the requests in the last slots: a ring and a state are their
        # slot's, whatever the row of the prefill dispatch
        slots = [engine.num_slots - 1 - j for j in range(len(lens))]
        seqs, logits, chosen, counts, left = self.serve(
            engine, prompts, slots, self.STEPS)
        routed = [l for r in G.layer_runs(self.CFG) if r.routes
                  for l in range(r.first, r.first + r.count)]
        if routed:      # a fifth count where the router scores zero experts
            assert engine.decode_routing.shape == (
                1, 4 + bool(self.CFG.moe_zero_experts))
        for slot, p in zip(slots, prompts):
            ids = np.asarray(seqs[slot], np.int32)
            want = np.asarray(self.REF.logits(self.MODEL, params, ids))
            # the greedy tokens along the way, where the reference has no tie
            for t in range(len(p) - 1, len(ids) - 1):
                top = np.sort(want[t])[-2:]
                if top[1] - top[0] > 1e-4:
                    assert ids[t + 1] == int(np.argmax(want[t])), (
                        path, slot, t)
            assert np.abs(logits[slot] - want[-1]).max() < self.TOL
            own = (np.asarray(self.REF.forward(self.MODEL, params, ids)[1])[-1]
                   if hasattr(self.REF, "forward") else None)
            self.check_state(params, ids, slot, own, left)
            if not routed:
                continue
            # the step's experts are the reference's own at that position, in
            # the routed layers; the others name nothing
            got, named = np.asarray(chosen[slot]), self.experts_of(own)
            for l in range(self.CFG.n_layer):
                if l in routed:
                    assert sorted(got[l]) == sorted(named[l]), (path, slot, l)
                else:
                    assert (got[l] == -1).all()
        if routed:
            assert int(counts[0]) == len(lens) * len(routed) * self.MODEL["k"]
            self.check_counts(int(counts[0]), int(counts[1]))

    def test_a_mixed_run_with_a_preemption_leaves_a_clean_audit(
            self, params, engines):
        """Requests of mixed lengths through the scheduler with a pool too
        small for all: one is preempted and prefilled again, from its prompt
        and what it generated, into whatever slot comes free (its ring and
        its state start over there), every request's tokens are those of a
        run with room and the reference's where it has no tie, and the page
        audit is clean. Both runs on the class's engine of ``MIXED``: the
        tight one's scheduler hands out 120 tokens' pages of its pool and no
        more. What ``check_engine`` says of the engine (a slot's ring stays
        its own size while its request grows) holds after every step."""
        prompts = [row[:n] for row, n in zip(self.ids(5, 64, seed=9),
                                             (5, 20, 40, 12, 33))]
        engine = engines(**self.MIXED)

        def run(pages=None):
            return run_to_idle(engine, prompts, 14, pages,
                               lambda: self.check_engine(engine))

        roomy, audit = run()
        assert audit["ok"] and not sum(r.preemptions for r in roomy)
        tight, audit = run(-(-120 // engine.serving.page_size))
        assert audit["ok"], audit
        assert sum(r.preemptions for r in tight) >= 1
        for a, b, p in zip(roomy, tight, prompts):
            assert len(a.tokens) == 14 and a.tokens == b.tokens
            if not self.PREEMPTED_AGAINST_REF:
                continue
            want = np.asarray(self.REF.logits(
                self.MODEL, params, np.asarray(list(p) + a.tokens, np.int32)))
            for t in range(len(p) - 1, len(p) + 13):
                top = np.sort(want[t])[-2:]
                if top[1] - top[0] > 1e-4:
                    assert a.tokens[t - len(p) + 1] == int(np.argmax(want[t]))

    def test_one_function_sizes_every_cache(self, engines):
        """``cache_row`` and the counts of :func:`gpt.layer_runs` are the
        cache's kind at every sizing site: the byte formulas are held to the
        caches ``init_cache`` and ``init_paged_cache`` really build, here and
        in the engine; then the family's published sizes."""
        page = self.ENGINE["page_size"]
        for cfg in (self.CFG, G.PRESETS["tiny"]):
            pool = G.init_paged_cache(cfg, 9, page, jnp.bfloat16,
                                      ring_slots=2)
            assert sum(pool[k].nbytes for k in PAGES if k in pool) == \
                G.paged_kv_bytes_per_token(cfg, page_size=page) * 9 * page
            dense = G.init_cache(cfg, 2, 32, jnp.bfloat16)
            assert sum(a.nbytes for a in G.dense_caches(dense)) == \
                G.dense_kv_bytes(cfg, 2, 32)
        engine = engines()
        assert sum(engine.paged_cache[k].nbytes for k in PAGES
                   if k in engine.paged_cache) == \
            engine.kv_bytes_per_token() * engine.num_pages * page
        assert engine.model.facts.cache_layers == G.cache_layers(self.CFG)
        self.the_sizes()

    def test_one_position_a_row_is_that_row_of_every_positions_logits(
            self, params):
        """``forward_with_cache(last=)`` over a chunk of 24 whose rows hold
        24 and 10 real tokens: the head on ``last[b]`` of row ``b`` alone
        gives that row of the logits of every position (another tiling of
        the same product: within ``TOL``, the same greedy token), zeros
        where no row's prompt ends, and the cache and the states of the
        program that ran the head everywhere."""
        ids = jnp.asarray(self.ids(2, 24, seed=3))
        step = jax.jit(functools.partial(
            G.forward_with_cache, self.CFG, return_states=True,
            real=jnp.asarray([24, 10])))
        cache = G.init_cache(self.CFG, 2, 32, jnp.float32)
        want, *rest = step(params, ids, cache)
        assert want.shape == (2, 24, self.CFG.vocab_size)
        want = np.asarray(want)
        for last in ([23, 9], [-1, 9], [-1, -1]):
            got, *left = step(params, ids, cache, last=jnp.asarray(last))
            got = np.asarray(got)
            assert got.shape == (2, self.CFG.vocab_size)
            if max(last) < 0:
                assert not got.any()
            for b, t in enumerate(last):
                if t >= 0:
                    assert np.abs(got[b] - want[b, t]).max() < self.TOL
                    assert got[b].argmax() == want[b, t].argmax()
            for a, b in zip(jax.tree_util.tree_leaves(left),
                            jax.tree_util.tree_leaves(rest), strict=True):
                assert np.array_equal(np.asarray(a), np.asarray(b))

    def prefilled(self, cfg, params, ids, pool_dtype,
                  step=G.paged_prefill_step):
        """A prompt of 40 of ``ids`` straight to pages (rings, a state) of a
        pool that holds all of ``ids``, by ``step`` (here the model's own,
        op by op: under whatever a fault patched): (the prefill's logits, the
        pool, the one row's block table)."""
        page = self.ENGINE["page_size"]
        pages = -(-len(ids) // page)
        tables = jnp.arange(1, 1 + pages, dtype=jnp.int32)[None]
        pool = G.init_paged_cache(cfg, pages + 2, page, pool_dtype,
                                  ring_slots=1)
        first, pool, _ = step(
            cfg, params, jnp.asarray(ids[None, :40]), pool, tables,
            jnp.asarray([40]), jnp.asarray([0]), jnp.asarray([0]))
        return first, pool, tables

    def test_a_float32_stream_over_bf16_weights_and_pages(self, params):
        """The served arrangement: bf16 weights, pages and rings, the stream
        of the prompts' and the decode token's forwards in float32
        (``stream_float32``): the kernel takes the float32 query in two
        passes, the cache stays bf16, the decode logits come back in float32,
        and the result stays the bf16 stream's and the reference's to bf16's
        own accuracy (which of the two lies nearer the reference is a chip
        measurement: the experts that flip between them decide it)."""
        served = jax.tree_util.tree_map(lambda a: a.astype(jnp.bfloat16),
                                        params)
        ids = self.ids(1, 41, seed=13)[0]
        got = {}
        for name, over in (("float32", self.WIDE), ("bf16", self.NARROW)):
            cfg = dataclasses.replace(self.CFG, **over)
            first, pool, tables = self.prefilled(cfg, served, ids,
                                                 jnp.bfloat16, prefill_step)
            logits, pool = decode_step(
                cfg, served, jnp.asarray(ids[40:]), pool, tables,
                jnp.asarray([40]), impl="kernel")
            # what is cached; a step's selection beside it is positions
            assert {a.dtype for a in pool.values()
                    if jnp.issubdtype(a.dtype, jnp.floating)} == {
                jnp.dtype(jnp.bfloat16)}
            assert first.dtype == jnp.bfloat16
            assert logits.dtype == (jnp.float32 if cfg.stream_float32
                                    else jnp.bfloat16)
            got[name] = np.asarray(logits[0], np.float32)
        want = np.asarray(self.REF.logits(self.MODEL, served, ids))[-1]
        assert np.isfinite(got["float32"]).all()
        assert 0 < np.abs(got["float32"] - got["bf16"]).max() < 0.2
        assert np.abs(got["float32"] - want).max() < 0.2

    def served_fault(self, cfg, params, pool_dtype=jnp.float32):
        """How far the logits of ``cfg`` lie from the reference's after a
        prompt of 40 straight to pages and ``FAULT_STEPS`` decode steps, the
        reference under the last step's own experts; and the slack it read
        in them."""
        last = 39 + self.FAULT_STEPS
        ids = self.ids(1, last + 1, seed=11)[0]
        _, pool, tables = self.prefilled(cfg, params, ids, pool_dtype)
        # traced here, under whatever the fault patched
        step = jax.jit(lambda token, pool, t: G.paged_decode_step(
            cfg, params, token, pool, tables, t, impl="gather",
            return_routing=True))
        for t in range(40, last + 1):
            logits, pool, (chosen, _) = step(
                jnp.asarray(ids[t:t + 1]), pool, jnp.asarray([t]))
        want, slack = self.REF.logits(self.MODEL, params, ids,
                                      positions=[last],
                                      choices={last: np.asarray(chosen[0])})
        return (float(np.abs(np.asarray(logits[0]) - np.asarray(want[0])
                             ).max()), float(np.max(slack[last])))

    @pytest.fixture(scope="class")
    def honest(self, params):
        """The unfaulted path's distance, read once a class (before any
        fault is planted: a class's fixture is set up before a test's)."""
        read, slack = self.served_fault(self.CFG, params)
        assert read < self.TOL and slack == 0.0
        return read

    def test_a_planted_fault_fails_the_comparison(self, fault, params,
                                                  honest, monkeypatch):
        """Each fault once, through prefill into pages (and rings) and the
        decode steps, under the step's own experts: the honest path passes
        ``TOL``, the fault does not."""
        faulty = self.FAULTS[fault](monkeypatch)
        cfg, *pool = faulty if isinstance(faulty, tuple) else (faulty,)
        read = self.served_fault(cfg, params, *pool)[0]
        print(f"{fault}: {read:.3g} for the honest {honest:.3g}")
        assert read > 5 * self.TOL

    def test_each_new_field_alone_is_named(self, field):
        """A config object that says one new field and nothing else (built
        past ``__post_init__``, which ties the fields to one another) is
        refused by that field's name on a path that carries neither kinds nor
        other blocks."""
        tiny = G.PRESETS["tiny"]
        cfg = dataclasses.replace(tiny)
        object.__setattr__(cfg, field, self.NEW_FIELDS[field])
        for fields in (G.KIND_FIELDS, G.BLOCK_FIELDS):
            if field in fields:
                with pytest.raises(ValueError, match=f"{field}="):
                    G.require_default_block(cfg, "here", fields)
            G.require_default_block(tiny, "here", fields)

    def test_a_path_that_does_not_carry_the_family_refuses_by_a_fields_name(
            self, refusal):
        with pytest.raises(ValueError, match=self.REFUSES[refusal]):
            self.REFUSALS[refusal](type(self))
