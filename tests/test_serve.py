"""Serving stack tests: paged KV bit-parity, page-pool accounting,
scheduler determinism, engine behavior, and the paged flash-decode kernel.

The parity tests are the teeth of the PR 8 contract (also a HARD CI gate
via benchmarks/serve_throughput.py): the paged and contiguous backends
share one attention-math path, so their f32 logits must be IDENTICAL —
not allclose — across eviction / re-admission churn that lands slots on
LIFO-scrambled physical pages.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.models import transformer as tf
from repro.serve import (PagePool, PageSpec, Request, ServeEngine,
                         run_serve_loop, synthetic_workload)
from repro.serve import paged as pg


@pytest.fixture(scope="module")
def gemma():
    cfg = reduced(get_config("gemma3-4b"))
    params = tf.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _churn_reqs(cfg, seed=1, n=8):
    """Workload sized so every slot of a 2-slot spec is recycled."""
    return synthetic_workload(seed, n, vocab=cfg.vocab_size,
                              prompt_lens=(3, 20), gen_short=(3, 8),
                              gen_long=(12, 20), p_long=0.3)


# ------------------------- PageSpec ---------------------------------------
def test_page_spec_tiling_rules():
    assert PageSpec(page_len=16).n_pages == 4 * 8
    with pytest.raises(ValueError):
        PageSpec(page_len=12)                      # not an f32 sublane tile
    with pytest.raises(ValueError):
        PageSpec(page_len=8, store_dtype=jnp.bfloat16)   # bf16 tiles 16
    spec = PageSpec(page_len=16, pages_per_slot=4, n_slots=2)
    assert spec.slot_tokens == 64
    # budget covers the padded prefill extent plus decode tokens
    assert spec.pages_needed(17, 1, 16) == 3       # pad to 32, +1 new
    assert spec.pages_needed(16, 16, 16) == 2


def test_non_attention_arch_rejected(gemma):
    cfg = reduced(get_config("zamba2-2.7b"))
    with pytest.raises(ValueError, match="attention-only"):
        pg.attention_segments(cfg)
    with pytest.raises(ValueError, match="attention-only"):
        ServeEngine(cfg, {}, spec=PageSpec())


# ------------------------- PagePool accounting ----------------------------
def test_page_pool_basics():
    pool = PagePool(6)
    a = pool.alloc("a", 4)
    assert len(a) == 4 and pool.n_free == 2
    with pytest.raises(ValueError):
        pool.alloc("a", 1)                          # already holds
    with pytest.raises(ValueError):
        pool.alloc("b", 3)                          # capacity refusal
    assert not pool.can_alloc(3) and pool.can_alloc(2)
    pool.free("a")
    with pytest.raises(KeyError):
        pool.free("a")                              # double free
    assert pool.n_free == 6
    pool.audit()


def test_page_pool_property_random_interleavings():
    hypothesis = pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=40, deadline=None)
    @given(ops=st.lists(st.tuples(st.integers(0, 7), st.integers(1, 5)),
                        min_size=1, max_size=40),
           n_pages=st.integers(4, 12))
    def run(ops, n_pages):
        pool = PagePool(n_pages)
        held = {}
        for rid, n in ops:
            if rid in held:
                freed = pool.free(rid)
                assert sorted(freed) == sorted(held.pop(rid))
            elif n <= pool.n_free:
                held[rid] = pool.alloc(rid, n)
            else:
                with pytest.raises(ValueError):
                    pool.alloc(rid, n)              # refusal, not silence
            pool.audit()                            # no leaks, no dupes
        # every held page distinct across holders
        flat = [p for ps in held.values() for p in ps]
        assert len(flat) == len(set(flat))
        assert pool.n_free + len(flat) == n_pages

    run()


# ------------------------- scheduler ---------------------------------------
class _StubHooks:
    """Device-free hooks: the schedule must be fully determined without
    ever looking at model output."""

    def admit(self, slot, req, pages, **kw):
        pass

    def prefill(self, slot, req, chunk, pos, last):
        pass

    def decode(self, slots):
        pass

    def evict(self, slot, req):
        pass


def test_scheduler_deterministic_and_accounted():
    spec = PageSpec(page_len=16, pages_per_slot=6, n_slots=3)
    reqs = synthetic_workload(7, 12, vocab=64)
    logs = [run_serve_loop(reqs, spec, _StubHooks(), prefill_chunk=8)
            for _ in range(2)]
    assert logs[0] == logs[1]                       # bit-for-bit identical
    kinds = [e[0] for e in logs[0]]
    assert kinds.count("admit") == 12 == kinds.count("evict")
    # different seed -> different schedule (the test has teeth)
    other = run_serve_loop(synthetic_workload(8, 12, vocab=64), spec,
                           _StubHooks(), prefill_chunk=8)
    assert other != logs[0]


def test_scheduler_static_drains_before_admitting():
    spec = PageSpec(page_len=16, pages_per_slot=4, n_slots=2)
    reqs = [Request(rid=0, tokens=(1, 2, 3), max_new=3),
            Request(rid=1, tokens=(1, 2, 3), max_new=12),   # straggler
            Request(rid=2, tokens=(1, 2, 3), max_new=3),
            Request(rid=3, tokens=(1, 2, 3), max_new=3)]
    slog = run_serve_loop(reqs, spec, _StubHooks(), prefill_chunk=8,
                          policy="static")
    admit = {e[2]: e[1] for e in slog if e[0] == "admit"}
    evict = {e[2]: e[1] for e in slog if e[0] == "evict"}
    # static waits for the straggler: batch 2 admitted only after FULL drain
    assert admit[2] > evict[1] > evict[0]
    # continuous back-fills the freed slot while the straggler is in flight
    clog = run_serve_loop(reqs, spec, _StubHooks(), prefill_chunk=8)
    cadmit = {e[2]: e[1] for e in clog if e[0] == "admit"}
    cevict = {e[2]: e[1] for e in clog if e[0] == "evict"}
    assert cadmit[2] < cevict[1]


def test_scheduler_rejects_oversized_request():
    spec = PageSpec(page_len=16, pages_per_slot=2, n_slots=2)
    with pytest.raises(ValueError, match="pages_per_slot"):
        run_serve_loop([Request(rid=0, tokens=tuple(range(40)), max_new=8)],
                       spec, _StubHooks(), prefill_chunk=8)


# ------------------------- paged vs contiguous bit-parity ------------------
def test_paged_contig_bit_parity_under_churn(gemma):
    cfg, params = gemma
    spec = PageSpec(page_len=16, pages_per_slot=4, n_slots=2)
    reqs = _churn_reqs(cfg)
    pa = ServeEngine(cfg, params, spec=spec, backend="paged",
                     slot_buckets=False, record_logits=True, prefill_chunk=8)
    co = ServeEngine(cfg, params, spec=spec, backend="contig",
                     record_logits=True, prefill_chunk=8)
    ra, rc = pa.serve(reqs), co.serve(reqs)
    assert pa.log == co.log                         # same schedule
    # slots were genuinely recycled onto scrambled pages
    assert len([e for e in pa.log if e[0] == "admit"]) > spec.n_slots
    for a, b in zip(ra, rc):
        assert a.tokens == b.tokens
        assert len(a.logits) == len(b.logits) > 0
        for la, lb in zip(a.logits, b.logits):
            assert np.array_equal(la, lb)           # BITWISE, not allclose


def test_paged_bf16_pages_match_contig_bf16(gemma):
    cfg, params = gemma
    spec = PageSpec(page_len=32, pages_per_slot=2, n_slots=2,
                    store_dtype=jnp.bfloat16)
    reqs = _churn_reqs(cfg, seed=2, n=5)
    pa = ServeEngine(cfg, params, spec=spec, backend="paged",
                     slot_buckets=False, record_logits=True, prefill_chunk=8)
    co = ServeEngine(cfg, params, spec=spec, backend="contig",
                     record_logits=True, prefill_chunk=8)
    ra, rc = pa.serve(reqs), co.serve(reqs)
    for a, b in zip(ra, rc):
        assert a.tokens == b.tokens
        for la, lb in zip(a.logits, b.logits):
            assert np.array_equal(la, lb)   # parity holds per store dtype
    # and bf16 pages halve the pool bytes vs f32 at equal geometry
    f32 = PageSpec(page_len=32, pages_per_slot=2, n_slots=2)
    assert spec.pool_bytes(cfg) * 2 == f32.pool_bytes(cfg)


def test_serve_matches_reference_decode_loop(gemma):
    """Single request through the paged engine == the classic
    transformer.decode_step loop, token for token."""
    cfg, params = gemma
    spec = PageSpec(page_len=16, pages_per_slot=4, n_slots=2)
    prompt = [int(t) for t in
              np.random.default_rng(0).integers(0, cfg.vocab_size, 11)]
    gen = 6
    cache = tf.init_cache(cfg, 1, spec.slot_tokens)
    logits = None
    for t in range(len(prompt)):
        logits, cache = tf.decode_step(
            params, cfg, cache, jnp.asarray([[prompt[t]]], jnp.int32),
            jnp.int32(t))
    out = []
    for g in range(gen):
        nxt = int(jnp.argmax(logits[0, -1]))
        out.append(nxt)
        if g < gen - 1:
            logits, cache = tf.decode_step(
                params, cfg, cache, jnp.asarray([[nxt]], jnp.int32),
                jnp.int32(len(prompt) + g))
    rec = ServeEngine(cfg, params, spec=spec, prefill_chunk=8).serve(
        [Request(rid=0, tokens=prompt, max_new=gen)])[0]
    assert rec.tokens == out


def test_contig_step_writes_only_new_tokens(gemma):
    """One prefill chunk into one slot, one decode step, and a chunk that
    runs past the slot's end, each against a copy of the cache taken
    before: the step writes exactly positions ``[length, length +
    active)`` of its own slots, below ``slot_tokens``, and leaves every
    other slot and position bit-identical."""
    cfg, params = gemma
    spec = PageSpec(page_len=16, pages_per_slot=2, n_slots=3)
    s_tok = spec.slot_tokens
    rng = np.random.default_rng(0)
    caches = [{n: jnp.asarray(rng.standard_normal(a.shape), a.dtype)
               for n, a in c.items()} for c in pg.init_contig_cache(cfg, spec)]
    prefill = jax.jit(pg.make_serve_step(cfg, spec, "contig",
                                         gather_rows=True))
    decode = jax.jit(pg.make_serve_step(cfg, spec, "contig"))

    def run(fn, caches, rows, lengths, active, t):
        m = len(rows)
        toks = rng.integers(0, cfg.vocab_size, (m, t)).astype(np.int32)
        before = [{n: np.asarray(a) for n, a in c.items()} for c in caches]
        _, after = fn(params, caches, np.asarray(rows, np.int32),
                      np.asarray(lengths, np.int32),
                      np.asarray(active, np.int32), toks)
        written = np.zeros((spec.n_slots, s_tok), bool)
        for r, n0, a in zip(rows, lengths, active):
            written[r, n0:min(n0 + a, s_tok)] = True
        for b, c in zip(before, after):
            for n in ("k", "v"):
                a = np.asarray(c[n])
                assert a.shape == b[n].shape
                assert np.array_equal(a[:, ~written], b[n][:, ~written])
                assert np.all(np.any(a[:, written] != b[n][:, written],
                                     axis=-1))
        return after, toks

    caches, _ = run(prefill, caches, [1], [5], [6], 8)
    caches, _ = run(decode, caches, [0, 1, 2], [7, 11, 0], [1, 1, 0], 1)
    # past the slot's end: positions s_tok - 4 .. s_tok + 3, four written
    tail, toks = run(prefill, caches, [2], [s_tok - 4], [8], 8)
    # nothing shifted: the four positions hold what a chunk of exactly
    # those four tokens writes there
    _, short = prefill(params, caches, np.asarray([2], np.int32),
                       np.asarray([s_tok - 4], np.int32),
                       np.asarray([4], np.int32), toks)
    for c, d in zip(tail, short):
        for n in ("k", "v"):
            assert np.array_equal(np.asarray(c[n])[:, 2, -4:],
                                  np.asarray(d[n])[:, 2, -4:])


# ------------------------- engine behavior ---------------------------------
def test_continuous_equals_static_tokens_and_buckets(gemma):
    cfg, params = gemma
    spec = PageSpec(page_len=16, pages_per_slot=6, n_slots=3)
    reqs = _churn_reqs(cfg, seed=3, n=6)
    cont = ServeEngine(cfg, params, spec=spec, backend="paged",
                       prefill_chunk=8)
    stat = ServeEngine(cfg, params, spec=spec, backend="contig",
                       prefill_chunk=8)
    rc = cont.serve(reqs, policy="continuous")
    rs = stat.serve(reqs, policy="static")
    # scheduling never changes greedy tokens (causal slot independence)
    assert [r.tokens for r in rc] == [r.tokens for r in rs]
    assert all(len(r.tokens) == reqs[i].max_new for i, r in enumerate(rc))
    # bucketed decode compiled only pow2 row counts <= n_slots
    decode_keys = [k for k in cont.compile_log if k[2] == 1]
    assert all(m in (1, 2, 4) and m <= spec.n_slots or m == spec.n_slots
               for _, m, _ in decode_keys)


def test_compile_cache_stops_growing(gemma):
    cfg, params = gemma
    spec = PageSpec(page_len=16, pages_per_slot=6, n_slots=3)
    eng = ServeEngine(cfg, params, spec=spec, prefill_chunk=8)
    eng.serve(_churn_reqs(cfg, seed=4, n=5))
    n = len(eng.compile_log)
    eng.serve(_churn_reqs(cfg, seed=5, n=5))        # fresh workload
    assert len(eng.compile_log) == n                # no new step shapes


def test_latency_records(gemma):
    cfg, params = gemma
    spec = PageSpec(page_len=16, pages_per_slot=6, n_slots=2)
    eng = ServeEngine(cfg, params, spec=spec, prefill_chunk=8)
    recs = eng.serve([Request(rid=0, tokens=tuple(range(1, 10)), max_new=5),
                      Request(rid=1, tokens=(3, 4), max_new=4, arrival=2)])
    for r in recs:
        assert r.t_admit > 0 and r.t_first >= r.t_admit
        assert r.t_done >= r.token_times[-1]
        assert r.ttft_s >= 0 and len(r.token_times) == len(r.tokens)
        assert list(r.token_times) == sorted(r.token_times)
    assert recs[0].tpot_s > 0


def test_eos_early_stop(gemma):
    cfg, params = gemma
    spec = PageSpec(page_len=16, pages_per_slot=6, n_slots=2)
    req = Request(rid=0, tokens=tuple(range(1, 8)), max_new=10)
    base = ServeEngine(cfg, params, spec=spec, prefill_chunk=8).serve([req])
    toks = base[0].tokens
    # greedy output of a tiny random model repeats; stop on the first
    # token value that recurs mid-stream
    eos = next((t for i, t in enumerate(toks) if t in toks[:i]), None)
    if eos is None:
        pytest.skip("greedy stream produced no repeated token")
    eng = ServeEngine(cfg, params, spec=spec, prefill_chunk=8, eos_id=eos)
    rec = eng.serve([req])[0]
    assert len(rec.tokens) < 10
    assert rec.tokens[-1] == eos


# ------------------------- flash_decode fallback + paged kernel ------------
def test_resolve_impl_cpu_honest():
    from repro.kernels import flash_decode as fd
    expect = "pallas" if jax.default_backend() == "tpu" else "xla"
    assert fd.resolve_impl("auto") == expect
    assert fd.resolve_impl("xla") == "xla"
    assert fd.resolve_impl("pallas") == "pallas"


def test_flash_decode_auto_matches_interpreted_kernel():
    from repro.kernels import flash_decode as fd
    rng = np.random.default_rng(0)
    b, h, kv, hd, s = 2, 4, 2, 64, 256
    q = jnp.asarray(rng.standard_normal((b, h, 1, hd)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, kv, s, hd)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, kv, s, hd)), jnp.float32)
    for window in (0, 64):
        auto = fd.flash_decode(q, k, v, 170, window=window)
        kern = fd.flash_decode(q, k, v, 170, window=window, interpret=True)
        np.testing.assert_allclose(np.asarray(auto), np.asarray(kern),
                                   atol=2e-5, rtol=2e-5)


def test_paged_decode_ref_matches_contiguous():
    """Scatter a contiguous cache into scrambled pages; the paged reference
    must reproduce the contiguous decode attention bit-for-bit."""
    from repro.kernels import flash_decode as fd
    rng = np.random.default_rng(1)
    ns, h, kv, hd = 3, 4, 2, 64
    page_len, pp, n_pages = 16, 4, 16
    s = pp * page_len
    q = jnp.asarray(rng.standard_normal((ns, h, 1, hd)), jnp.float32)
    contig = rng.standard_normal((ns, kv, s, hd)).astype(np.float32)
    contig_v = rng.standard_normal((ns, kv, s, hd)).astype(np.float32)
    table = rng.permutation(n_pages)[:ns * pp].reshape(ns, pp)
    k_pages = np.zeros((n_pages, page_len, kv, hd), np.float32)
    v_pages = np.zeros((n_pages, page_len, kv, hd), np.float32)
    for si in range(ns):
        for pi in range(pp):
            sl = slice(pi * page_len, (pi + 1) * page_len)
            k_pages[table[si, pi]] = contig[si, :, sl].transpose(1, 0, 2)
            v_pages[table[si, pi]] = contig_v[si, :, sl].transpose(1, 0, 2)
    lengths = jnp.asarray([37, 5, 63], jnp.int32)
    paged = fd.paged_decode_ref(q, jnp.asarray(k_pages),
                                jnp.asarray(v_pages),
                                jnp.asarray(table, jnp.int32), lengths)
    for si in range(ns):
        ref = fd._xla_decode(q[si:si + 1], jnp.asarray(contig[si:si + 1]),
                             jnp.asarray(contig_v[si:si + 1]),
                             int(lengths[si]))
        np.testing.assert_allclose(np.asarray(paged[si:si + 1]),
                                   np.asarray(ref), atol=1e-6, rtol=1e-6)


def test_flash_decode_paged_kernel_interpret():
    from repro.kernels import flash_decode as fd
    rng = np.random.default_rng(2)
    ns, h, kv, hd = 2, 4, 2, 64
    page_len, pp, n_pages = 16, 2, 8
    q = jnp.asarray(rng.standard_normal((ns, h, 1, hd)), jnp.float32)
    k_pages = jnp.asarray(rng.standard_normal((n_pages, page_len, kv, hd)),
                          jnp.float32)
    v_pages = jnp.asarray(rng.standard_normal((n_pages, page_len, kv, hd)),
                          jnp.float32)
    table = jnp.asarray(rng.permutation(n_pages)[:ns * pp].reshape(ns, pp),
                        jnp.int32)
    lengths = jnp.asarray([19, 30], jnp.int32)
    for window in (0, 8):
        ref = fd.paged_decode_ref(q, k_pages, v_pages, table, lengths,
                                  window=window)
        kern = fd.flash_decode_paged(q, k_pages, v_pages, table, lengths,
                                     window=window, interpret=True)
        np.testing.assert_allclose(np.asarray(kern), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)


# ------------------------- launch.serve prefill ----------------------------
def test_chunked_prefill_matches_stepped(gemma):
    from repro.launch.serve import chunkable, generate
    cfg, params = gemma
    assert chunkable(cfg)
    prompts = jax.random.randint(jax.random.PRNGKey(1), (2, 9), 0,
                                 cfg.vocab_size)
    a = generate(cfg, params, prompts, gen=5, max_seq=32)
    b = generate(cfg, params, prompts, gen=5, max_seq=32,
                 stepped_prefill=True)
    assert jnp.array_equal(a, b)


def test_recurrent_arch_keeps_stepping_path():
    from repro.launch.serve import chunkable, generate
    cfg = reduced(get_config("rwkv6-7b"))
    assert not chunkable(cfg)
    params = tf.init_params(cfg, jax.random.PRNGKey(0))
    prompts = jax.random.randint(jax.random.PRNGKey(1), (2, 5), 0,
                                 cfg.vocab_size)
    out = generate(cfg, params, prompts, gen=3, max_seq=8)
    assert out.shape == (2, 8)


def test_chunked_decode_rejects_recurrent_chunks():
    cfg = reduced(get_config("rwkv6-7b"))
    params = tf.init_params(cfg, jax.random.PRNGKey(0))
    cache = tf.init_cache(cfg, 1, 8)
    with pytest.raises(ValueError, match="chunked decode"):
        tf.decode_step(params, cfg, cache,
                       jnp.zeros((1, 4), jnp.int32), jnp.int32(0))
