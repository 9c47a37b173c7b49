"""Paged KV cache + chunked prefill oracles.

Oracle pattern (SURVEY.md §4): paged == contiguous BIT-parity — the
page pool plus block tables must be invisible to everything but the
byte counts. Model-level logits parity (XLA fallback: gathered bytes +
the contiguous score expressions verbatim) across plain/int8/fp8 and
single-/multi-column writes; engine-level stream parity (greedy AND
sampled) across plain, quantized, tp2-vs-tp1, speculative, and
fault-replay paths; copy-on-write prefix hits bit-identical to the
PR-7 pooled-slot hits; chunked-prefill admission bit-identical to
monolithic; allocator backpressure completing everything; and
recompile-guard flatness over a mixed paged workload.

Engines are built once per shape through the shared helper and their
streams cached in ``_STREAMS`` so parity tests never re-run a side.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from apex_tpu import mesh as mx
from apex_tpu.models import gpt
from apex_tpu.serving import Request, SamplingParams
from apex_tpu.serving.engine import Engine, EngineConfig
from apex_tpu.serving.pages import SINK, PageAllocator, PagesExhausted
from apex_tpu.serving.scheduler import Scheduler
from apex_tpu.telemetry.spans import SpanRecorder
from apex_tpu.transformer.testing import standalone_gpt_config

VOCAB = 96


def _cfg(**overrides):
    base = dict(vocab_size=VOCAB, seq_len=64)
    base.update(overrides)
    return standalone_gpt_config(**base)


# -- the page allocator (pure host) -----------------------------------------


def test_page_allocator_semantics():
    a = PageAllocator(num_pages=9, page_size=8)
    assert a.capacity == 8 and a.free_pages == 8
    p1 = a.alloc(3)
    assert len(p1) == 3 and SINK not in p1
    assert a.pages_in_use == 3
    # copy-on-write pin: one more holder on an allocated page
    a.share(p1[:1])
    assert a.shared_pages == 1
    # all-or-nothing: a too-large request leaves state untouched
    with pytest.raises(PagesExhausted) as ei:
        a.alloc(6)
    assert ei.value.requested == 6 and ei.value.free == 5
    assert a.free_pages == 5
    # free drops one pin; the shared page survives its first free
    a.free(p1)
    assert a.free_pages == 7 and a.pages_in_use == 1
    a.free(p1[:1])
    assert a.free_pages == 8 and a.shared_pages == 0
    with pytest.raises(ValueError):
        a.free(p1[:1])  # double free
    with pytest.raises(ValueError):
        a.share([SINK])  # the sink is never a holder
    # fragmentation: 2 pages hold 10 of 16 possible tokens
    p2 = a.alloc(2)
    a.used_tokens += 10
    assert a.fragmentation() == pytest.approx(1.0 - 10 / 16)
    a.free(p2)
    a.reset()
    assert a.free_pages == 8 and a.used_tokens == 0
    # determinism: same call sequence, same page ids (fault replay)
    b = PageAllocator(num_pages=9, page_size=8)
    assert b.alloc(3) == PageAllocator(num_pages=9, page_size=8).alloc(3)


# -- model-level logits parity (the XLA-fallback bit-exact oracle) ----------


@pytest.mark.parametrize("kind", [
    "auto", "int8",
    # fp8 rides the identical quantized read/write paths as int8 with
    # only the storage dtype swapped — the costliest variant (~18 s)
    # runs in the slow tier; int8 keeps the quantized arm in tier-1
    # (tier-1 budget offset for the fleet-router suite)
    pytest.param("fp8", marks=pytest.mark.slow)])
def test_paged_decode_logits_oracle(devices8, kind):
    """Paged ``decode_step``/``decode_verify`` (block table through a
    scrambled page pool) emit BIT-identical logits to the contiguous
    cache under the XLA path — the gathered bytes + verbatim score
    expressions contract — for every cache storage kind, across
    chained single-column decode and a multi-column verify write."""
    if kind == "fp8" and not hasattr(jnp, "float8_e4m3fn"):
        pytest.skip("jax build without float8_e4m3fn")
    cfg = dataclasses.replace(_cfg(seq_len=64), kv_cache_dtype=kind)
    params = gpt.init(cfg, jax.random.PRNGKey(0))
    mesh = mx.build_mesh(tp=1, devices=devices8[:1])
    pspecs = gpt.param_specs(cfg)
    b, p_sz, mp, n_pages = 2, 8, 6, 16
    s = mp * p_sz
    rng = np.random.RandomState(1)
    table = jnp.asarray(rng.permutation(np.arange(1, n_pages))[
        np.arange(b * mp).reshape(b, mp)].astype(np.int32))
    tok = jnp.array([5, 9], jnp.int32)

    def run(p, tk, tbl):
        cc = gpt.init_cache(cfg, p, b, s)
        pc = gpt.init_cache(cfg, p, n_pages, p_sz)
        pos = jnp.zeros((b,), jnp.int32)
        t_c = t_p = tk
        outs_c, outs_p = [], []
        for _ in range(4):
            lg_c, cc = gpt.decode_step(cfg, p, cc, t_c, pos)
            lg_p, pc = gpt.decode_step(cfg, p, pc, t_p, pos, tbl)
            outs_c.append(lg_c)
            outs_p.append(lg_p)
            t_c = jnp.argmax(lg_c, -1).astype(jnp.int32)
            t_p = jnp.argmax(lg_p, -1).astype(jnp.int32)
            pos = pos + 1
        # the speculative verify's multi-column write + follow-on read
        toks = jnp.stack([t_c, (t_c + 1) % VOCAB, (t_c + 2) % VOCAB],
                         axis=1)
        la_c, cc = gpt.decode_verify(cfg, p, cc, toks, pos)
        la_p, pc = gpt.decode_verify(cfg, p, pc, toks, pos, tbl)
        lf_c, _ = gpt.decode_step(cfg, p, cc, t_c, pos + 3)
        lf_p, _ = gpt.decode_step(cfg, p, pc, t_p, pos + 3, tbl)
        return jnp.stack(outs_c), jnp.stack(outs_p), la_c, la_p, lf_c, lf_p

    oc, op, la_c, la_p, lf_c, lf_p = jax.jit(jax.shard_map(
        run, mesh=mesh, in_specs=(pspecs, P(None), P(None, None)),
        out_specs=P(), check_vma=False))(params, tok, table)
    np.testing.assert_array_equal(np.asarray(oc), np.asarray(op))
    np.testing.assert_array_equal(np.asarray(la_c), np.asarray(la_p))
    np.testing.assert_array_equal(np.asarray(lf_c), np.asarray(lf_p))


def test_paged_kernel_vs_xla_oracle(devices8):
    """The Pallas paged kernels (interpreted off-TPU) agree with the
    XLA paged fallback within kernel-oracle tolerance, and greedily
    emit the same tokens — the on-chip read/write path's CPU oracle."""
    cfgs = {impl: dataclasses.replace(_cfg(seq_len=64),
                                      decode_attn_impl=impl)
            for impl in ("kernel", "xla")}
    params = gpt.init(cfgs["xla"], jax.random.PRNGKey(0))
    mesh = mx.build_mesh(tp=1, devices=devices8[:1])
    pspecs = gpt.param_specs(cfgs["xla"])
    b, p_sz, mp, n_pages = 2, 8, 6, 14
    table = jnp.asarray(np.arange(1, 1 + b * mp, dtype=np.int32)
                        .reshape(b, mp))
    tok = jnp.array([5, 9], jnp.int32)

    def mk(c):
        def run(p, tk, tbl):
            pc = gpt.init_cache(c, p, n_pages, p_sz)
            pos = jnp.zeros((b,), jnp.int32)
            t = tk
            outs = []
            for _ in range(4):
                lg, pc = gpt.decode_step(c, p, pc, t, pos, tbl)
                outs.append(lg)
                t = jnp.argmax(lg, -1).astype(jnp.int32)
                pos = pos + 1
            return jnp.stack(outs)
        return jax.jit(jax.shard_map(
            run, mesh=mesh, in_specs=(pspecs, P(None), P(None, None)),
            out_specs=P(None, None, None), check_vma=False))

    ok = np.asarray(mk(cfgs["kernel"])(params, tok, table))
    ox = np.asarray(mk(cfgs["xla"])(params, tok, table))
    np.testing.assert_allclose(ok, ox, rtol=2e-4, atol=2e-4)
    np.testing.assert_array_equal(ok.argmax(-1), ox.argmax(-1))


# -- engine-level stream parity ---------------------------------------------

#: streams keyed by (shape, side) — parity tests read a side another
#: test already produced instead of re-running it
_STREAMS = {}


def _mk_engine(cfg, ecfg, mesh, fault_plan=None):  # apex: noqa[TIER1-COST]: shared tiny-engine builder — one warm-cache warmup per paged-parity variant serves every test below
    return Engine(cfg, params_of(cfg), mesh, ecfg,
                  fault_plan=fault_plan).warmup()


_PARAMS = {}


def params_of(cfg):
    # one shared init — parameters are storage-kind independent
    if "p" not in _PARAMS:
        base = dataclasses.replace(cfg, kv_cache_dtype="auto")
        _PARAMS["p"] = gpt.init(base, jax.random.PRNGKey(0))
    return _PARAMS["p"]


def _trace(n=6, mt=6, mpl=14, long_every=0, long_len=0, prefix=None):
    reqs = []
    for i in range(n):
        if long_every and i % long_every == 1:
            p_len = long_len
        else:
            p_len = 1 + (7 * i + 3) % mpl
        body = [int(t) for t in jax.random.randint(
            jax.random.PRNGKey(50 + i), (p_len,), 0, VOCAB)]
        prompt = (list(prefix) + body[:3]) if prefix and i % 2 == 0 \
            else body
        sp = (SamplingParams(temperature=0.9, top_k=20, seed=i)
              if i % 2 else SamplingParams())
        reqs.append(Request(f"r{i}", prompt, max_tokens=mt, sampling=sp))
    return reqs


def _run(engine, reqs, **kw):
    sched = Scheduler(engine, **kw)
    for r in reqs:
        sched.submit(r)
    sched.run_until_idle()
    return ({rid: c.tokens for rid, c in sched.completions.items()},
            sched.summary())


_ECFG = EngineConfig(slots=3, max_prompt_len=16, max_seq_len=32,
                     decode_chunk=2, prompt_buckets=(8, 16),
                     admit_batch_sizes=(1, 2))


def _baseline(devices8, kind="auto"):
    key = ("base", kind)
    if key not in _STREAMS:
        cfg = dataclasses.replace(_cfg(), kv_cache_dtype=kind)
        eng = _mk_engine(cfg, _ECFG,
                         mx.build_mesh(tp=1, devices=devices8[:1]))
        _STREAMS[key] = _run(eng, _trace())[0]
        eng.close()
    return _STREAMS[key]


@pytest.mark.parametrize("kind", [
    "auto",
    # the int8 engine-level stream parity is the logits oracle's int8
    # arm composed with the (auto-covered) engine plumbing — slow tier
    # (tier-1 budget offset for the fleet-router suite)
    pytest.param("int8", marks=pytest.mark.slow)])
def test_paged_engine_stream_parity(devices8, kind):
    """A paged engine emits BIT-identical token streams (greedy and
    sampled rows alike) to the contiguous engine — plain and
    quantized-KV storage; pages all return to the pool at drain."""
    base = _baseline(devices8, kind)
    cfg = dataclasses.replace(_cfg(), kv_cache_dtype=kind)
    eng = _mk_engine(cfg, dataclasses.replace(_ECFG, page_size=8),
                     mx.build_mesh(tp=1, devices=devices8[:1]))
    toks, s = _run(eng, _trace())
    _STREAMS[("paged", kind)] = toks
    eng.close()
    assert toks == base
    assert s["pages_in_use"] == 0.0  # every release freed its pages


@pytest.mark.slow  # plain tp2 parity (test_serving) stays tier-1; this paged-only composition is subsumed by the composed-path oracle below — both long-suite (self-tuning-runtime tier-1 offset)
def test_paged_tp2_vs_tp1_parity(devices8):
    """Paged decode under tp=2 (heads sharded; pool + tables
    replicated geometry) emits the tp=1 paged streams bit-for-bit."""
    base = _baseline(devices8, "auto")
    eng = _mk_engine(_cfg(), dataclasses.replace(_ECFG, page_size=8),
                     mx.build_mesh(tp=2, devices=devices8[:2]))
    toks, _ = _run(eng, _trace())
    eng.close()
    assert toks == base


@pytest.mark.slow
def test_composed_tp2_vs_tp1_full_path_parity(devices8):
    """THE full composed serving path the ROADMAP flagged as
    uncovered, tp2 vs tp1 in ONE run: pipelined decode (depth 2) +
    batched bucketed admission + prefix-pool hits mapped
    copy-on-write + the paged cache. Every per-feature tp oracle
    (plain, quantized, spec, paged) passes individually; this pins
    the COMPOSITION — sharded gathers over shared pages while chunks
    are in flight behind batched bucketed admissions — bit-identical
    across shardings."""
    cfg = _cfg()
    ecfg = dataclasses.replace(_POOL_ECFG, page_size=8)
    toks = {}
    for tp in (1, 2):
        eng = _mk_engine(cfg, ecfg,
                         mx.build_mesh(tp=tp, devices=devices8[:tp]))
        eng.register_prefix(_template())
        toks[tp], s = _run(eng, _prefix_trace(), pipeline_depth=2)
        eng.close()
        # the run must actually exercise every composed feature
        assert s["prefix_hits"] > 0 and s["page_share_hits"] > 0
        assert s["pipeline_depth"] == 2.0
        assert s["admitted_requests"] == 6.0
        assert s["pages_in_use"] == 16 / 8  # only registration pins
    assert toks[2] == toks[1]


def test_paged_spec_stream_parity(devices8):
    """Speculative decoding over the paged cache (draft-verify's
    multi-column paged writes included) stays bit-identical to the
    plain contiguous path, and the guard stays flat across the gate's
    spec/plain switching on paged tables (probe cadence forced to
    alternate — every program, table re-upload included, must hold
    cache size 1)."""
    from apex_tpu.serving.scheduler import SpecGateConfig

    base = _baseline(devices8, "auto")
    eng = _mk_engine(_cfg(), dataclasses.replace(
        _ECFG, page_size=8, spec_k=2),
        mx.build_mesh(tp=1, devices=devices8[:1]))
    with eng.recompile_guard():
        toks, s = _run(eng, _trace(), spec_gate=SpecGateConfig(
            probe_every=1, min_probe_chunks=1))
    sizes = {k: v for k, v in eng.compiled_cache_sizes().items()
             if v is not None}
    eng.close()
    assert toks == base
    assert all(v == 1 for v in sizes.values()), sizes


def test_paged_fault_replay_parity(devices8):
    """A mid-serve fault on the paged engine (donated buffers +
    tables + allocator rebuilt, prefix-free) replays interrupted
    requests to bit-identical completions — the paged layout is
    invisible to deterministic replay."""
    from apex_tpu.serving.resilience import FaultPlan, FaultSpec

    base = _baseline(devices8, "auto")
    plan = FaultPlan([FaultSpec(point="fetch", index=2, kind="error")])
    eng = _mk_engine(_cfg(), dataclasses.replace(_ECFG, page_size=8),
                     mx.build_mesh(tp=1, devices=devices8[:1]),
                     fault_plan=plan)
    toks, s = _run(eng, _trace())
    eng.close()
    assert s["rebuilds"] >= 1.0
    assert toks == base
    assert len(plan.injected) == 1


# -- copy-on-write prefix sharing + chunked prefill -------------------------

_POOL_ECFG = EngineConfig(slots=3, max_prompt_len=32, max_seq_len=48,
                          decode_chunk=2, prompt_buckets=(8, 16, 32),
                          admit_batch_sizes=(1, 2),
                          prefix_pool_slots=1)


def _template():
    return [int(t) for t in jax.random.randint(
        jax.random.PRNGKey(900), (16,), 0, VOCAB)]


def _prefix_trace():
    return _trace(n=6, mt=6, mpl=5, prefix=_template())


@pytest.mark.parametrize("kind", [
    "auto",
    # the quantized CoW pair rides the identical pagein/insert code
    # path (same quantizer, same inputs) — long-suite confirmation,
    # not tier-1 budget
    pytest.param("int8", marks=pytest.mark.slow),
])
def test_cow_prefix_hits_bit_identical(devices8, kind):
    """Paged prefix hits map the registered prefix's pages
    copy-on-write (zero prefix bytes moved at admission) and emit
    BIT-identical streams to the PR-7 pooled-slot hits; the shared
    pages survive every hit's release (refcount pin) so a second
    admission wave still shares them."""
    cfg = dataclasses.replace(_cfg(), kv_cache_dtype=kind)
    mesh = mx.build_mesh(tp=1, devices=devices8[:1])
    eng_pool = _mk_engine(cfg, _POOL_ECFG, mesh)
    eng_pool.register_prefix(_template())
    pooled, _ = _run(eng_pool, _prefix_trace())
    eng_pool.close()
    eng_cow = _mk_engine(cfg, dataclasses.replace(
        _POOL_ECFG, page_size=8), mesh)
    eng_cow.register_prefix(_template())
    cow, s1 = _run(eng_cow, _prefix_trace())
    assert cow == pooled
    assert s1["page_share_hits"] == s1["prefix_hits"] > 0
    # second wave: the prefix pages are still pinned and still shared
    cow2, s2 = _run(eng_cow, _prefix_trace())
    assert cow2 == pooled
    assert s2["page_share_hits"] > 0
    # only the registration pins remain mapped after drain
    stats = eng_cow.page_stats()
    eng_cow.close()
    assert stats["pages_in_use"] == 16 / 8  # the pinned prefix pages
    assert stats["pages_shared"] == 0.0


def test_chunked_prefill_stream_parity(devices8):
    """Chunked-prefill admission (chunk-0 cold prefill +
    ``prefill_extend`` chunks + finish, decode waves interleaved at
    chunk boundaries) emits BIT-identical streams to monolithic
    admission — on the paged cache, under a flat recompile guard,
    with every compiled program used exactly once."""
    mesh = mx.build_mesh(tp=1, devices=devices8[:1])
    trace_kw = dict(n=6, mt=6, mpl=14, long_every=3, long_len=30)
    eng_m = _mk_engine(_cfg(), dataclasses.replace(
        _POOL_ECFG, prefix_pool_slots=0), mesh)
    base, _ = _run(eng_m, _trace(**trace_kw))
    eng_m.close()
    eng_ch = _mk_engine(_cfg(), dataclasses.replace(
        _POOL_ECFG, prefix_pool_slots=0, page_size=8,
        prefill_chunk=16), mesh)
    spans = SpanRecorder()
    with eng_ch.recompile_guard():
        toks, s = _run(eng_ch, _trace(**trace_kw), spans=spans)
    sizes = {k: v for k, v in eng_ch.compiled_cache_sizes().items()
             if v is not None}
    eng_ch.close()
    assert toks == base
    assert s["chunked_admissions"] == 2.0  # the two 30-token prompts
    assert s["chunked_chunks"] == 4.0      # two chunks each
    assert all(v == 1 for v in sizes.values()), sizes
    # the recorder's admission counts hold the chunked rows too: each
    # 30-token prompt ran two forwards of a whole 16-token chunk and a
    # finish
    counts = {}
    for e in spans.events():
        if e[0] == 2:
            counts.setdefault(e[2], []).append(e[3])
    assert sum(counts["prefill.rows"]) == 6
    assert sum(counts["prefill.tokens_real"]) == sum(
        len(r.prompt) for r in _trace(**trace_kw))
    chunked = [i for i, n in enumerate(counts["prefill.tokens_real"])
               if n == 30]
    assert [(counts["prefill.tokens_padded"][i],
             counts["prefill.dispatches"][i]) for i in chunked] == [
        (32, 3), (32, 3)]
    assert sum(counts["prefill.dispatches"]) == s["admit_dispatches"] \
        + s["chunked_chunks"]


def test_paged_backpressure_completes_everything(devices8):
    """An oversubscribed pool (fewer pages than the burst needs at
    once) backpressures admissions instead of failing them: every
    request still completes with bit-identical streams, pages_exhausted
    waits are observed, and the pool drains back to empty."""
    base = _baseline(devices8, "auto")
    eng = _mk_engine(_cfg(), dataclasses.replace(
        _ECFG, page_size=8, num_pages=8),  # 7 allocatable ≈ 2 slots
        mx.build_mesh(tp=1, devices=devices8[:1]))
    toks, s = _run(eng, _trace())
    eng.close()
    assert toks == base
    assert s["pages_exhausted_waits"] > 0
    assert s["pages_in_use"] == 0.0
