"""Quantized KV cache + shared-prefix reuse oracles.

Oracle pattern (SURVEY.md §4): the int8/fp8 cache vs the compute-dtype
cache with per-dtype tolerances (kernel AND XLA fallback), sharded vs
unsharded parity for the quantized path, prefix-hit vs cold-prefill
BIT-parity for greedy decode, and recompile-guard flatness across a
mixed quantized/prefix/cold workload — the capacity plays must be
invisible to everything but the byte counts.
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
from apex_tpu.serving.engine import Admission, Engine, EngineConfig
from apex_tpu.serving.scheduler import Scheduler
from apex_tpu.transformer.testing import standalone_gpt_config

VOCAB = 96

#: decode-logits tolerance of the quantized cache vs the compute-dtype
#: cache — the quantization error band (per-row symmetric absmax)
_KV_TOL = {"int8": dict(rtol=4e-2, atol=4e-2),
           "fp8": dict(rtol=8e-2, atol=8e-2)}


def _cfg(**overrides):
    base = dict(vocab_size=VOCAB, seq_len=64)
    base.update(overrides)
    return standalone_gpt_config(**base)


def _decode_logits(cfg, params, mesh, prompt, tok, pos, n_steps=2):
    """Prefill + ``n_steps`` decode steps; returns the stacked fp32
    logits of every step (the quantization-error observable)."""
    pspecs = gpt.param_specs(cfg)

    def run(p, t, tk):
        cache, _ = gpt.prefill(cfg, p, t, max_len=cfg.seq_len)
        outs = []
        pv = pos
        cur = tk
        for _ in range(n_steps):
            lg, cache = gpt.decode_step(cfg, p, cache, cur, pv)
            outs.append(lg)
            cur = jnp.argmax(lg, axis=-1).astype(jnp.int32)
            pv = pv + 1
        return jnp.stack(outs)

    return np.asarray(jax.jit(jax.shard_map(
        run, mesh=mesh,
        in_specs=(pspecs, P(None, None), P(None)),
        out_specs=P(None, None, None), check_vma=False))(
            params, prompt, tok), np.float32)


@pytest.mark.parametrize("kind", ["int8", "fp8"])
@pytest.mark.parametrize("impl", ["xla", "kernel"])
def test_kv_quant_decode_oracle(devices8, kind, impl):
    """The quantized cache's decode logits stay inside the
    quantization error band of the compute-dtype cache over several
    chained steps — for BOTH the Pallas kernel (interpreted off-TPU)
    and the XLA fallback layout."""
    if kind == "fp8" and not hasattr(jnp, "float8_e4m3fn"):
        pytest.skip("jax build without float8_e4m3fn")
    cfg0 = _cfg(seq_len=32)
    params = gpt.init(cfg0, jax.random.PRNGKey(0))
    mesh = mx.build_mesh(tp=1, devices=devices8[:1])
    prompt = jax.random.randint(jax.random.PRNGKey(1), (2, 6), 0, VOCAB)
    tok = jax.random.randint(jax.random.PRNGKey(2), (2,), 0, VOCAB)
    pos = jnp.asarray([6, 3], jnp.int32)
    base = _decode_logits(cfg0, params, mesh, prompt, tok, pos)
    quant = _decode_logits(
        dataclasses.replace(cfg0, kv_cache_dtype=kind,
                            decode_attn_impl=impl),
        params, mesh, prompt, tok, pos)
    np.testing.assert_allclose(quant, base, **_KV_TOL[kind])


def _run_trace(eng, reqs, **kw):
    sched = Scheduler(eng, **kw)
    for r in reqs:
        sched.submit(r)
    sched.run_until_idle()
    return sched


def _mixed_requests(n, max_prompt_len, *, seed0, eos=None, prefix=None):
    """Greedy + sampled lanes; with ``prefix``, every other prompt
    starts with it (the shared-template workload)."""
    reqs = []
    for i in range(n):
        p_len = 1 + (7 * i + 3) % max_prompt_len
        tail = [int(t) for t in jax.random.randint(
            jax.random.PRNGKey(seed0 + i), (p_len,), 0, VOCAB)]
        prompt = tail
        if prefix is not None and i % 2 == 0:
            prompt = (list(prefix) + tail)[:max_prompt_len]
            if len(prompt) <= len(prefix):
                prompt = list(prefix[:max_prompt_len - 1]) + tail[:1]
        sp = (SamplingParams(temperature=0.8 + 0.1 * (i % 3),
                             top_k=(0, 5, 9)[i % 3], seed=seed0 + i)
              if i % 3 == 1 else SamplingParams())
        reqs.append(Request(f"kv{seed0}_{i}", prompt,
                            max_tokens=3 + i % 4, sampling=sp,
                            eos_token_id=eos))
    return reqs


@pytest.mark.slow  # plain tp2-vs-tp1 engine parity stays tier-1 (test_serving); the quantized composition is long-suite (fleet-router tier-1 offset)
def test_quantized_engine_tp2_matches_tp1(devices8):
    """Sharded-vs-unsharded parity for the quantized serving path (the
    repo-wide oracle pattern): the same trace over tp=2 — per-head
    scales shard with their heads — emits identical tokens."""
    cfg = _cfg(kv_cache_dtype="int8")
    params = gpt.init(cfg, jax.random.PRNGKey(0))
    ecfg = EngineConfig(slots=2, max_prompt_len=8, max_seq_len=20)
    reqs = _mixed_requests(3, 8, seed0=300)
    clone = lambda: [Request(r.request_id, r.prompt, r.max_tokens,
                             sampling=r.sampling) for r in reqs]
    got1 = {rid: c.tokens for rid, c in _run_trace(
        Engine(cfg, params, mx.build_mesh(tp=1, devices=devices8[:1]),
               ecfg), clone()).completions.items()}
    got2 = {rid: c.tokens for rid, c in _run_trace(
        Engine(cfg, params, mx.build_mesh(tp=2, devices=devices8[:2]),
               ecfg), clone()).completions.items()}
    assert got1 == got2


def test_cache_bytes_reduction_and_accessor(devices8):
    """The capacity headline: int8 storage shrinks cache bytes per
    slot >= 1.9x vs the compute-dtype cache (data plane / storage
    width, plus the fp32 scale plane at 1/head_dim overhead), and
    ``Engine.cache_bytes()`` reports exactly the device buffer
    bytes."""
    params_of = {}
    engines = {}
    mesh = mx.build_mesh(tp=1, devices=devices8[:1])
    ecfg = EngineConfig(slots=2, max_prompt_len=8, max_seq_len=16)
    for kind in ("auto", "int8", "fp8"):
        if kind == "fp8" and not hasattr(jnp, "float8_e4m3fn"):
            continue
        cfg = _cfg(kv_cache_dtype=kind)
        params_of[kind] = gpt.init(cfg, jax.random.PRNGKey(0))
        engines[kind] = Engine(cfg, params_of[kind], mesh, ecfg)
    base = engines["auto"].cache_bytes()
    # exact accounting: [l, 2, B, h, S, d] data + [l, 2, B, h, S] scale
    cfg = _cfg()
    l, h, d = cfg.num_layers, cfg.num_heads, cfg.head_dim
    n = l * 2 * ecfg.slots * h * ecfg.max_seq_len
    assert base == n * d * jnp.dtype(cfg.compute_dtype).itemsize
    for kind in engines:
        if kind == "auto":
            continue
        got = engines[kind].cache_bytes()
        assert got == n * d * 1 + n * 4  # storage byte + fp32 scale
        ratio = base / got
        assert ratio >= 1.9, (
            f"{kind} cache-bytes reduction {ratio:.2f}x < 1.9x")
    # summary() carries the accessor
    s = Scheduler(engines["int8"]).summary()
    assert s["cache_bytes"] == engines["int8"].cache_bytes()


@pytest.mark.parametrize("kv", [
    "auto",
    # the quantized prefix hit rides the identical pooled-copy +
    # tail-extend path with only the slot-insert quantize added (the
    # quantized write contract has its own tier-1 oracle) — long-suite
    # confirmation (tier-1 budget offset for the fleet-router suite)
    pytest.param("int8", marks=pytest.mark.slow)])
def test_prefix_hit_matches_cold(devices8, kv):
    """The prefix-reuse bit-parity oracle: a prompt admitted through a
    pooled prefix (compiled gather copy + tail-only prefill) emits
    EXACTLY the cold-prefill stream — greedy and seeded-sampled lanes,
    plain and quantized caches."""
    cfg = _cfg(kv_cache_dtype=kv)
    params = gpt.init(cfg, jax.random.PRNGKey(0))
    mesh = mx.build_mesh(tp=1, devices=devices8[:1])
    ecfg = EngineConfig(slots=2, max_prompt_len=10, max_seq_len=24,
                        prefix_pool_slots=1)
    template = [int(t) for t in jax.random.randint(
        jax.random.PRNGKey(77), (9,), 0, VOCAB)]
    eng = Engine(cfg, params, mesh, ecfg).warmup()  # apex: noqa[TIER1-COST]: tiny engine; prefix-hit vs cold parity needs all warmed variants
    assert eng.prefix_splits == (8,)
    eng.register_prefix(template)
    cold = Engine(cfg, params, mesh, dataclasses.replace(
        ecfg, prefix_pool_slots=0)).warmup()  # apex: noqa[TIER1-COST]: cold-side twin of the parity oracle; same tiny engine
    for i, sp in enumerate((dict(), dict(temperature=0.9, top_k=5,
                                         seed=41))):
        prompt = template[:8] + [3 + i, 5]
        hit = eng.match_prefix(prompt)
        assert hit == (0, 8)
        out = {}
        for name, e in (("hit", eng), ("cold", cold)):
            kw = dict(sp)
            page, ps = (hit if name == "hit" else (None, 0))
            res = e.admit_many([Admission(
                slot=0, prompt=prompt, max_tokens=4,
                prefix_page=page, prefix_len=ps, **kw)])[0]
            toks = [res.first_token]
            for _ in range(3):
                t, _, _ = e.step()
                toks.append(int(t[0, 0]))
            out[name] = toks
        assert out["hit"] == out["cold"], (
            f"prefix-hit drift ({'sampled' if sp else 'greedy'}): "
            f"{out}")
    # the hit paid the TAIL bucket, not the full prompt bucket
    res = eng.admit_many([Admission(
        slot=1, prompt=template[:8] + [9, 9], max_tokens=2,
        prefix_page=0, prefix_len=8)])[0]
    assert res.bucket == 8 and res.batch_size == 1


@pytest.mark.slow  # register/match/admission stay exercised in tier-1 by the hit-parity oracle; the contract corners here are long-suite (fleet-router tier-1 offset)
def test_prefix_registration_and_match(devices8):
    """Host-side pool semantics: dedupe, longest-split matching,
    page/split validation, pool-full and too-short errors, and
    match_prefix returning None for misses / tail-less prompts."""
    cfg = _cfg()
    params = gpt.init(cfg, jax.random.PRNGKey(0))
    mesh = mx.build_mesh(tp=1, devices=devices8[:1])
    ecfg = EngineConfig(slots=2, max_prompt_len=10, max_seq_len=24,
                        prefix_pool_slots=1)
    eng = Engine(cfg, params, mesh, ecfg).warmup()
    template = list(range(1, 10))  # 9 tokens -> stored at split 8
    page = eng.register_prefix(template)
    assert page == 0
    assert eng.register_prefix(template) == 0  # dedupe, no new page
    assert eng.register_prefix(template[:8]) == 0  # same stored slice
    with pytest.raises(ValueError, match="full"):
        eng.register_prefix(list(range(20, 29)))
    with pytest.raises(ValueError, match="shorter"):
        eng.register_prefix([1, 2, 3])
    with pytest.raises(ValueError, match="vocab"):
        eng.register_prefix([VOCAB] * 8)
    # matching: longest usable split, >= 1 tail token required
    assert eng.match_prefix(template[:8] + [50]) == (0, 8)
    assert eng.match_prefix(template[:8]) is None       # no tail
    assert eng.match_prefix([9] + template[:7]) is None  # mismatch
    # admission-side validation: mismatched prompt vs page is loud
    with pytest.raises(ValueError, match="does not match"):
        eng.admit_many([Admission(slot=0, prompt=[9] * 9, max_tokens=2,
                                  prefix_page=0, prefix_len=8)])
    with pytest.raises(ValueError, match="prefix_len"):
        eng.admit_many([Admission(slot=0, prompt=template[:8] + [1],
                                  max_tokens=2, prefix_page=0,
                                  prefix_len=7)])
    with pytest.raises(ValueError, match="without prefix_page"):
        eng.admit_many([Admission(slot=0, prompt=template[:8] + [1],
                                  max_tokens=2, prefix_len=8)])
    # pool disabled: config knob off means no pool API
    cold = Engine(cfg, params, mesh,
                  dataclasses.replace(ecfg, prefix_pool_slots=0))
    assert not cold.prefix_pool_enabled
    assert cold.match_prefix(template) is None
    with pytest.raises(ValueError, match="disabled"):
        cold.register_prefix(template)
    # a ladder with no usable split is rejected at construction
    with pytest.raises(ValueError, match="usable split"):
        Engine(cfg, params, mesh, EngineConfig(
            slots=2, max_prompt_len=8, max_seq_len=12,
            prompt_buckets=(8,), prefix_pool_slots=1))
    # registering before warmup is loud (warmup resets the pool and
    # would silently drop the template otherwise)
    fresh = Engine(cfg, params, mesh, ecfg)
    fresh.register_prefix(template)
    with pytest.raises(ValueError, match="before warmup"):
        fresh.warmup()


def _assert_within_ulps(got, want, why, ulps=4):
    """``got`` within ``ulps`` units in the last place of ``want``'s
    largest magnitude (float32, the tiny preset's compute dtype)."""
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    np.testing.assert_allclose(
        got, want, rtol=0,
        atol=ulps * np.finfo(np.float32).eps * np.abs(want).max(),
        err_msg=why)


def test_prefill_extend_matches_cold_compute_scores(devices8):
    """attn_score_dtype="compute" parity: prefill_extend shares THE
    materialised-scores expression with the cold path
    (gpt._xla_attn_probs), so under BOTH score-dtype branches the end
    logits pick the same token as a cold prefill_many, and they and the
    tail K/V match it to a few ulp. Not bitwise: the two are
    differently shaped programs, and the backend promises no reduction
    order across shapes."""
    why = ("prefill_extend attends a rectangular [T, P + T] score block "
           "and cold prefill a square [S, S] one; XLA:CPU reduces the two "
           "in different orders (measured 1.7 ulp of the largest value), "
           "so hit == cold holds to a few ulp of the compute dtype, not "
           "bitwise: attn_score_dtype=")
    for sd in ("f32", "compute"):
        cfg = _cfg(seq_len=32, attn_score_dtype=sd)
        params = gpt.init(cfg, jax.random.PRNGKey(0))
        mesh = mx.build_mesh(tp=1, devices=devices8[:1])
        pspecs = gpt.param_specs(cfg)
        toks = jax.random.randint(jax.random.PRNGKey(11), (1, 10), 0,
                                  VOCAB)

        def run(p, t):
            cold_cache, cold_lg = gpt.prefill_many(
                cfg, p, t, jnp.asarray([9], jnp.int32), max_len=10)
            pre_cache, _ = gpt.prefill_many(
                cfg, p, t[:, :8], jnp.asarray([7], jnp.int32),
                max_len=8)
            tail = jnp.concatenate(
                [t[:, 8:], jnp.zeros((1, 6), jnp.int32)], axis=1)
            tail_kv, hit_lg = gpt.prefill_extend(
                cfg, p, pre_cache, tail, jnp.asarray([1], jnp.int32),
                prefix_len=8)
            return cold_cache, cold_lg, tail_kv, hit_lg

        cold_cache, cold_lg, tail_kv, hit_lg = jax.jit(jax.shard_map(
            run, mesh=mesh, in_specs=(pspecs, P(None, None)),
            out_specs=(P(None, None, None, "tp", None, None),
                       P(None, None),
                       P(None, None, None, "tp", None, None),
                       P(None, None)), check_vma=False))(params, toks)
        np.testing.assert_array_equal(
            np.argmax(np.asarray(hit_lg), -1),
            np.argmax(np.asarray(cold_lg), -1), err_msg=sd)
        _assert_within_ulps(hit_lg, cold_lg, why + sd)
        _assert_within_ulps(tail_kv[:, :, :, :, :2],
                            cold_cache[:, :, :, :, 8:10], why + sd)


def test_prefix_pool_rejects_moe(devices8):
    """MoE expert capacity depends on the routed token count, so
    tail-only routing breaks hit/cold parity — rejected loudly at
    engine construction AND at the gpt level."""
    cfg = _cfg(num_experts=2)
    params = gpt.init(_cfg(), jax.random.PRNGKey(0))  # never touched
    mesh = mx.build_mesh(tp=1, devices=devices8[:1])
    with pytest.raises(ValueError, match="num_experts"):
        Engine(cfg, params, mesh, EngineConfig(
            slots=2, max_prompt_len=10, max_seq_len=24,
            prefix_pool_slots=1))
    with pytest.raises(ValueError, match="num_experts"):
        gpt.prefill_extend(cfg, params, None,
                           np.zeros((1, 8), np.int32),
                           np.zeros((1,), np.int32), prefix_len=8)


# register/match/admission stay tier-1 via the hit-parity oracle
# (test_prefix_hit_matches_cold); the pool-reset failure corner is
# long-suite (durable-journal tier-1 offset)
@pytest.mark.slow
def test_register_prefix_failure_resets_pool(devices8):
    """The pool insert DONATES the pool buffer: a failing registration
    must reset the pool + registry to a clean empty state (no index
    entries pointing into a dead buffer, no leaked page) and leave the
    engine registerable again."""
    cfg = _cfg()
    params = gpt.init(cfg, jax.random.PRNGKey(0))
    mesh = mx.build_mesh(tp=1, devices=devices8[:1])
    eng = Engine(cfg, params, mesh, EngineConfig(
        slots=2, max_prompt_len=10, max_seq_len=24,
        prefix_pool_slots=2)).warmup()
    t1 = list(range(1, 10))
    assert eng.register_prefix(t1) == 0

    def boom(*a, **kw):
        raise RuntimeError("injected pool-insert failure")

    real = eng._pool_inserts
    eng._pool_inserts = {pb: boom for pb in real}
    with pytest.raises(RuntimeError, match="injected"):
        eng.register_prefix(list(range(20, 29)))
    eng._pool_inserts = real
    # clean slate: registry empty, no stale match, page 0 free again
    assert eng._prefix_used == 0
    assert eng.match_prefix(t1 + [5]) is None
    assert eng.register_prefix(t1) == 0
    hit = eng.match_prefix(t1[:8] + [3])
    assert hit == (0, 8)
    res = eng.admit_many([Admission(slot=0, prompt=t1[:8] + [3],
                                    max_tokens=2, prefix_page=hit[0],
                                    prefix_len=hit[1])])[0]
    assert 0 <= res.first_token < VOCAB


@pytest.mark.slow  # the hit==cold BIT-parity oracle stays tier-1 per dtype; this two-engine scheduler/telemetry composition is long-suite (multi-tenant tier-1 offset)
def test_scheduler_prefix_detection_and_oracle(devices8):
    """End-to-end through the scheduler: hits are detected at submit
    (hash-keyed, transparent to callers), counted in telemetry and
    summary(), and the mixed hit/miss trace emits token streams
    identical to the SAME trace on a pool-less engine."""
    from apex_tpu.telemetry import Registry

    cfg = _cfg()
    params = gpt.init(cfg, jax.random.PRNGKey(0))
    mesh = mx.build_mesh(tp=1, devices=devices8[:1])
    ecfg = EngineConfig(slots=2, max_prompt_len=10, max_seq_len=24,
                        decode_chunk=2, prefix_pool_slots=1)
    template = [int(t) for t in jax.random.randint(
        jax.random.PRNGKey(88), (8,), 0, VOCAB)]
    reqs = _mixed_requests(5, 10, seed0=500, prefix=template)
    clone = lambda: [Request(r.request_id, r.prompt, r.max_tokens,
                             sampling=r.sampling) for r in reqs]
    registry = Registry()
    eng = Engine(cfg, params, mesh, ecfg).warmup()
    eng.register_prefix(template)
    sched = _run_trace(eng, clone(), registry=registry,
                       pipeline_depth=2)
    s = sched.summary()
    n_hits = sum(1 for r in reqs
                 if eng.match_prefix(list(r.prompt)) is not None)
    assert n_hits >= 2  # the trace actually exercises the hit path
    assert s["prefix_hits"] == n_hits
    assert s["prefix_misses"] == len(reqs) - n_hits
    assert registry.counter("serving_prefix_hits_total").value == n_hits
    assert registry.gauge("serving_kv_cache_bytes").value == \
        eng.cache_bytes()
    cold = _run_trace(
        Engine(cfg, params, mesh, dataclasses.replace(
            ecfg, prefix_pool_slots=0)).warmup(), clone(),
        pipeline_depth=2)
    assert {rid: c.tokens for rid, c in sched.completions.items()} == \
        {rid: c.tokens for rid, c in cold.completions.items()}
    assert cold.summary()["prefix_hits"] == 0.0


@pytest.mark.slow  # guard flatness (test_resilience/test_serving), int8 parity, and prefix hit-parity each stay tier-1; this quantized+prefix+guard composition is long-suite (slo-observatory tier-1 offset)
def test_quantized_prefix_guard_stays_flat(devices8):
    """The PR-4 acceptance test extended to the capacity plays: a
    quantized (int8) engine with a prefix pool — warmup, register, then
    a mixed workload of prefix hits, cold admissions in BOTH buckets,
    chunked decode, varied sampling — never compiles inside an armed
    RecompileGuard."""
    from apex_tpu.telemetry.recompile import RecompileError

    cfg = _cfg(kv_cache_dtype="int8")
    params = gpt.init(cfg, jax.random.PRNGKey(0))
    mesh = mx.build_mesh(tp=1, devices=devices8[:1])
    eng = Engine(cfg, params, mesh, EngineConfig(
        slots=2, max_prompt_len=10, max_seq_len=24, decode_chunk=4,
        prefix_pool_slots=1))
    try:
        eng.warmup()
        sizes0 = eng.compiled_cache_sizes()
        assert set(sizes0.values()) == {1}, sizes0
        for name in ("pool_init", "pool_p8", "admit_prefix_p8_t8"):
            assert name in sizes0, sorted(sizes0)
        template = [int(t) for t in jax.random.randint(
            jax.random.PRNGKey(99), (8,), 0, VOCAB)]
        # build requests OUTSIDE the guard (prompt synthesis compiles)
        reqs = _mixed_requests(5, 10, seed0=700, prefix=template)
        with eng.recompile_guard() as g:
            eng.register_prefix(template)  # rides compiled pool_p8
            sched = Scheduler(eng, pipeline_depth=2)
            for r in reqs:
                sched.submit(r)
            sched.run_until_idle()
            assert len(sched.completions) == 5
            assert sched.summary()["prefix_hits"] >= 2
            assert g.check() == {}
        assert not g.tripped
        assert eng.compiled_cache_sizes() == sizes0
        with pytest.raises(RecompileError):
            with eng.recompile_guard():
                jax.jit(lambda x: x * 3.0)(np.arange(5.0))
    finally:
        eng.close()


def test_decode_attn_impl_predicate(monkeypatch):
    """THE decode-attention gate, arm by arm (satellite: one
    documented predicate, unit-tested, shared by the quantized
    layout). On-TPU behaviour is simulated by patching
    ``use_interpret``."""
    import apex_tpu.kernels._utils as ku

    base = standalone_gpt_config()
    # off-TPU (interpret): always xla, any horizon or dtype
    monkeypatch.setattr(ku, "use_interpret", lambda: True)
    assert gpt._decode_attn_impl(base, 4096) == "xla"
    assert gpt._decode_attn_impl(
        dataclasses.replace(base, kv_cache_dtype="int8"), 4096) == "xla"
    # on-TPU: kernel from horizon 128, xla below
    monkeypatch.setattr(ku, "use_interpret", lambda: False)
    assert gpt._decode_attn_impl(base, 128) == "kernel"
    assert gpt._decode_attn_impl(base, 127) == "xla"
    # f16 compute pins an UNQUANTIZED cache to xla (the widen-both-
    # caches trap) but a quantized cache crosses in storage dtype
    f16 = dataclasses.replace(base, compute_dtype=jnp.float16)
    assert gpt._decode_attn_impl(f16, 4096) == "xla"
    assert gpt._decode_attn_impl(
        dataclasses.replace(f16, kv_cache_dtype="int8"),
        4096) == "kernel"
    # explicit settings pass through; junk is loud
    assert gpt._decode_attn_impl(
        dataclasses.replace(base, decode_attn_impl="xla"), 4096) == "xla"
    assert gpt._decode_attn_impl(
        dataclasses.replace(base, decode_attn_impl="kernel"), 8) == \
        "kernel"
    with pytest.raises(ValueError, match="kv_cache_dtype"):
        gpt._kv_cache_dtype(
            dataclasses.replace(base, kv_cache_dtype="int4"))


# ---------------------------------------------------------------------------
# the decode scan's shape: the cache is carried, never sliced and stacked
# ---------------------------------------------------------------------------

def _sub_jaxprs(eqn):
    for v in eqn.params.values():
        for j in (v if isinstance(v, (tuple, list)) else (v,)):
            j = getattr(j, "jaxpr", j)
            if hasattr(j, "eqns"):
                yield j


def _walk(jaxpr):
    """Every equation under ``jaxpr``, kernels' bodies left out (their
    operands are blocks, not the cache)."""
    for eqn in jaxpr.eqns:
        yield eqn
        if eqn.primitive.name != "pallas_call":
            for sub in _sub_jaxprs(eqn):
                yield from _walk(sub)


def _layer_scans(jaxpr, n_layers):
    return [e for e in _walk(jaxpr) if e.primitive.name == "scan"
            and e.params["length"] == n_layers
            and "decode.layers" in str(e.source_info.name_stack)]


@pytest.mark.parametrize("kv", ["auto", "int8"])
@pytest.mark.parametrize("impl", ["kernel", "xla"])
@pytest.mark.parametrize("fn", ["decode_steps", "decode_steps_spec"])
def test_decode_scan_carries_the_cache(fn, impl, kv):
    """The layer scan of ``decode_step`` / ``decode_verify`` has the
    whole stacked cache in its CARRY and not among its ``xs`` / ``ys``;
    on the kernel path of ``decode_step`` its body has no
    ``dynamic_slice`` / ``concatenate`` / ``dynamic_update_slice`` on
    anything as large as one layer's cache (the verify forward keeps
    its materialised read of the layer; the XLA fallback slices the
    layer out and puts it back, by design)."""
    n_layers, slots, horizon = 3, 4, 32
    cfg = _cfg(seq_len=horizon, num_layers=n_layers, decode_attn_impl=impl,
               kv_cache_dtype=kv)
    params = jax.eval_shape(lambda: gpt.init(cfg, jax.random.PRNGKey(0)))
    cache = jax.eval_shape(
        lambda p: gpt.init_cache(cfg, p, slots, horizon), params)
    vec = lambda dt: jax.ShapeDtypeStruct((slots,), dt)
    state = {"tok": vec(jnp.int32), "pos": vec(jnp.int32),
             "remaining": vec(jnp.int32), "done": vec(jnp.bool_),
             "eos": vec(jnp.int32),
             "hist": jax.ShapeDtypeStruct((slots, 8), jnp.int32)}
    greedy = lambda logits, pos: jnp.argmax(logits, -1).astype(jnp.int32)
    if fn == "decode_steps":
        run = lambda p, c, s: gpt.decode_steps(cfg, p, c, s, 2,
                                               draw_fn=greedy)[:2]
    else:
        run = lambda p, c, s: gpt.decode_steps_spec(
            cfg, p, c, s, 2, spec_k=2, draw_fn=greedy)[:2]
    state_spec = {k: P() for k in state}
    jaxpr = jax.make_jaxpr(jax.shard_map(
        run, mesh=mx.build_mesh(tp=1, devices=jax.devices()[:1]),
        in_specs=(gpt.param_specs(cfg), gpt.cache_specs(cfg), state_spec),
        out_specs=(gpt.cache_specs(cfg), state_spec),
        check_vma=False))(params, cache, state).jaxpr
    scans = _layer_scans(jaxpr, n_layers)
    assert len(scans) == 1, [str(e.source_info.name_stack) for e in scans]
    scan = scans[0]
    n_consts, n_carry = scan.params["num_consts"], scan.params["num_carry"]
    shapes = lambda vs: [tuple(v.aval.shape) for v in vs]
    cache_shapes = [tuple(x.shape) for x in jax.tree.leaves(cache)]
    carried = shapes(scan.invars[n_consts:n_consts + n_carry])
    scanned = (shapes(scan.invars[n_consts + n_carry:])
               + shapes(scan.outvars[n_carry:]))
    for shape in cache_shapes:
        assert shape in carried, (shape, carried)
        assert shape not in scanned, (shape, scanned)
    layer_elems = int(np.prod(cache_shapes[0][1:]))
    big = [e.primitive.name for e in _walk(scan.params["jaxpr"].jaxpr)
           if e.primitive.name in ("dynamic_slice", "concatenate",
                                   "dynamic_update_slice")
           and max(int(np.prod(v.aval.shape))
                   for v in (*e.invars, *e.outvars)
                   if hasattr(v.aval, "shape")) >= layer_elems]
    if impl == "kernel" and fn == "decode_steps":
        assert not big, big
    else:
        assert big   # the walk does see such operations where they are
