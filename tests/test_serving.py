"""apex_tpu.serving — continuous-batching engine oracles.

Headline oracle: a continuously-batched run over N requests with
staggered arrivals and mixed per-request sampling params emits, per
request, exactly the tokens a solo ``gpt.generate`` run with that
request's params and key emits — and admission is trace-stable (no
compiled-program cache miss after warmup). Sharded-vs-unsharded parity
(tp=2 vs tp=1) follows the repo-wide oracle pattern."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from apex_tpu import mesh as mx
from apex_tpu import profiler
from apex_tpu.models import gpt
from apex_tpu.serving import Request, SamplingParams, sampling
from apex_tpu.serving.engine import Engine, EngineConfig
from apex_tpu.serving.request import (
    FINISH_EOS,
    FINISH_LENGTH,
    FINISH_TIMEOUT,
)
from apex_tpu.serving.scheduler import QueueFull, Scheduler
from apex_tpu.transformer.testing import standalone_gpt_config

VOCAB = 96


def _cfg(**overrides):
    base = dict(vocab_size=VOCAB, seq_len=64)
    base.update(overrides)
    return standalone_gpt_config(**base)


def _solo_generate(cfg, params, mesh, prompt, n_new, sp: SamplingParams,
                   eos_token_id=None):
    """The solo reference: one ``gpt.generate`` run with this request's
    params and key, exactly as a user would issue it."""
    pspecs = gpt.param_specs(cfg)
    key = (jax.random.PRNGKey(sp.seed)
           if sp.temperature > 0 and sp.seed is not None else None)
    out = jax.jit(jax.shard_map(
        lambda p, t: gpt.generate(
            cfg, p, t, n_new, temperature=sp.temperature, top_k=sp.top_k,
            top_p=sp.top_p, key=key, eos_token_id=eos_token_id,
            pad_token_id=0),
        mesh=mesh, in_specs=(pspecs, P(None, None)),
        out_specs=P(None, None), check_vma=False))(
            params, jnp.asarray([prompt], jnp.int32))
    return [int(t) for t in np.asarray(out)[0]]


def _expect_tokens(solo, eos):
    """Truncate the solo reference at its eos (inclusive) — the engine
    releases the slot there instead of emitting pad to the horizon."""
    if eos is None or eos not in solo:
        return solo
    return solo[:solo.index(eos) + 1]


def _mixed_requests(n, max_prompt_len, *, eos=None, seed0=100):
    """Deterministic mixed-parameter request set: greedy and sampled
    lanes, varied prompt lengths and budgets."""
    reqs = []
    for i in range(n):
        k = jax.random.PRNGKey(seed0 + i)
        p_len = 1 + (7 * i + 3) % max_prompt_len
        prompt = [int(t) for t in
                  jax.random.randint(k, (p_len,), 0, VOCAB)]
        if i % 3 == 1:
            sp = SamplingParams(temperature=0.8 + 0.1 * (i % 4),
                                top_k=(0, 7, 3, 11)[i % 4],
                                top_p=(1.0, 0.9, 0.8, 1.0)[i % 4],
                                seed=17 + i)
        else:
            sp = SamplingParams()
        reqs.append(Request(f"r{i}", prompt, max_tokens=4 + i % 5,
                            sampling=sp, eos_token_id=eos))
    return reqs


def _assert_oracle(cfg, params, mesh, sched, reqs):
    for r in reqs:
        comp = sched.completions[r.request_id]
        solo = _solo_generate(cfg, params, mesh, list(r.prompt),
                              r.max_tokens, r.sampling, r.eos_token_id)
        want = _expect_tokens(solo, r.eos_token_id)
        assert comp.tokens == want, (
            f"{r.request_id}: engine {comp.tokens} != solo {want}")
        want_reason = (FINISH_EOS if r.eos_token_id is not None
                       and want and want[-1] == r.eos_token_id
                       else FINISH_LENGTH)
        assert comp.finish_reason == want_reason


def test_continuous_batching_oracle(devices8):
    """Staggered arrivals + mixed sampling params: every request's output
    is token-identical to its solo ``gpt.generate`` run, and no program
    recompiles after warmup."""
    cfg = _cfg()
    params = gpt.init(cfg, jax.random.PRNGKey(0))
    mesh = mx.build_mesh(tp=1, devices=devices8[:1])
    eng = Engine(cfg, params, mesh,
                 EngineConfig(slots=2, max_prompt_len=10, max_seq_len=24))
    sched = Scheduler(eng)
    reqs = _mixed_requests(5, 10)

    sched.submit(reqs[0])
    sched.submit(reqs[1])
    sched.step()
    sched.step()
    sched.submit(reqs[2])
    sched.step()
    sched.submit(reqs[3])
    sched.submit(reqs[4])
    sched.run_until_idle()

    assert set(sched.completions) == {r.request_id for r in reqs}
    _assert_oracle(cfg, params, mesh, sched, reqs)
    # trace stability: one compiled program each, however many admissions
    sizes = eng.compiled_cache_sizes()
    for name in ("init", "step", "admit"):
        assert sizes[name] in (1, None), sizes


def test_oracle_with_eos_early_stop(devices8):
    """A request whose continuation hits eos releases its slot there and
    matches the solo run up to and including the eos token; the freed
    slot is reused by a queued request."""
    cfg = _cfg()
    params = gpt.init(cfg, jax.random.PRNGKey(0))
    mesh = mx.build_mesh(tp=1, devices=devices8[:1])
    base_prompt = [int(t) for t in
                   jax.random.randint(jax.random.PRNGKey(4), (6,), 0, VOCAB)]
    base = _solo_generate(cfg, params, mesh, base_prompt, 8,
                          SamplingParams())
    # the third greedy token becomes the stop token (the first two
    # collide with the prompt's own last token, which would trip the
    # eos-terminal-prompt completion at submit instead)
    eos = base[2]
    assert base_prompt[-1] != eos

    eng = Engine(cfg, params, mesh,
                 EngineConfig(slots=1, max_prompt_len=8, max_seq_len=20))
    sched = Scheduler(eng)
    reqs = [Request("stop", base_prompt, max_tokens=8,
                    eos_token_id=eos),
            Request("after", [int(x) for x in base_prompt[:4]],
                    max_tokens=5)]
    for r in reqs:
        sched.submit(r)
    sched.run_until_idle()
    comp = sched.completions["stop"]
    assert comp.finish_reason == FINISH_EOS
    assert comp.tokens == base[:3]  # up to and including the eos
    _assert_oracle(cfg, params, mesh, sched, reqs)


def test_eos_terminal_prompt_completes_at_submit(devices8):
    """The engine-boundary fix: a prompt already ending in eos completes
    immediately with zero generated tokens — it never occupies a slot
    (and the admit program is never even compiled for it)."""
    cfg = _cfg()
    params = gpt.init(cfg, jax.random.PRNGKey(0))
    mesh = mx.build_mesh(tp=1, devices=devices8[:1])
    eng = Engine(cfg, params, mesh,
                 EngineConfig(slots=1, max_prompt_len=8, max_seq_len=16))
    sched = Scheduler(eng)
    sched.submit(Request("done", [5, 9, 7], max_tokens=6, eos_token_id=7))
    comp = sched.completions["done"]
    assert comp.tokens == [] and comp.finish_reason == FINISH_EOS
    assert comp.ttft is None and comp.latency is not None
    assert not sched.queue and not sched.active
    assert eng.compiled_cache_sizes()["admit"] in (0, None)
    evs = sched.pop_events()
    assert len(evs) == 1 and evs[0].finished and evs[0].token is None
    # a prompt merely CONTAINING eos mid-stream is not terminal
    sched.submit(Request("mid", [7, 5, 9], max_tokens=2, eos_token_id=7))
    sched.run_until_idle()
    assert len(sched.completions["mid"].tokens) >= 1


def test_deadline_timeout_and_slot_reuse(devices8):
    """Deadlines under an injected clock: a queued request expires in
    place; an active slot is retired mid-decode with its partial output;
    the freed slot serves the next request normally."""
    cfg = _cfg()
    params = gpt.init(cfg, jax.random.PRNGKey(0))
    mesh = mx.build_mesh(tp=1, devices=devices8[:1])
    eng = Engine(cfg, params, mesh,
                 EngineConfig(slots=1, max_prompt_len=8, max_seq_len=24))
    now = [0.0]
    sched = Scheduler(eng, clock=lambda: now[0])
    prompt = [1, 2, 3, 4]
    sched.submit(Request("active", prompt, max_tokens=10, deadline=50.0))
    sched.submit(Request("queued", prompt, max_tokens=4, deadline=5.0))
    sched.step()  # admits "active"; "queued" still waiting
    now[0] = 6.0
    sched.step()  # "queued" expires in the queue
    qc = sched.completions["queued"]
    assert qc.finish_reason == FINISH_TIMEOUT and qc.tokens == []
    now[0] = 60.0
    sched.step()  # "active" blows its deadline mid-decode
    ac = sched.completions["active"]
    assert ac.finish_reason == FINISH_TIMEOUT
    assert 1 <= len(ac.tokens) < 10  # partial output is preserved
    assert not sched.active
    # the freed slot still serves
    sched.submit(Request("fresh", prompt, max_tokens=3))
    sched.run_until_idle()
    assert sched.completions["fresh"].finish_reason == FINISH_LENGTH
    assert len(sched.completions["fresh"].tokens) == 3


def test_queue_backpressure_and_validation(devices8):
    cfg = _cfg()
    params = gpt.init(cfg, jax.random.PRNGKey(0))
    mesh = mx.build_mesh(tp=1, devices=devices8[:1])
    eng = Engine(cfg, params, mesh,
                 EngineConfig(slots=1, max_prompt_len=6, max_seq_len=12))
    sched = Scheduler(eng, max_queue=1)
    sched.submit(Request("a", [1, 2], max_tokens=2))
    with pytest.raises(QueueFull):
        sched.submit(Request("b", [1, 2], max_tokens=2))
    with pytest.raises(ValueError, match="duplicate"):
        sched.submit(Request("a", [1, 2], max_tokens=2))
    with pytest.raises(ValueError, match="prompt length"):
        sched.submit(Request("long", [1] * 7, max_tokens=2))
    with pytest.raises(ValueError, match="max_tokens"):
        sched.submit(Request("zero", [1, 2], max_tokens=0))
    # budget beyond the slot horizon raises instead of silently clamping
    with pytest.raises(ValueError, match="max_tokens"):
        sched.submit(Request("big", [1, 2], max_tokens=11))
    with pytest.raises(ValueError, match="eos_token_id"):
        sched.submit(Request("eos", [1, 2], max_tokens=2,
                             eos_token_id=VOCAB))
    with pytest.raises(ValueError, match="eos_token_id"):
        eng.admit(0, [1, 2], max_tokens=2, eos_token_id=-1)
    with pytest.raises(ValueError, match="temperature"):
        sched.submit(Request("filt", [1, 2], max_tokens=2,
                             sampling=SamplingParams(top_k=3)))
    with pytest.raises(ValueError, match="seed"):
        sched.submit(Request("seed", [1, 2], max_tokens=2,
                             sampling=SamplingParams(temperature=1.0)))
    with pytest.raises(ValueError, match="max_tokens"):
        eng.admit(0, [1, 2], max_tokens=99)
    # an out-of-range slot would CLAMP into a neighbour's cache if traced
    with pytest.raises(ValueError, match="slot"):
        eng.admit(1, [1, 2], max_tokens=2)
    with pytest.raises(ValueError, match="slot"):
        eng.admit(-1, [1, 2], max_tokens=2)


def test_engine_config_validation(devices8):
    cfg = _cfg()
    params = gpt.init(cfg, jax.random.PRNGKey(0))
    mesh = mx.build_mesh(tp=1, devices=devices8[:1])
    with pytest.raises(ValueError, match="slot"):
        Engine(cfg, params, mesh, EngineConfig(slots=0))
    with pytest.raises(ValueError, match="max_prompt_len"):
        Engine(cfg, params, mesh,
               EngineConfig(max_prompt_len=32, max_seq_len=16))
    with pytest.raises(ValueError, match="position"):
        Engine(cfg, params, mesh,
               EngineConfig(max_prompt_len=16, max_seq_len=128))
    with pytest.raises(ValueError, match="engine_cfg or field"):
        Engine(cfg, params, mesh, EngineConfig(), slots=2)
    mesh_dp = mx.build_mesh(dp=2, tp=1, devices=devices8[:2])
    with pytest.raises(ValueError, match="tp only"):
        Engine(cfg, params, mesh_dp,
               EngineConfig(max_prompt_len=8, max_seq_len=16))


def _run_trace(eng, reqs):
    sched = Scheduler(eng)
    for r in reqs:
        sched.submit(r)
    sched.run_until_idle()
    return {rid: c.tokens for rid, c in sched.completions.items()}


def test_engine_tp2_matches_tp1(devices8):
    """Sharded-vs-unsharded parity for the serving path (the repo-wide
    oracle pattern): the same trace over tp=2 emits identical tokens."""
    cfg = _cfg()
    params = gpt.init(cfg, jax.random.PRNGKey(0))
    ecfg = EngineConfig(slots=2, max_prompt_len=8, max_seq_len=20)
    reqs = _mixed_requests(4, 8, seed0=300)
    got1 = _run_trace(
        Engine(cfg, params, mx.build_mesh(tp=1, devices=devices8[:1]),
               ecfg), reqs)
    got2 = _run_trace(
        Engine(cfg, params, mx.build_mesh(tp=2, devices=devices8[:2]),
               ecfg), [Request(r.request_id, r.prompt, r.max_tokens,
                               sampling=r.sampling) for r in reqs])
    assert got1 == got2


def test_scheduler_metrics_and_summary(devices8, tmp_path):
    """Serving metrics flow through profiler.MetricsLogger, and
    summary() carries throughput + TTFT/latency percentiles. A
    zero-token completion (eos-terminal prompt) OMITS ``ttft_s`` from
    its record — there is no first token, and the old ``-1.0`` sentinel
    silently poisoned any downstream aggregation."""
    import json

    cfg = _cfg()
    params = gpt.init(cfg, jax.random.PRNGKey(0))
    mesh = mx.build_mesh(tp=1, devices=devices8[:1])
    eng = Engine(cfg, params, mesh,
                 EngineConfig(slots=2, max_prompt_len=6, max_seq_len=16))
    jsonl = str(tmp_path / "serve.jsonl")
    with profiler.MetricsLogger(jsonl_path=jsonl) as logger:
        sched = Scheduler(eng, metrics=logger)
        for r in _mixed_requests(3, 6, seed0=400):
            sched.submit(r)
        # eos-terminal prompt: completes at submit with no first token
        sched.submit(Request("term", [5, 9, 7], max_tokens=4,
                             eos_token_id=7))
        sched.run_until_idle()
    assert logger._jsonl.closed  # context manager closed the sink
    s = sched.summary()
    assert s["requests_completed"] == 4.0
    assert s["tokens_per_sec"] > 0
    for k in ("ttft_mean_ms", "ttft_p99_ms", "token_latency_mean_ms"):
        assert s[k] >= 0.0
    lines = [json.loads(l) for l in open(jsonl)]
    step_recs = [l for l in lines if "slot_occupancy" in l]
    comp_recs = [l for l in lines if "completed" in l]
    assert step_recs and len(comp_recs) == 4
    assert max(l["slot_occupancy"] for l in step_recs) == 1.0
    with_ttft = [l for l in comp_recs if "ttft_s" in l]
    assert len(with_ttft) == 3  # the slotted requests
    assert all(l["ttft_s"] >= 0.0 for l in with_ttft)
    term = [l for l in comp_recs if l["n_tokens"] == 0.0]
    assert len(term) == 1 and "ttft_s" not in term[0]
    assert term[0]["latency_s"] >= 0.0


# --- sampling extraction: old-vs-new parity --------------------------------


def _legacy_filter_logits(logits, top_k, top_p):
    """Verbatim copy of the pre-refactor ``gpt._filter_logits`` — the
    reference the extracted ``serving.sampling.filter_logits`` is pinned
    against."""
    vocab = logits.shape[-1]
    kk = top_k if 0 < top_k < vocab else 0
    pp = top_p if 0.0 < top_p < 1.0 else 0.0
    if not kk and not pp:
        return logits
    neg = jnp.finfo(logits.dtype).min
    sorted_desc = jnp.sort(logits, axis=-1)[..., ::-1]
    if kk:
        sorted_desc = jnp.where(jnp.arange(vocab) < kk, sorted_desc, neg)
        thresh = sorted_desc[..., kk - 1][..., None]
    else:
        thresh = None
    if pp:
        probs = jax.nn.softmax(sorted_desc, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        keep = jnp.concatenate(
            [jnp.ones_like(cum[..., :1], bool), cum[..., :-1] < pp],
            axis=-1)
        pthresh = jnp.min(
            jnp.where(keep, sorted_desc, jnp.inf), axis=-1, keepdims=True)
        thresh = pthresh if thresh is None else jnp.maximum(thresh, pthresh)
    return jnp.where(logits < thresh, neg, logits)


def _legacy_generate(cfg, params, prompt, n_new, *, temperature=0.0,
                     top_k=0, top_p=1.0, key=None):
    """``gpt.generate``'s pre-refactor body with its draw closure inlined
    verbatim (prefill + decode_step + legacy filter) — local semantics."""
    b, p_len = prompt.shape
    total = p_len + n_new

    def draw(logits, t):
        if temperature > 0.0:
            scaled = _legacy_filter_logits(
                logits / temperature, top_k, top_p)
            return jax.random.categorical(
                jax.random.fold_in(key, t), scaled, axis=-1
            ).astype(jnp.int32)
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)

    cache0, logits0 = gpt.prefill(cfg, params, prompt, max_len=total)
    first = draw(logits0, p_len - 1)

    def step(carry, t):
        tok, cache = carry
        logits, cache = gpt.decode_step(cfg, params, cache, tok, t)
        nxt = draw(logits, t)
        return (nxt, cache), nxt

    _, outs = jax.lax.scan(step, (first, cache0),
                           jnp.arange(p_len, total - 1, dtype=jnp.int32))
    return jnp.transpose(
        jnp.concatenate([first[None], outs], axis=0), (1, 0))


def test_generate_matches_pre_refactor_tokens(devices8):
    """The extraction satellite's parity pin: post-refactor
    ``gpt.generate`` (drawing through serving.sampling) emits exactly
    the tokens the pre-refactor implementation emits — greedy and
    sampled with temperature/top_k/top_p."""
    cfg = _cfg(seq_len=32)
    params = gpt.init(cfg, jax.random.PRNGKey(0))
    mesh = mx.build_mesh(tp=1, devices=devices8[:1])
    pspecs = gpt.param_specs(cfg)
    prompt = jax.random.randint(jax.random.PRNGKey(1), (3, 8), 0, VOCAB)
    for kw in (dict(),
               dict(temperature=0.9, top_k=7, top_p=0.8,
                    key=jax.random.PRNGKey(3))):
        new = jax.jit(jax.shard_map(
            lambda p, t: gpt.generate(cfg, p, t, 6, **kw), mesh=mesh,
            in_specs=(pspecs, P(None, None)), out_specs=P(None, None),
            check_vma=False))(params, prompt)
        old = jax.jit(jax.shard_map(
            lambda p, t: _legacy_generate(cfg, p, t, 6, **kw), mesh=mesh,
            in_specs=(pspecs, P(None, None)), out_specs=P(None, None),
            check_vma=False))(params, prompt)
        np.testing.assert_array_equal(np.asarray(new), np.asarray(old))


def test_draw_slots_matches_scalar_draw():
    """Each lane of the vectorised per-slot draw is bit-identical to the
    scalar ``draw`` a solo generate run would issue — greedy and sampled
    lanes side by side in one batch."""
    logits = jax.random.normal(jax.random.PRNGKey(0), (4, 33)) * 3.0
    temps = [0.0, 0.7, 1.3, 1.0]
    top_ks = [0, 5, 0, 3]
    top_ps = [1.0, 1.0, 0.6, 0.9]
    ts = [3, 5, 0, 9]
    keys = jnp.stack([jnp.asarray(jax.random.PRNGKey(40 + i), jnp.uint32)
                      for i in range(4)])
    got = sampling.draw_slots(
        logits, keys, jnp.asarray(ts, jnp.int32),
        jnp.asarray(temps, jnp.float32), jnp.asarray(top_ks, jnp.int32),
        jnp.asarray(top_ps, jnp.float32))
    for i in range(4):
        want = sampling.draw(
            logits[i:i + 1], ts[i], temperature=temps[i], top_k=top_ks[i],
            top_p=top_ps[i], key=keys[i])[0]
        assert int(got[i]) == int(want), f"lane {i}"


def test_traced_filter_matches_static():
    """The traced-parameter filter (per-slot values under vmap) is
    value-equal to the static form across enabled, combined, and
    disabled settings."""
    logits = jax.random.normal(jax.random.PRNGKey(7), (2, 33)) * 2.0
    for kk in (0, 2, 5, 33):
        for pp in (1.0, 0.85, 0.3):
            want = np.asarray(sampling.filter_logits(logits, kk, pp))
            got = np.asarray(sampling._filter_logits_traced(
                logits, jnp.int32(kk), jnp.float32(pp)))
            np.testing.assert_array_equal(got, want, err_msg=f"k={kk} p={pp}")


# --- chunked decode (gpt.decode_steps + EngineConfig.decode_chunk) ---------


def _singles_reference(cfg, params, cache, state, n, pad):
    """n SINGLE per-token steps — the pre-chunk engine step body
    verbatim (decode_step + draw_slots + eos/budget masking + the
    logprob gather), the reference ``gpt.decode_steps(n)`` is pinned
    against."""
    toks, lps, fins = [], [], []
    for _ in range(n):
        logits, cache = gpt.decode_step(
            cfg, params, cache, state["tok"], state["pos"])
        nxt = sampling.draw_slots(
            logits, state["key"], state["pos"], state["temp"],
            state["top_k"], state["top_p"])
        lp = jnp.take_along_axis(
            jax.nn.log_softmax(logits, axis=-1), nxt[:, None],
            axis=1)[:, 0]
        live = ~state["done"]
        emit = jnp.where(live, nxt, jnp.int32(pad))
        lp = jnp.where(live, lp, jnp.float32(0.0))
        remaining = state["remaining"] - live.astype(jnp.int32)
        hit_eos = live & (state["eos"] >= 0) & (emit == state["eos"])
        finished = live & (hit_eos | (remaining <= 0))
        state = {
            **state,
            "tok": jnp.where(live, emit, state["tok"]),
            "pos": state["pos"] + live.astype(jnp.int32),
            "remaining": remaining,
            "done": state["done"] | finished,
        }
        toks.append(emit)
        lps.append(lp)
        fins.append(finished)
    return (cache, state, jnp.stack(toks, 1), jnp.stack(lps, 1),
            jnp.stack(fins, 1))


def _chunk_state(b):
    """Mixed per-slot state: greedy and sampled lanes, one eos lane,
    one budget-starved lane, one already-done lane."""
    keys = jnp.stack([jnp.asarray(jax.random.PRNGKey(60 + i), jnp.uint32)
                      for i in range(b)])
    return {
        "tok": jnp.asarray([3, 9, 14, 2][:b], jnp.int32),
        "pos": jnp.asarray([6, 4, 2, 5][:b], jnp.int32),
        "remaining": jnp.asarray([20, 3, 20, 20][:b], jnp.int32),
        "done": jnp.asarray([False, False, False, True][:b], bool),
        "temp": jnp.asarray([0.0, 0.9, 1.2, 0.0][:b], jnp.float32),
        "top_k": jnp.asarray([0, 5, 0, 0][:b], jnp.int32),
        "top_p": jnp.asarray([1.0, 0.9, 1.0, 1.0][:b], jnp.float32),
        "key": keys,
        "eos": jnp.asarray([11, -1, 11, -1][:b], jnp.int32),
    }


def _run_decode_steps(cfg, params, mesh, n, chunked: bool):
    """Prefill a 4-row batch, then n tokens — one decode_steps(n) scan
    or n single per-token step dispatches."""
    pspecs = gpt.param_specs(cfg)
    prompt = jax.random.randint(jax.random.PRNGKey(2), (4, 8), 0, VOCAB)
    cache_spec = P(None, None, None, "tp", None, None)
    state = _chunk_state(4)
    st_spec = {k: P() for k in state}

    def pre(p, t):
        cache, _ = gpt.prefill(cfg, p, t, max_len=24)
        return cache

    cache = jax.jit(jax.shard_map(
        pre, mesh=mesh, in_specs=(pspecs, P(None, None)),
        out_specs=cache_spec, check_vma=False))(params, prompt)
    if chunked:
        fn = jax.jit(jax.shard_map(
            lambda p, c, st: gpt.decode_steps(cfg, p, c, st, n),
            mesh=mesh, in_specs=(pspecs, cache_spec, st_spec),
            out_specs=(cache_spec, st_spec, P(), P(), P()),
            check_vma=False))
        _, _, toks, lps, fins = fn(params, cache, state)
    else:
        fn = jax.jit(jax.shard_map(
            lambda p, c, st: _singles_reference(cfg, p, c, st, 1, 0),
            mesh=mesh, in_specs=(pspecs, cache_spec, st_spec),
            out_specs=(cache_spec, st_spec, P(), P(), P()),
            check_vma=False))
        cols_t, cols_l, cols_f = [], [], []
        for _ in range(n):
            cache, state, t1, l1, f1 = fn(params, cache, state)
            cols_t.append(t1)
            cols_l.append(l1)
            cols_f.append(f1)
        toks = jnp.concatenate(cols_t, axis=1)
        lps = jnp.concatenate(cols_l, axis=1)
        fins = jnp.concatenate(cols_f, axis=1)
    return np.asarray(toks), np.asarray(lps), np.asarray(fins)


def test_decode_steps_matches_single_steps(devices8):
    """Token parity: decode_steps(n) == n single decode_step dispatches
    — greedy AND sampled lanes, eos and budget finishes mid-chunk, and
    tp2-vs-tp1 (the repo-wide sharded-parity oracle)."""
    cfg = _cfg()
    params = gpt.init(cfg, jax.random.PRNGKey(0))
    got = {}
    for tp in (1, 2):
        mesh = mx.build_mesh(tp=tp, devices=devices8[:tp])
        got[(tp, "chunk")] = _run_decode_steps(cfg, params, mesh, 6, True)
        got[(tp, "single")] = _run_decode_steps(cfg, params, mesh, 6,
                                                False)
    def check(lhs, rhs, msg):
        # tokens/finished pin bitwise; the logprob floats ride
        # different XLA programs (scan vs unrolled, tp1 vs tp2), so
        # they pin to fp32 tolerance instead
        np.testing.assert_array_equal(lhs[0], rhs[0], err_msg=msg)
        np.testing.assert_allclose(lhs[1], rhs[1], rtol=1e-5,
                                   atol=1e-5, err_msg=msg)
        np.testing.assert_array_equal(lhs[2], rhs[2], err_msg=msg)

    for tp in (1, 2):
        check(got[(tp, "chunk")], got[(tp, "single")], f"tp{tp}")
    check(got[(1, "chunk")], got[(2, "chunk")], "tp2 vs tp1")
    toks, lps, fins = got[(1, "chunk")]
    assert np.isfinite(lps).all() and (lps <= 0.0).all()
    assert fins.any(), "expected a mid-chunk finish in the fixture"
    # the budget-starved lane (remaining=3) pads after its 3rd token
    assert (toks[1, 3:] == 0).all()


def test_engine_chunked_matches_per_token_and_solo(devices8):
    """decode_chunk=8 vs =1 vs solo generate: bit-identical tokens per
    request, and the chunked engine's programs stay at one compiled
    entry across admissions (trace stability)."""
    cfg = _cfg()
    params = gpt.init(cfg, jax.random.PRNGKey(0))
    mesh = mx.build_mesh(tp=1, devices=devices8[:1])
    reqs = _mixed_requests(5, 8, eos=13, seed0=700)
    mk = lambda chunk: Engine(
        cfg, params, mesh,
        EngineConfig(slots=2, max_prompt_len=8, max_seq_len=24,
                     decode_chunk=chunk))
    eng8 = mk(8)
    got8 = _run_trace(eng8, reqs)
    got1 = _run_trace(mk(1), [Request(r.request_id, r.prompt,
                                      r.max_tokens, sampling=r.sampling,
                                      eos_token_id=r.eos_token_id)
                              for r in reqs])
    assert got8 == got1
    sizes = eng8.compiled_cache_sizes()
    for name in ("init", "step", "admit"):
        assert sizes[name] in (1, None), sizes
    # solo-generate parity through the chunked path (the headline
    # oracle, re-run at chunk=8)
    sched = Scheduler(eng8)
    for r in _mixed_requests(4, 8, eos=13, seed0=900):
        sched.submit(r)
    sched.run_until_idle()
    _assert_oracle(cfg, params, mesh, sched,
                   _mixed_requests(4, 8, eos=13, seed0=900))


def test_engine_decode_chunk_validation(devices8):
    cfg = _cfg()
    params = gpt.init(cfg, jax.random.PRNGKey(0))
    mesh = mx.build_mesh(tp=1, devices=devices8[:1])
    with pytest.raises(ValueError, match="decode_chunk"):
        Engine(cfg, params, mesh,
               EngineConfig(max_prompt_len=8, max_seq_len=16,
                            decode_chunk=0))


# --- soak (slow) + fast smoke ----------------------------------------------


def _soak(cfg, params, mesh, n_requests, slots, *, eos=None):
    eng = Engine(cfg, params, mesh,
                 EngineConfig(slots=slots, max_prompt_len=10,
                              max_seq_len=24))
    sched = Scheduler(eng)
    reqs = _mixed_requests(n_requests, 10, eos=eos, seed0=500)
    # staggered arrivals: a deterministic drip of 2 submissions per tick
    pending = list(reqs)
    while pending or sched.queue or sched.active:
        for r in pending[:2]:
            sched.submit(r)
        pending = pending[2:]
        sched.step()
    return eng, sched, reqs


@pytest.mark.slow
def test_serving_soak_full_parity(devices8):
    """Soak/stress: 18 mixed requests (greedy + sampled + eos lanes)
    dripped through 3 slots — EVERY request stays token-identical to its
    solo generate run, and the programs never recompile."""
    cfg = _cfg()
    params = gpt.init(cfg, jax.random.PRNGKey(0))
    mesh = mx.build_mesh(tp=1, devices=devices8[:1])
    eng, sched, reqs = _soak(cfg, params, mesh, 18, 3, eos=11)
    assert len(sched.completions) == 18
    _assert_oracle(cfg, params, mesh, sched, reqs)
    sizes = eng.compiled_cache_sizes()
    for name in ("step", "admit"):
        assert sizes[name] in (1, None), sizes


def test_serving_soak_smoke(devices8):
    """Tier-1 smoke variant of the soak: a short drip through 2 slots
    completes every request with sane shapes and stable programs (full
    per-request parity runs in the slow soak)."""
    cfg = _cfg()
    params = gpt.init(cfg, jax.random.PRNGKey(0))
    mesh = mx.build_mesh(tp=1, devices=devices8[:1])
    eng, sched, reqs = _soak(cfg, params, mesh, 5, 2)
    assert len(sched.completions) == 5
    for r in reqs:
        comp = sched.completions[r.request_id]
        assert 1 <= len(comp.tokens) <= r.max_tokens
        assert all(0 <= t < VOCAB for t in comp.tokens)
        assert comp.finish_reason == FINISH_LENGTH
        assert comp.ttft is not None and comp.ttft >= 0
    sizes = eng.compiled_cache_sizes()
    for name in ("step", "admit"):
        assert sizes[name] in (1, None), sizes


# --- batched, bucketed admission + pipelined loop (PR 4) --------------------


def test_admit_many_matches_single_admits(devices8):
    """The admission-parity oracle: ``admit_many(k)`` — one padded
    [k, bucket] prefill forward + one state/cache scatter — produces
    the SAME first tokens and the same subsequent decode streams as k
    single ``admit`` calls in the same order (greedy and sampled lanes,
    mixed prompt lengths spanning buckets), with logprobs equal to a
    few ulp."""
    cfg = _cfg()
    params = gpt.init(cfg, jax.random.PRNGKey(0))
    mesh = mx.build_mesh(tp=1, devices=devices8[:1])
    from apex_tpu.serving.engine import Admission

    ecfg = EngineConfig(slots=4, max_prompt_len=10, max_seq_len=24)
    items = []
    for i in range(4):
        p_len = (3, 9, 5, 10)[i]
        prompt = [int(t) for t in jax.random.randint(
            jax.random.PRNGKey(810 + i), (p_len,), 0, VOCAB)]
        kw = (dict(temperature=0.9, top_k=5, seed=60 + i) if i % 2
              else {})
        items.append(Admission(slot=i, prompt=prompt, max_tokens=8,
                               eos_token_id=13, **kw))

    eng_b = Engine(cfg, params, mesh, ecfg)
    batched = eng_b.admit_many(items)
    assert [r.batch_size for r in batched] == [4] * 4
    assert batched[0].bucket == 10  # smallest bucket >= the batch max
    eng_s = Engine(cfg, params, mesh, ecfg)
    singles = [eng_s.admit(a.slot, a.prompt, a.max_tokens,
                           temperature=a.temperature, top_k=a.top_k,
                           top_p=a.top_p, seed=a.seed,
                           eos_token_id=a.eos_token_id) for a in items]
    assert [(r.first_token, r.hit_eos, r.finished) for r in batched] == \
        singles
    for _ in range(4):  # the inserted caches/state rows decode the same
        tb, lb, fb = eng_b.step()
        ts, ls, fs = eng_s.step()
        np.testing.assert_array_equal(tb, ts)
        np.testing.assert_array_equal(fb, fs)
        # the [4, bucket] and [1, bucket] prefills are differently
        # shaped programs, and XLA:CPU promises no reduction order
        # across shapes: their caches, and so the logprobs decoded from
        # them, agree to a few ulp of the compute dtype (measured 0.5
        # ulp of the largest logprob), not bitwise
        np.testing.assert_allclose(
            lb, ls, rtol=0,
            atol=4 * np.finfo(np.float32).eps * np.abs(ls).max(),
            err_msg="logprobs of a batched admission vs single "
            "admissions: the same tokens, float32 values within 4 ulp")
    # a 3-item call decomposes over the ladder largest-first: 2 + 1
    eng_b2 = Engine(cfg, params, mesh, ecfg)
    three = eng_b2.admit_many(items[:3])
    assert [(r.batch_size, r.group) for r in three] == \
        [(2, 0), (2, 0), (1, 1)]
    assert [r.first_token for r in three] == \
        [s[0] for s in singles[:3]]
    with pytest.raises(ValueError, match="distinct"):
        eng_b2.admit_many([items[0], items[0]])


def test_bucketed_prefill_matches_max_length(devices8):
    """Bucketed admission is bit-identical to the flat max-length
    prefill (causal padding exactness — same argument as prefill_at),
    across a whole scheduler trace AND for the same request admitted
    at two different bucket ladders."""
    cfg = _cfg()
    params = gpt.init(cfg, jax.random.PRNGKey(0))
    mesh = mx.build_mesh(tp=1, devices=devices8[:1])
    reqs = _mixed_requests(6, 10, eos=13, seed0=820)
    clone = lambda: [Request(r.request_id, r.prompt, r.max_tokens,
                             sampling=r.sampling,
                             eos_token_id=r.eos_token_id) for r in reqs]
    got_bucketed = _run_trace(
        Engine(cfg, params, mesh,
               EngineConfig(slots=2, max_prompt_len=10, max_seq_len=24)),
        clone())
    got_flat = _run_trace(
        Engine(cfg, params, mesh,
               EngineConfig(slots=2, max_prompt_len=10, max_seq_len=24,
                            prompt_buckets=(10,),
                            admit_batch_sizes=(1,))),
        clone())
    assert got_bucketed == got_flat


def test_engine_ladder_validation(devices8):
    cfg = _cfg()
    params = gpt.init(cfg, jax.random.PRNGKey(0))
    mesh = mx.build_mesh(tp=1, devices=devices8[:1])
    mk = lambda **kw: Engine(cfg, params, mesh, EngineConfig(
        slots=2, max_prompt_len=8, max_seq_len=16, **kw))
    with pytest.raises(ValueError, match="end"):
        mk(prompt_buckets=(4, 6))       # must end at max_prompt_len
    with pytest.raises(ValueError, match="increasing"):
        mk(prompt_buckets=(8, 4))
    with pytest.raises(ValueError, match="start at 1"):
        mk(admit_batch_sizes=(2,))
    with pytest.raises(ValueError, match="exceeds slots"):
        mk(admit_batch_sizes=(1, 4))
    from apex_tpu.serving.engine import default_prompt_buckets

    assert default_prompt_buckets(64) == (8, 16, 32, 64)
    assert default_prompt_buckets(10) == (8, 10)
    assert default_prompt_buckets(6) == (6,)
    with pytest.raises(ValueError, match="pipeline_depth"):
        Scheduler(mk(), pipeline_depth=0)
    with pytest.raises(ValueError, match="max_admit_batch"):
        Scheduler(mk(), max_admit_batch=0)


def test_pipelined_matches_serial_and_solo(devices8):
    """The pipelining oracle: per-request token streams are
    bit-identical at pipeline depths 1 (serial), 2, and 3, with and
    without batched admission, and match solo ``gpt.generate`` — the
    in-flight snapshot bookkeeping never corrupts a stream."""
    cfg = _cfg()
    params = gpt.init(cfg, jax.random.PRNGKey(0))
    mesh = mx.build_mesh(tp=1, devices=devices8[:1])
    reqs = _mixed_requests(7, 10, eos=13, seed0=830)
    mk_eng = lambda: Engine(
        cfg, params, mesh,
        EngineConfig(slots=2, max_prompt_len=10, max_seq_len=24,
                     decode_chunk=4))
    got = {}
    scheds = {}
    for depth, mab in ((1, 1), (2, None), (3, None)):
        sched = Scheduler(mk_eng(), pipeline_depth=depth,
                          max_admit_batch=mab)
        for r in reqs:
            sched.submit(Request(r.request_id, r.prompt, r.max_tokens,
                                 sampling=r.sampling,
                                 eos_token_id=r.eos_token_id))
        sched.run_until_idle()
        assert not sched._inflight  # idle means the pipeline drained
        got[(depth, mab)] = {rid: c.tokens
                             for rid, c in sched.completions.items()}
        scheds[(depth, mab)] = sched
    assert got[(1, 1)] == got[(2, None)] == got[(3, None)]
    # batched admission actually amortised: fewer dispatches than
    # requests on the pipelined runs
    assert scheds[(2, None)].summary()["admit_dispatches"] < len(reqs)
    _assert_oracle(cfg, params, mesh, scheds[(2, None)], reqs)


def test_retire_lands_while_chunk_in_flight(devices8):
    """Deadline expiry with a decode chunk IN FLIGHT (pipeline depth
    2): the retired request keeps only the tokens collected before the
    retire (the in-flight chunk's lanes are dropped — the device emits
    its tokens, the scheduler discards them), its span timeline still
    closes with a ``retired`` mark, the batch-mate's stream is
    untouched, and the freed slot serves a fresh request with full
    solo parity — no state corruption."""
    from apex_tpu.telemetry import SpanRecorder
    from apex_tpu.telemetry import spans as spans_mod

    cfg = _cfg()
    params = gpt.init(cfg, jax.random.PRNGKey(0))
    mesh = mx.build_mesh(tp=1, devices=devices8[:1])
    eng = Engine(cfg, params, mesh,
                 EngineConfig(slots=2, max_prompt_len=8, max_seq_len=24,
                              decode_chunk=4))
    now = [0.0]
    spans = SpanRecorder()
    sched = Scheduler(eng, clock=lambda: now[0], pipeline_depth=2,
                      spans=spans)
    doomed = Request("doomed", [1, 2, 3], max_tokens=12, deadline=5.0)
    mate = Request("mate", [4, 5, 6, 7], max_tokens=10)
    sched.submit(doomed)
    sched.submit(mate)
    sched.step()   # admits both, dispatches chunk 1 (stays in flight)
    assert sched._inflight and len(sched.completions) == 0
    now[0] = 6.0   # chunk 1 still in flight when the deadline lands
    sched.step()   # expire retires "doomed"; its in-flight lanes drop
    dc = sched.completions["doomed"]
    assert dc.finish_reason == FINISH_TIMEOUT
    assert len(dc.tokens) == 1  # the admission token only — chunk 1's
    # four real tokens for the retired slot were dropped, not leaked
    sched.run_until_idle()
    mc = sched.completions["mate"]
    assert mc.tokens == _solo_generate(cfg, params, mesh, [4, 5, 6, 7],
                                       10, mate.sampling)
    # the span timeline still closed for the retired request
    retired = [e for e in spans.events()
               if e[0] == 0 and e[2] == "doomed"
               and e[3] == spans_mod.PHASE_RETIRED]
    assert retired and retired[0][4] == FINISH_TIMEOUT
    # the freed slot (and its stale cache columns) serve a fresh
    # request with full parity
    fresh = Request("fresh", [8, 9], max_tokens=6)
    sched.submit(fresh)
    sched.run_until_idle()
    assert sched.completions["fresh"].tokens == _solo_generate(
        cfg, params, mesh, [8, 9], 6, fresh.sampling)


def test_unseeded_requests_get_distinct_default_keys(devices8):
    """The shared-default-PRNG fix: two unseeded sampled requests with
    the SAME prompt and params draw DIFFERENT streams (every request
    used to inherit the zero key), the derivation is deterministic
    across engine rebuilds (a monotonic counter folded on device), and
    seeded paths are bit-stable against an explicit PRNGKey."""
    cfg = _cfg()
    params = gpt.init(cfg, jax.random.PRNGKey(0))
    mesh = mx.build_mesh(tp=1, devices=devices8[:1])
    mk = lambda: Engine(cfg, params, mesh,
                        EngineConfig(slots=2, max_prompt_len=8,
                                     max_seq_len=24))

    def run_pair(eng):
        streams = [[], []]
        for s in (0, 1):
            first, _, _ = eng.admit(s, [5, 6, 7], 8, temperature=1.0)
            streams[s].append(first)
        for _ in range(7):
            toks, _, _ = eng.step()
            for s in (0, 1):
                streams[s].append(int(toks[s, 0]))
        return streams

    a = run_pair(mk())
    assert a[0] != a[1], "unseeded requests shared a PRNG stream"
    assert run_pair(mk()) == a  # deterministic across rebuilds
    # a seeded admit is untouched by the counter machinery: same
    # stream whether it is the 1st or the 10th admission
    eng1, eng2 = mk(), mk()
    for i in range(5):  # burn counters on engine 2 only
        eng2.admit(0, [1 + i], 1)
    s1 = eng1.admit(0, [5, 6, 7], 4, temperature=0.9, seed=42)
    s2 = eng2.admit(0, [5, 6, 7], 4, temperature=0.9, seed=42)
    assert s1 == s2


def test_stop_matcher_hold_trim_flush():
    """StopMatcher unit semantics: the longest possible-stop-prefix
    tail is held back (never streamed), a completed stop is trimmed,
    overlapping candidates resolve to the earliest match, and flush()
    releases the held tail on non-stop finishes."""
    from apex_tpu.serving.request import StopMatcher

    def feed(stops, tokens):
        m = StopMatcher(stops)
        out, matched = [], False
        for t in tokens:
            flushed, matched = m.push(t, 0.0)
            out += [tok for tok, _ in flushed]
            if matched:
                break
        return out, matched, m

    # exact trim: stop [3, 4] inside the stream
    out, matched, _ = feed([[3, 4]], [1, 2, 3, 4, 5])
    assert (out, matched) == ([1, 2], True)
    # holdback: prefix [3] is held until disambiguated
    m = StopMatcher([[3, 4]])
    assert m.push(3, 0.0) == ([], False)      # possible stop start
    assert m.push(9, 0.0) == ([(3, 0.0), (9, 0.0)], False)  # broke
    # self-overlapping stop: [7, 7] in stream 5,7,7
    out, matched, _ = feed([[7, 7]], [5, 7, 7, 7])
    assert (out, matched) == ([5], True)
    # a stop crossing a would-be flush boundary: [1, 2, 3] with the
    # stream teasing 1,2 then completing
    out, matched, _ = feed([[1, 2, 3]], [9, 1, 2, 3])
    assert (out, matched) == ([9], True)
    # two stops completing on the same token: list order decides the
    # trim ([2, 5] first trims both tokens; [5] first would keep the 2)
    out, matched, _ = feed([[2, 5], [5]], [2, 5])
    assert matched and out == []
    out, matched, _ = feed([[5], [2, 5]], [2, 5])
    assert matched and out == [2]
    # flush releases held tokens (device finish without a match)
    m = StopMatcher([[1, 2, 3]])
    m.push(1, 0.1)
    m.push(2, 0.2)
    assert m.flush() == [(1, 0.1), (2, 0.2)]
    assert m.pending == []


def test_threefry_key_data_matches_prngkey():
    """The host-side numpy key packing admit_many uses for seeded
    requests is bit-identical to ``jax.random.PRNGKey`` — the
    non-negative int32 domain takes the numpy fast path (no device
    round trip); exotic seeds fall back to the real PRNGKey, so
    equality holds everywhere."""
    from apex_tpu.serving.engine import _threefry_key_data

    for seed in (0, 1, 42, 2**31 - 1, -1):
        np.testing.assert_array_equal(
            _threefry_key_data(seed),
            np.asarray(jax.random.PRNGKey(seed), np.uint32),
            err_msg=f"seed {seed}")


def test_warmup_compiles_everything_and_stays_flat(devices8):  # apex: noqa[TIER1-COST]: the warmup-compiles-everything contract IS the test subject (covers the idempotence re-call too)
    """``Engine.warmup()`` compiles every program — init/step/retire
    and ALL (bucket, k) admission variants — resets the slots, and a
    full varied serve cycle afterwards never adds a cache entry."""
    cfg = _cfg()
    params = gpt.init(cfg, jax.random.PRNGKey(0))
    mesh = mx.build_mesh(tp=1, devices=devices8[:1])
    eng = Engine(cfg, params, mesh,
                 EngineConfig(slots=2, max_prompt_len=10, max_seq_len=24,
                              decode_chunk=4))
    assert eng.prompt_buckets == (8, 10)
    assert eng.admit_batch_sizes == (1, 2)
    eng.warmup()
    sizes = eng.compiled_cache_sizes()
    assert set(sizes.values()) == {1}, sizes
    assert eng.warmup() is eng  # idempotent
    sched = Scheduler(eng, pipeline_depth=2)
    for r in _mixed_requests(6, 10, eos=13, seed0=840):
        sched.submit(r)
    sched.run_until_idle()
    assert len(sched.completions) == 6
    assert eng.compiled_cache_sizes() == sizes
