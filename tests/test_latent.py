"""The latent mixer (``GPTConfig.latent``: DeepSeek-V3.2's block) against
the plain reference ``benchmark/reference/deepseek_v32.py``, at a tiny
size that keeps every ratio of the published model: four groups of
experts of which two are kept, the experts held a strict subset of those
routed over, a top-k (8) far below the contexts used (24 to 64), a dense
layer before two routed ones, an untied head over a vocabulary slice.

Everything runs in float32, so the program and the reference differ by
rounding order only: ``TOL`` is 1e-4 on log-probabilities of size ~5
(measured 3e-6). The same tolerance must REJECT three wrong models —
that is what shows it discriminates: with small random weights the
softmax over the selection is near uniform and a wrong selection hides
inside any tolerance, so the query projections are drawn wide
(``attn_init_gain``) and one test pins the softmax's entropy.
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu import mesh as mx
from apex_tpu.models import gpt, latent
from apex_tpu.serving import Engine, EngineConfig, Request, Scheduler
from apex_tpu.transformer import moe
from benchmark.families import deepseek_v32 as fam
from benchmark.harness import recipe
from benchmark.reference import deepseek_v32 as ref

TOL = 1e-4
PAGE = 8


@functools.lru_cache(maxsize=None)
def model():
    """``(config file as rehearsed, program config, parameters,
    reference kwargs, reference parameters)`` — float32, wide queries."""
    file = dict(recipe.load_json("configs", "deepseek-v3.2-ep16.json"),
                n_embd=1)           # what harness/tiny.py stamps on it
    cfg = fam.program_config(file, {})
    cfg = dataclasses.replace(
        cfg, compute_dtype=jnp.float32, param_dtype=jnp.float32,
        init_std=0.08,
        latent=dataclasses.replace(cfg.latent, attn_init_gain=8.0))
    params = gpt.init(cfg, jax.random.PRNGKey(0))
    return (file, cfg, params, fam.reference_kwargs(file),
            fam.reference_params(params))


def tokens(n, seed=1):
    cfg = model()[1]
    return np.random.default_rng(seed).integers(0, cfg.vocab_size, n)


def reference_logprobs(seq, variant=None, round_to=None):
    _, _, _, kw, rp = model()
    return np.asarray(ref.token_logprobs(
        rp, jnp.asarray(seq), variant=variant, round_to=round_to, **kw))


def program_logprobs(seq, chunk, n_prefill):
    """Log-probabilities after positions ``n_prefill - 1 ..`` of ``seq``:
    prefill through the paged cache in chunks of ``chunk``, then decode a
    token at a time — one row, pages in an order that is not the
    identity."""
    _, cfg, params, _, _ = model()
    n_pages = -(-len(seq) // PAGE)
    cache = gpt.init_cache(cfg, params, 1 + n_pages, max_len=PAGE)
    table = jnp.asarray(np.random.default_rng(7).permutation(n_pages)[None]
                        + 1, jnp.int32)
    fill = jax.jit(lambda c, t, s, last: gpt.prefill_paged(
        cfg, params, c, t, s, last, table))
    step = jax.jit(lambda c, t, p: gpt.decode_step(cfg, params, c, t, p,
                                                   table))
    out = []
    for at in range(0, n_prefill, chunk):
        piece = np.zeros((1, chunk), np.int32)
        real = min(chunk, n_prefill - at)
        piece[0, :real] = seq[at:at + real]
        cache, lg = fill(cache, jnp.asarray(piece), jnp.asarray([at]),
                         jnp.asarray([real - 1]))
    out.append(jax.nn.log_softmax(lg, -1)[0])
    for t in range(n_prefill, len(seq)):
        lg, cache = step(cache, jnp.asarray(seq[t:t + 1]), jnp.asarray([t]))
        out.append(jax.nn.log_softmax(lg, -1)[0])
    return np.stack(out), cache


@pytest.mark.parametrize("chunk,n_prefill", [(8, 24), (16, 40), (40, 33)],
                         ids=["chunks_of_a_page", "two_pages_a_chunk",
                              "one_padded_chunk"])
def test_prefill_and_decode_match_the_reference(chunk, n_prefill):
    seq = tokens(48)
    got, cache = program_logprobs(seq, chunk, n_prefill)
    want = reference_logprobs(seq)[n_prefill - 1:]
    assert np.abs(got - want).max() < TOL
    # routed pairs: every real token, top-k experts, two routed layers
    routed, held, hit, offered = np.asarray(cache["counts"])
    assert routed == len(seq) * 4 * 2 and 0 < held < routed
    assert 0 < hit <= offered


@pytest.mark.parametrize("variant", ["recent", "no_relu", "no_renorm"])
def test_the_tolerance_rejects_wrong_models(variant):
    """Attending the most recent top-k instead of the indexer's choice,
    dropping the indexer's ReLU, leaving the routed weights
    unnormalised: each moves the log-probabilities by far more than the
    tolerance (measured 1.4 to 3.6)."""
    seq = tokens(48)
    got, _ = program_logprobs(seq, 16, 40)
    wrong = reference_logprobs(seq, variant=variant)[39:]
    assert np.abs(got - wrong).max() > 1000 * TOL


def test_lower_precision_is_rejected():
    """The reference with every matmul operand rounded to bfloat16 —
    the nearest precision below this test's float32 — fails the
    tolerance too."""
    seq = tokens(48)
    want = reference_logprobs(seq)
    low = reference_logprobs(seq, round_to=jnp.bfloat16)
    assert np.abs(low - want).max() > 10 * TOL


def layer_inputs(n=40):
    """A normed stream, positions and one routed layer's parameters."""
    _, cfg, params, kw, rp = model()
    h = jax.random.normal(jax.random.PRNGKey(3), (n, cfg.hidden_size))
    h = ref.rms_norm(h, jnp.ones((cfg.hidden_size,)), 1e-6)
    p = jax.tree.map(lambda x: x[0], params["moe_layers"])
    return cfg, h, jnp.arange(n, dtype=jnp.int32), p, rp["layers"][1], kw


def test_softmax_over_the_selection_is_peaked():
    """Entropy of MLA's softmax over the 8 selected keys, averaged over
    heads and the queries that have 8: well under ``ln 8 = 2.08`` (a
    uniform softmax would hide any selection)."""
    cfg, h, pos, p, _, _ = layer_inputs()
    lc = cfg.latent
    pr = latent.project(cfg, p, h[None], pos[None])
    cache = latent.init_cache(cfg, 1, 40)
    _, cache = latent.cache_attend(cfg, p, h[None], cache, 0, pos[None])
    rows = cache["ckv"][0, 0, 0, 0]                           # [40, row]
    s = jnp.einsum("thc,sc->ths", pr["q"][0], rows) * lc.softmax_scale
    assert rows.shape[-1] == lc.row_store == 128 and lc.row_dim == 24
    scores = latent.index_scores(lc, pr["q_i"], pr["w_i"], cache["ki"], 0,
                                 jnp.zeros((1, 1), jnp.int32))
    idx, valid = latent.select(scores, pos[None], lc.index_topk)
    picked = jnp.take_along_axis(s, idx[0][:, None, :], -1)[8:]
    pr_ = jax.nn.softmax(picked, -1)
    entropy = float(-(pr_ * jnp.log(pr_ + 1e-30)).sum(-1).mean())
    assert entropy < np.log(lc.index_topk) - 0.7, entropy


def test_indexer_scores_and_selection_match_the_reference():
    """``I(t, s)`` to float32 rounding, and the selected sets equal —
    or, where they differ, only by positions whose score lies within
    the score tolerance of the query's threshold."""
    cfg, h, pos, p, rp, kw = layer_inputs()
    lc, k = cfg.latent, kw["kw"]
    pr = latent.project(cfg, p, h[None], pos[None])
    cache = latent.init_cache(cfg, 1, 40)
    _, cache = latent.cache_attend(cfg, p, h[None], cache, 0, pos[None])
    got = np.asarray(latent.index_scores(
        lc, pr["q_i"], pr["w_i"], cache["ki"], 0,
        jnp.zeros((1, 1), jnp.int32))[0])
    # the reference's scores, by its own functions
    inv = ref.yarn_inv_freq(k["rope"], k["theta"], k["factor"],
                            k["original"], k["beta_fast"], k["beta_slow"])
    ang = pos.astype(jnp.float32)[:, None] * inv[None]
    a, ix = rp["attn"], rp["index"]
    c_q = ref.rms_norm(h @ a["q_a"], a["q_norm"], k["eps"])
    q_i = (c_q @ ix["wq_b"]).reshape(40, k["index_heads"], k["index_dim"])
    q_i = jnp.concatenate([ref.rope_half(q_i[..., :k["rope"]],
                                         ang[:, None]),
                           q_i[..., k["rope"]:]], -1)
    k_i = ref.layer_norm(h @ ix["wk"], ix["k_norm"]["scale"],
                         ix["k_norm"]["bias"], k["index_eps"])
    k_i = jnp.concatenate([ref.rope_half(k_i[:, :k["rope"]], ang),
                           k_i[:, k["rope"]:]], -1)
    w_i = (h @ ix["weights_proj"]) * (k["index_heads"] ** -0.5
                                      * k["index_dim"] ** -0.5)
    want = np.asarray(jnp.einsum(
        "qjs,qj->qs", jax.nn.relu(jnp.einsum("qjd,sd->qjs", q_i, k_i)),
        w_i))
    causal = np.tril(np.ones((40, 40), bool))
    score_tol = 1e-5 * np.abs(want).max()
    assert np.abs(got - want)[causal].max() < score_tol
    idx, valid = latent.select(jnp.asarray(got)[None], pos[None],
                               lc.index_topk)
    mask = np.asarray(ref.select_mask(
        jnp.where(jnp.asarray(causal), jnp.asarray(want), ref.NEG),
        lc.index_topk))
    for t in range(40):
        mine = set(np.asarray(idx[0, t])[np.asarray(valid[0, t])].tolist())
        theirs = set(np.flatnonzero(mask[t]).tolist())
        assert len(mine) == min(t + 1, lc.index_topk)
        if mine != theirs:
            kth = np.sort(want[t, :t + 1])[-lc.index_topk]
            for s in mine ^ theirs:
                assert abs(want[t, s] - kth) < score_tol


def test_attention_over_the_selection_matches_the_reference():
    cfg, h, pos, p, rp, kw = layer_inputs()
    cache = latent.init_cache(cfg, 1, 40)
    ctx, _ = latent.cache_attend(cfg, p, h[None], cache, 0, pos[None])
    got = np.asarray(ctx[0] @ p["attn"]["o"])
    want = np.asarray(ref.attention(h, rp, pos, kw["kw"]))
    assert np.abs(got - want).max() < 1e-4 * np.abs(want).max()


def test_routed_layer_matches_the_reference():
    cfg, h, _, p, rp, kw = layer_inputs()
    got, counts = moe.routed_ffn(cfg.latent.routed, p["moe"], h)
    want = ref.moe(h, rp["moe"], kw["held"], kw["kw"])
    assert np.abs(np.asarray(got) - np.asarray(want)).max() < 1e-5
    experts, _ = ref.route(h, rp["moe"]["router"], top_k=4, n_group=4,
                           topk_group=2, scale=2.5)
    assert int(counts[0]) == 40 * 4
    assert int(counts[1]) == int((np.asarray(experts) < 4).sum())


def test_the_shares_of_all_chips_add_up_to_the_uncut_layer():
    """Four chips hold experts 0-3, 4-7, 8-11, 12-15 of the 16 the
    router scores. Each computes its experts' part plus the shared
    expert; the parts, with the shared expert counted once, sum to what
    the reference gives holding all 16."""
    cfg, h, _, _, _, kw = layer_inputs()
    full = dataclasses.replace(cfg, latent=dataclasses.replace(
        cfg.latent, routed=dataclasses.replace(
            cfg.latent.routed, experts_held=(0, 16))))
    p = jax.tree.map(lambda x: x[0], gpt.init(
        full, jax.random.PRNGKey(5))["moe_layers"])["moe"]
    whole = ref.moe(h, p, (0, 16), kw["kw"])
    shared = ref.swiglu(h, p["shared"])
    parts = 0
    for first in range(0, 16, 4):
        share = {**p, "experts": jax.tree.map(
            lambda x: x[first:first + 4], p["experts"])}
        rcfg = dataclasses.replace(cfg.latent.routed,
                                   experts_held=(first, 4))
        parts = parts + moe.routed_ffn(rcfg, share, h)[0] - shared
    assert np.abs(np.asarray(parts + shared - whole)).max() < 1e-5


# -- through the engine ------------------------------------------------------

def engine(**over):
    _, cfg, params, _, _ = model()
    ecfg = EngineConfig(**{**dict(
        slots=4, max_prompt_len=56, max_seq_len=64, decode_chunk=2,
        page_size=PAGE, prompt_buckets=(4, 8), admit_batch_sizes=(1, 2),
        prefix_pool_slots=2, prefill_chunk=16), **over})
    mesh = mx.build_mesh(tp=1, devices=jax.devices()[:1])
    return Engine(cfg, params, mesh, ecfg)


def serve(eng, prompts, n_new=5):
    sched = Scheduler(eng)
    for i, p in enumerate(prompts):
        sched.submit(Request(f"r{i}", list(p), n_new))
    sched.run_until_idle()
    evs = [e for e in sched.pop_events() if e.token is not None]
    return [([e.token for e in evs if e.request_id == f"r{i}"],
             [e.logprob for e in evs if e.request_id == f"r{i}"])
            for i in range(len(prompts))], sched


def test_questions_over_shared_documents_through_the_engine():
    """Two documents registered as shared prefixes, five questions over
    them and one cold prompt, batched admission, decode in chunks: every
    streamed log-probability is the reference's, every token its
    argmax; a question over a shared document streams what the same
    prompt streams when it is admitted whole (no prefix registered) —
    the same tokens, log-probabilities to float32 rounding — and both
    planes of a shared page are read in place (pages shared, not
    copied)."""
    docs = [tokens(24, 11).tolist(), tokens(40, 12).tolist()]
    rng = np.random.default_rng(13)
    prompts = [docs[i % 2] + tokens(int(rng.integers(2, 9)), 20 + i).tolist()
               for i in range(5)] + [tokens(7, 30).tolist()]
    with engine() as eng:
        for d in docs:
            eng.register_prefix(d)
        assert eng.match_prefix(prompts[1]) == (1, 40)
        assert eng.match_prefix(docs[0]) is None     # nothing outgrows it
        shared, sched = serve(eng, prompts)
        assert sched.summary()["prefix_hits"] == 5
        assert eng.page_allocator.stats()["shares_total"] >= 5 * 3
        # no program compiled twice, whatever mix of documents, tails
        # and batch sizes came (programs compile at first use here: a
        # tier-1 test does not pay for warming those it never runs)
        assert set(eng.compiled_cache_sizes().values()) <= {0, 1}
    with engine(prefix_pool_slots=0) as eng:
        whole, _ = serve(eng, prompts)
    for p, (toks, lps), (toks_w, lps_w) in zip(prompts, shared, whole):
        want = reference_logprobs(np.asarray(p + toks))[len(p) - 1:-1]
        assert toks == want.argmax(-1).tolist() == toks_w
        assert np.abs(np.asarray(lps)
                      - want[np.arange(len(toks)), toks]).max() < TOL
        assert np.abs(np.asarray(lps) - np.asarray(lps_w)).max() < 1e-5


def test_a_fault_rebuild_fills_the_registered_prefixes_again():
    doc = tokens(24, 11).tolist()
    prompt = doc + tokens(5, 21).tolist()
    with engine() as eng:
        eng.register_prefix(doc)
        before, _ = serve(eng, [prompt])
        eng.rebuild_slots()
        after, _ = serve(eng, [prompt])
    assert before[0][0] == after[0][0]
    assert np.allclose(before[0][1], after[0][1], atol=1e-6)


@pytest.mark.parametrize("what,cfg_over,ecfg_over", [
    ("kv_cache_dtype", {"kv_cache_dtype": "int8"}, {}),
    ("kv_cache_dtype", {"kv_cache_dtype": "fp8"}, {}),
    ("adapter_slots", {}, {"adapter_slots": 2}),
    ("speculation", {}, {"spec_k": 2}),
    ("host_swap", {}, {"host_swap": True}),
    ("page_size == 0", {}, {"page_size": 0}),
    ("prefill_chunk", {}, {"prefill_chunk": 4}),
])
def test_the_engine_refuses_what_the_mixer_does_not_take(what, cfg_over,
                                                         ecfg_over):
    _, cfg, params, _, _ = model()
    ecfg = EngineConfig(**{**dict(
        slots=2, max_prompt_len=24, max_seq_len=32, page_size=PAGE,
        prompt_buckets=(8,)), **ecfg_over})
    mesh = mx.build_mesh(tp=1, devices=jax.devices()[:1])
    with pytest.raises(ValueError, match=what):
        Engine(dataclasses.replace(cfg, **cfg_over), params, mesh, ecfg)


def test_the_capacity_layer_keeps_its_refusals():
    """Prefix pool and chunked prefill stay refused with the
    capacity-factor expert layer (its capacity depends on the batch)."""
    from apex_tpu.transformer.testing import standalone_gpt_config

    cfg = standalone_gpt_config(vocab_size=96, seq_len=64, num_experts=4)
    params = gpt.init(cfg, jax.random.PRNGKey(0))
    mesh = mx.build_mesh(tp=1, devices=jax.devices()[:1])
    for over in ({"prefix_pool_slots": 1}, {"prefill_chunk": 8}):
        with pytest.raises(ValueError, match="num_experts"):
            Engine(cfg, params, mesh, EngineConfig(
                slots=2, max_prompt_len=16, max_seq_len=32,
                prompt_buckets=(8, 16), **over))


def test_training_entry_points_say_what_is_missing():
    _, cfg, params, _, _ = model()
    with pytest.raises(NotImplementedError, match="ROADMAP"):
        gpt.loss(cfg, params, jnp.zeros((1, 8), jnp.int32),
                 jnp.zeros((1, 8), jnp.int32))


@pytest.mark.parametrize("paged", [False, True])
def test_prefill_paged_serves_the_fused_qkv_mixer_too(paged):
    """``gpt.prefill_paged`` is the layer scan of ``decode_step`` over
    T columns for either mixer: a GPT-2 block's right-padded prompts
    taken through the cache in two chunks (the second over what the
    first wrote, ``start`` as data) give the logits of the cold
    ``prefill_many`` and leave a cache that decodes to the same next
    logits — float32, so to rounding order."""
    from jax.sharding import PartitionSpec as P

    from apex_tpu.transformer.testing import standalone_gpt_config

    cfg = dataclasses.replace(
        standalone_gpt_config(vocab_size=96, seq_len=32),
        compute_dtype=jnp.float32, param_dtype=jnp.float32)
    params = gpt.init(cfg, jax.random.PRNGKey(3))
    rng = np.random.default_rng(5)
    lens, width, chunk = np.asarray([13, 9]), 16, 8
    prompts = jnp.asarray(rng.integers(0, 96, (2, width)), jnp.int32)
    nxt = jnp.asarray(rng.integers(0, 96, (2,)), jnp.int32)
    table = jnp.asarray([[5, 2, 7, 1], [3, 8, 4, 6]],
                        jnp.int32) if paged else None

    def run(p, prompts, nxt):
        cold, want = gpt.prefill_many(cfg, p, prompts,
                                      jnp.asarray(lens - 1), max_len=32)
        cache = gpt.init_cache(cfg, p, 9, max_len=8) if paged \
            else gpt.init_cache(cfg, p, 2, max_len=32)
        for at in range(0, width, chunk):
            cache, got = gpt.prefill_paged(
                cfg, p, cache, prompts[:, at:at + chunk],
                jnp.full((2,), at, jnp.int32),
                jnp.asarray(np.clip(lens - 1 - at, 0, chunk - 1)), table)
            # a row's logits come from the chunk its last token lies
            # in (in the other chunk they are a pad column's)
            keep = jnp.asarray((lens - 1) // chunk == at // chunk)
            out = got if at == 0 else jnp.where(keep[:, None], got, out)
        a, _ = gpt.decode_step(cfg, p, cold, nxt, jnp.asarray(lens))
        b, _ = gpt.decode_step(cfg, p, cache, nxt, jnp.asarray(lens),
                               table)
        return want, out, a, b

    mesh = mx.build_mesh(tp=1, devices=jax.devices()[:1])
    want, out, a, b = jax.jit(jax.shard_map(
        run, mesh=mesh, in_specs=(gpt.param_specs(cfg), P(), P()),
        out_specs=(P(),) * 4, check_vma=False))(params, prompts, nxt)
    np.testing.assert_allclose(out, want, atol=2e-5)
    np.testing.assert_allclose(b, a, atol=2e-5)
