"""The serving engine holds its weights in the compute dtype.

Every cached forward casts the layer stacks' matmul weights and biases
to ``cfg.compute_dtype``; the engine, whose weights never change, does
it once when it is built (``gpt.cast_weights``' rule) and hands every
program the cast tree. The oracle: the same requests give the same
tokens and the same float32 logprob bits as an engine that casts in
every program, and the same tokens as a solo ``gpt.generate`` over the
caller's fp32 tree.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from apex_tpu import mesh as mx
from apex_tpu.models import gpt
from apex_tpu.serving import Request, SamplingParams, engine as engine_mod
from apex_tpu.serving.engine import Engine, EngineConfig
from apex_tpu.serving.scheduler import Scheduler
from apex_tpu.transformer.testing import standalone_gpt_config

BF16 = jnp.bfloat16


def _cfg(**overrides):
    return standalone_gpt_config(**{**dict(
        vocab_size=96, seq_len=64, compute_dtype=BF16), **overrides})


def _requests():
    """Greedy and sampled requests of several prompt lengths."""
    rng = np.random.default_rng(7)
    reqs = []
    for i in range(5):
        sp = (SamplingParams(temperature=0.9, top_k=(0, 5)[i % 2], seed=11 + i)
              if i % 2 else SamplingParams())
        reqs.append(Request(f"r{i}", [int(t) for t in rng.integers(
            0, 96, 2 + i)], max_tokens=4 + i, sampling=sp))
    return reqs


def _serve(eng):
    sched = Scheduler(eng)
    for r in _requests():
        sched.submit(Request(r.request_id, r.prompt, r.max_tokens,
                             sampling=r.sampling))
    sched.run_until_idle()
    return {rid: (c.tokens, np.asarray(c.logprobs, np.float32).view(
        np.uint32).tolist()) for rid, c in sched.completions.items()}


def _solo(cfg, params, mesh, r):
    sp = r.sampling
    key = jax.random.PRNGKey(sp.seed) if sp.temperature > 0 else None
    out = jax.jit(jax.shard_map(
        lambda p, t: gpt.generate(
            cfg, p, t, r.max_tokens, temperature=sp.temperature,
            top_k=sp.top_k, top_p=sp.top_p, key=key, pad_token_id=0),
        mesh=mesh, in_specs=(gpt.param_specs(cfg), P(None, None)),
        out_specs=P(None, None), check_vma=False))(
            params, jnp.asarray([r.prompt], jnp.int32))
    return [int(t) for t in np.asarray(out)[0]]


@pytest.mark.parametrize("layout", ["contiguous", "paged", "tp2"])
def test_engine_streams_match_casting_in_every_program(devices8, layout,
                                                       monkeypatch):
    """An engine built from the fp32 tree, one built from a tree the
    caller cast beforehand (``gpt.cast_weights``) and one whose held
    cast is switched off, so that every program casts the fp32 tree:
    identical tokens and float32 logprob bits. Each
    request's tokens are its solo ``gpt.generate`` run's over the fp32
    tree, and the tp=2 engine's streams are the tp=1 engine's tokens."""
    cfg = _cfg()
    params = gpt.init(cfg, jax.random.PRNGKey(3))
    ecfg = EngineConfig(slots=2, max_prompt_len=8, max_seq_len=24,
                        decode_chunk=2, prompt_buckets=(4, 8),
                        admit_batch_sizes=(1, 2),
                        page_size=8 if layout == "paged" else 0)
    tp = 2 if layout == "tp2" else 1
    mesh = mx.build_mesh(tp=tp, devices=devices8[:tp])
    pre_cast = jax.jit(lambda p: gpt.cast_weights(cfg, p))(params)
    held = _serve(Engine(cfg, params, mesh, ecfg))
    assert _serve(Engine(cfg, pre_cast, mesh, ecfg)) == held
    with monkeypatch.context() as m:
        m.setattr(engine_mod, "_held_weights", lambda cfg, p, mesh: p)
        assert _serve(Engine(cfg, params, mesh, ecfg)) == held
    one = mx.build_mesh(tp=1, devices=devices8[:1])
    for r in _requests():
        assert held[r.request_id][0] == _solo(cfg, params, one, r), (
            r.request_id)
    if tp > 1:
        ref = _serve(Engine(cfg, params, one, ecfg))
        assert {k: v[0] for k, v in held.items()} == {
            k: v[0] for k, v in ref.items()}


class _Held(Engine):
    """An engine built and never run: ``init`` is not called, so its
    weights may be shapes."""

    def _build(self):
        super()._build()
        self._init = lambda params: (None, None)


def _engine(cfg, params, mesh, **ecfg):
    return _Held(cfg, params, mesh, EngineConfig(**{**dict(
        slots=2, max_prompt_len=8, max_seq_len=16), **ecfg}))


def _leaves(tree):
    return jax.tree_util.tree_flatten_with_path(tree)[0]


def _name(path):
    return "/".join(str(getattr(k, "key", k)) for k in path)


def _held_dtypes(devices8):
    """Matmul weights and biases held in bf16; the LayerNorm affine,
    the word and position tables (and, with experts, the router) the
    caller's own arrays, in fp32."""
    for cfg in (_cfg(), _cfg(num_experts=4)):
        params = gpt.init(cfg, jax.random.PRNGKey(0))
        mesh = mx.build_mesh(tp=1, devices=devices8[:1])
        held = _engine(cfg, params, mesh)._params
        kept = ("ln1", "ln2", "final_ln", "embedding", "router")
        for (path, x), (_, mine) in zip(_leaves(params), _leaves(held)):
            name = _name(path)
            if any(k in name for k in kept):
                assert mine is x, name
            else:
                assert ("attn" in name or "mlp" in name
                        or "experts" in name), name
                assert mine.dtype == BF16, name
                assert np.array_equal(np.asarray(mine),
                                      np.asarray(x.astype(BF16))), name


def _caller_unchanged(devices8):
    """The caller's tree keeps its fp32 arrays and their values."""
    cfg = _cfg()
    params = gpt.init(cfg, jax.random.PRNGKey(1))
    before = [(x, np.asarray(x)) for x in jax.tree.leaves(params)]
    mesh = mx.build_mesh(tp=2, devices=devices8[:2])
    _engine(cfg, params, mesh)
    for (x, saved), now in zip(before, jax.tree.leaves(params)):
        assert now is x and now.dtype == jnp.float32
        assert np.array_equal(np.asarray(now), saved)


def _already_compute_dtype(devices8):
    """A latent model whose parameters are bf16 already: the engine
    holds the caller's arrays themselves, none copied."""
    from benchmark.families import deepseek_v32 as fam
    from benchmark.harness import recipe

    file = dict(recipe.load_json("configs", "deepseek-v3.2-ep16.json"),
                n_embd=1)
    cfg = dataclasses.replace(fam.program_config(file, {}),
                              compute_dtype=BF16, param_dtype=BF16)
    params = gpt.init(cfg, jax.random.PRNGKey(0))
    mesh = mx.build_mesh(tp=1, devices=devices8[:1])
    held = _engine(cfg, params, mesh, page_size=8)._params
    pairs = list(zip(jax.tree.leaves(params), jax.tree.leaves(held)))
    assert pairs and all(mine is x for x, mine in pairs)


def _abstract(devices8):
    """Shapes in, shapes out: every leaf keeps its sharding, the cast
    ones in bf16."""
    cfg = _cfg()
    mesh = mx.build_mesh(tp=2, devices=devices8[:2])
    shapes = jax.tree.map(
        lambda s, sp: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=NamedSharding(mesh, sp)),
        jax.eval_shape(lambda: gpt.init(cfg, jax.random.PRNGKey(0))),
        gpt.param_specs(cfg))
    held = _engine(cfg, shapes, mesh)._params
    want = jax.eval_shape(lambda p: gpt.cast_weights(cfg, p), shapes)
    for (path, s), mine, w in zip(_leaves(shapes), jax.tree.leaves(held),
                                  jax.tree.leaves(want)):
        assert isinstance(mine, jax.ShapeDtypeStruct), _name(path)
        assert (mine.shape, mine.dtype) == (s.shape, w.dtype), _name(path)
        assert mine.sharding == s.sharding, _name(path)
    assert held["layers"]["mlp"]["fc1"]["kernel"].dtype == BF16
    emb, mine = shapes["embedding"], held["embedding"]
    assert mine["word"]["table"] is emb["word"]["table"]
    assert mine["position"] is emb["position"]


@pytest.mark.parametrize("check", [_held_dtypes, _caller_unchanged,
                                   _already_compute_dtype, _abstract],
                         ids=lambda f: f.__name__.strip("_"))
def test_engine_holds_compute_dtype_weights(devices8, check):
    check(devices8)
