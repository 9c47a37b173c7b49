"""Flagship GPT model: TP/SP parity + end-to-end train step.

Oracle pattern (SURVEY.md §4): the sharded model must match the unsharded
(tp=1) reference bit-for-tolerance at fp32 — the analogue of apex's
tests/L0/run_transformer/test_layers.py comparing parallel layers against
the monolithic nn.Linear (U).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from apex_tpu import mesh as mx
from apex_tpu.amp import ScalerConfig
from apex_tpu.models import gpt, training
from apex_tpu.optimizers import fused_adam, fused_sgd

CFG = dict(vocab_size=96, hidden_size=64, num_layers=2, num_heads=4,
           seq_len=32, compute_dtype=jnp.float32)


def _data(key, batch=8, seq=32, vocab=96):
    tok = jax.random.randint(key, (batch, seq), 0, vocab)
    return tok, jnp.roll(tok, -1, axis=1)


def _run(devices, tp, sp, steps=2, remat=True, opt=None, **cfg_kw):
    # parity runs use SGD: it is linear in the gradient, so cross-mesh
    # reduction-order fp noise stays O(eps) instead of being amplified by
    # Adam's zero-moment first step (~lr * sign(g))
    cfg = gpt.GPTConfig(sequence_parallel=sp, remat=remat, **{**CFG, **cfg_kw})
    mesh = mx.build_mesh(tp=tp, devices=devices)
    init_fn, step_fn = training.make_train_step(
        cfg, mesh, opt or fused_sgd(0.1), ScalerConfig(enabled=False))
    state = init_fn(jax.random.PRNGKey(0))
    tok, tgt = _data(jax.random.PRNGKey(1))
    losses = []
    for _ in range(steps):
        state, m = step_fn(state, tok, tgt)
        losses.append(float(m["loss"]))
    return jax.device_get(state.params), losses


@pytest.mark.parametrize("sp", [False, True])
def test_tp_matches_unsharded_reference(devices8, sp):
    ref_params, ref_losses = _run(devices8, tp=1, sp=False)
    tp_params, tp_losses = _run(devices8, tp=4, sp=sp)
    np.testing.assert_allclose(ref_losses, tp_losses, rtol=2e-4)
    flat_r, _ = jax.tree.flatten(ref_params)
    flat_t, _ = jax.tree.flatten(tp_params)
    for r, t in zip(flat_r, flat_t):
        np.testing.assert_allclose(np.asarray(r), np.asarray(t),
                                   rtol=5e-4, atol=5e-5)


def test_loss_decreases(devices8):
    _, losses = _run(devices8, tp=2, sp=True, steps=6, opt=fused_adam(1e-2))
    assert losses[-1] < losses[0]


def test_fp16_dynamic_scaling_path(devices8):
    """fp16 policy: dynamic scaler engages and steps stay finite."""
    cfg = gpt.GPTConfig(sequence_parallel=False, remat=False,
                        **{**CFG, "compute_dtype": jnp.float16})
    mesh = mx.build_mesh(tp=2, devices=devices8)
    init_fn, step_fn = training.make_train_step(
        cfg, mesh, fused_adam(1e-3), ScalerConfig(init_scale=2.0 ** 8))
    state = init_fn(jax.random.PRNGKey(0))
    tok, tgt = _data(jax.random.PRNGKey(1))
    for _ in range(3):
        state, m = step_fn(state, tok, tgt)
        assert np.isfinite(float(m["loss"]))
    assert float(state.scaler.loss_scale) == 2.0 ** 8  # no overflow backoff


def test_remat_matches_no_remat(devices8):
    p1, l1 = _run(devices8, tp=2, sp=False, remat=True)
    p2, l2 = _run(devices8, tp=2, sp=False, remat=False)
    np.testing.assert_allclose(l1, l2, rtol=1e-5)


def test_param_count():
    cfg = gpt.GPTConfig()  # GPT-2 355M-class
    n = cfg.param_count()
    assert 3.0e8 < n < 4.2e8


def test_perf_knobs_match_defaults(devices8):
    """The measured-fast configuration (XLA-fused LN, unrolled layer scan,
    compute-dtype scores) is numerically the same model as the defaults —
    at fp32 compute the score-dtype knob only moves where the softmax
    scale is applied and LN/unroll only reorder fp ops."""
    _, ref = _run(devices8, tp=2, sp=False, steps=1)
    _, fast = _run(devices8, tp=2, sp=False, steps=1, ln_impl="xla",
                   scan_unroll=True, attn_score_dtype="compute")
    np.testing.assert_allclose(ref, fast, rtol=2e-5)


@pytest.mark.parametrize(
    "policy", ["dots", "qkv_fc1", "fc1", "qkv_fc1_attn", "fc1_attn"])
def test_remat_policies_match_full_remat(devices8, policy):
    """Selective-recompute policies change only what is saved, never the
    math."""
    extra = {"attn_impl": "flash"} if policy.endswith("_attn") else {}
    _, ref = _run(devices8, tp=2, sp=False, steps=1, **extra)
    _, sel = _run(devices8, tp=2, sp=False, steps=1, remat_policy=policy,
                  **extra)
    np.testing.assert_allclose(ref, sel, rtol=1e-5)


def test_packed_attn_layout_matches_bhsd(devices8):
    """The lane-packed [b, s, hidden] flash path (hidden a multiple of
    128 → eligible, the production-shape route) is the same model as the
    head-major layout, including under pinned-residual remat — exercises
    the packed custom_vjp and its packed-shape flash_out/flash_lse
    residuals inside the scanned layer stack on the CPU backbone."""
    kw = dict(hidden_size=128, num_heads=2, attn_impl="flash",
              remat_policy="qkv_fc1_attn")
    _, packed = _run(devices8, tp=1, sp=False, steps=2, **kw)
    _, bhsd = _run(devices8, tp=1, sp=False, steps=2,
                   attn_layout="bhsd", **kw)
    np.testing.assert_allclose(packed, bhsd, rtol=1e-5)
    _, full = _run(devices8, tp=1, sp=False, steps=2, hidden_size=128,
                   num_heads=2, attn_impl="flash")
    np.testing.assert_allclose(packed, full, rtol=1e-5)


def test_attn_pinning_requires_flash(devices8):
    with pytest.raises(ValueError, match="flash"):
        _run(devices8, tp=2, sp=False, steps=1, remat_policy="fc1_attn")


def test_attn_residual_pinning_with_flash(devices8):
    """qkv_fc1_attn + the Pallas flash path: pinned (out, lse) kernel
    residuals must reproduce full-remat numerics exactly."""
    _, ref = _run(devices8, tp=2, sp=False, steps=1, attn_impl="flash")
    _, sel = _run(devices8, tp=2, sp=False, steps=1, attn_impl="flash",
                  remat_policy="qkv_fc1_attn")
    np.testing.assert_allclose(ref, sel, rtol=1e-5)


def test_ce_impl_fused_matches_xla(devices8):
    """ce_impl="fused" (Pallas xentropy per chunk, tp=1) equals the
    vocab-parallel XLA CE."""
    _, ref = _run(devices8, tp=1, sp=False, steps=1, ce_chunk=16)
    _, fus = _run(devices8, tp=1, sp=False, steps=1, ce_chunk=16,
                  ce_impl="fused")
    np.testing.assert_allclose(ref, fus, rtol=1e-5)


def test_ce_impl_validated(devices8):
    with pytest.raises(ValueError, match="ce_impl"):
        _run(devices8, tp=1, sp=False, steps=1, ce_impl="nope")


def test_ce_impl_fused_unchunked_matches_xla(devices8):
    _, ref = _run(devices8, tp=1, sp=False, steps=1)
    _, fus = _run(devices8, tp=1, sp=False, steps=1, ce_impl="fused")
    np.testing.assert_allclose(ref, fus, rtol=1e-5)


def test_ce_impl_fused_rejects_sharded_vocab(devices8):
    with pytest.raises(ValueError, match="unsharded"):
        _run(devices8, tp=2, sp=False, steps=1, ce_impl="fused")


# --- clip_grad_norm: global-norm clipping inside the fused step ---------

def _run_clip(devices, tp, clip, *, pp=1, n_micro=1, sp=False, steps=2):
    cfg = gpt.GPTConfig(sequence_parallel=sp, remat=True, **CFG)
    mesh = mx.build_mesh(tp=tp, pp=pp, devices=devices)
    init_fn, step_fn = training.make_train_step(
        cfg, mesh, fused_sgd(0.1), ScalerConfig(enabled=False),
        clip_grad_norm=clip, n_micro=n_micro,
    )
    state = init_fn(jax.random.PRNGKey(0))
    tok, tgt = _data(jax.random.PRNGKey(1))
    losses, norms = [], []
    for _ in range(steps):
        state, m = step_fn(state, tok, tgt)
        losses.append(float(m["loss"]))
        norms.append(float(m["grad_norm"]) if "grad_norm" in m
                     else float("nan"))
    return jax.device_get(state.params), losses, norms


def test_clip_grad_norm_sharded_matches_unsharded(devices8):
    """The model-parallel norm (tp-sharded leaves psum'd, replicated
    leaves counted once) must equal the tp=1 norm, so a *biting* clip
    produces the same trajectory on both meshes."""
    # clip=1e6 never bites (coeff clamps at 1): unclipped trajectory,
    # but the pre-clip norm metric is reported
    _, ref_losses, ref_norms = _run_clip(devices8, tp=1, clip=1e6)
    clip = ref_norms[0] / 2  # bites on every step
    _, l1, n1 = _run_clip(devices8, tp=1, clip=clip)
    _, l4, n4 = _run_clip(devices8, tp=4, clip=clip, sp=True)
    np.testing.assert_allclose(n1, n4, rtol=2e-4)
    np.testing.assert_allclose(l1, l4, rtol=2e-4)
    # clipping changed the trajectory (step 2 sees different params)...
    assert abs(l1[1] - ref_losses[1]) > 1e-6
    # ...but the reported norm is pre-clip, so step 1's matches unclipped
    np.testing.assert_allclose(n1[0], ref_norms[0], rtol=1e-5)


def test_clip_grad_norm_loose_is_identity(devices8):
    ref_params, ref_losses, _ = _run_clip(devices8, tp=2, clip=None)
    par, losses, norms = _run_clip(devices8, tp=2, clip=1e6)
    np.testing.assert_allclose(ref_losses, losses, rtol=1e-6)
    for r, t in zip(jax.tree.leaves(ref_params), jax.tree.leaves(par)):
        np.testing.assert_allclose(np.asarray(r), np.asarray(t), rtol=1e-6)
    assert norms[0] > 0


def test_clip_grad_norm_pipelined(devices8):
    """pp-sharded leaves contribute once per stage: the pp=2 norm equals
    the flat-mesh norm."""
    _, _, ref_norms = _run_clip(devices8, tp=1, clip=1e6)
    _, _, pp_norms = _run_clip(devices8, tp=1, pp=2, n_micro=2, clip=1e6)
    np.testing.assert_allclose(ref_norms[0], pp_norms[0], rtol=2e-4)


def test_clip_grad_norm_overflow_still_skips_step(devices8):
    """An overflowing fp16 step must skip the update even though the
    clip coefficient computed from the nan norm is nan — apply_if_finite
    guards the params, and the next step recovers at the backed-off
    scale."""
    cfg = gpt.GPTConfig(remat=True, **{**CFG,
                                       "compute_dtype": jnp.float16})
    mesh = mx.build_mesh(tp=2, devices=devices8)
    # fp16 max ≈ 65504: an init_scale beyond 2^24 overflows the scaled
    # loss itself, guaranteeing non-finite grads on step one
    init_fn, step_fn = training.make_train_step(
        cfg, mesh, fused_sgd(0.1),
        ScalerConfig(enabled=True, init_scale=2.0 ** 30,
                     max_scale=2.0 ** 30),
        clip_grad_norm=1.0)
    state = init_fn(jax.random.PRNGKey(0))
    params_before = jax.device_get(state.params)
    tok, tgt = _data(jax.random.PRNGKey(1))
    state, m = step_fn(state, tok, tgt)
    assert int(m["grads_finite"]) == 0
    assert float(m["loss_scale"]) == 2.0 ** 29  # backed off
    for r, t in zip(jax.tree.leaves(params_before),
                    jax.tree.leaves(jax.device_get(state.params))):
        np.testing.assert_array_equal(np.asarray(r), np.asarray(t))
    # scale keeps halving until a clean step lands and trains normally
    # (the recovery scale is layout/reduction-order sensitive within a
    # factor of ~2 — the window covers the 2^17 the batch-major layout
    # lands on)
    for _ in range(14):
        state, m = step_fn(state, tok, tgt)
        if int(m["grads_finite"]):
            break
    assert int(m["grads_finite"]) == 1
    assert np.isfinite(float(m["grad_norm"]))


def test_clip_grad_norm_rejects_zero_optimizer(devices8):
    from apex_tpu.optimizers import distributed_fused_adam
    cfg = gpt.GPTConfig(remat=True, **CFG)
    mesh = mx.build_mesh(tp=1, devices=devices8)
    with pytest.raises(ValueError, match="ZeRO"):
        training.make_train_step(
            cfg, mesh, distributed_fused_adam(1e-3),
            ScalerConfig(enabled=False), clip_grad_norm=1.0)


# -- one layer, one cache core ---------------------------------------------
#: entry point -> does its trace go through the cache-attention core
_ENTRY_POINTS = {
    "loss": False, "pipeline_loss": False, "bert": False,
    "prefill_many": False, "prefill_extend": False,
    "decode_step": True, "decode_step_paged": True,
    "decode_step_lora": True, "decode_verify": True,
    "decode_verify_paged": True,
}


def _entry_point(entry, devices):
    """``(fn, mesh, in_specs, args)``: entry point ``entry`` at the tiny
    preset as a function for ``shard_map``, on abstract arguments."""
    from apex_tpu.models import bert

    cfg = gpt.GPTConfig(remat=True, **CFG)
    mesh = mx.build_mesh(tp=1, devices=devices[:1])
    params = jax.eval_shape(lambda: gpt.init(cfg, jax.random.PRNGKey(0)))
    pspecs = gpt.param_specs(cfg)
    b, s, page, t = 2, 32, 8, 3
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)
    tok, row = i32(b, 16), i32(b)
    if entry == "loss":
        return (lambda p, x: gpt.loss(cfg, p, x, x), mesh,
                (pspecs, P()), (params, tok))
    if entry == "pipeline_loss":
        return (lambda p, x: gpt.pipeline_loss(cfg, p, x, x, n_micro=2),
                mx.build_mesh(tp=1, pp=2, dp=1, devices=devices[:2]),
                (gpt.param_specs(cfg, pipeline=True), P()), (params, tok))
    if entry == "bert":
        bcfg = bert.BertConfig(**CFG)
        return (lambda p, x: bert.mlm_loss(bcfg, p, x, x, x), mesh,
                (bert.param_specs(bcfg), P()),
                (jax.eval_shape(
                    lambda: bert.init(bcfg, jax.random.PRNGKey(0))), tok))
    if entry == "prefill_many":
        return (lambda p, x, last: gpt.prefill_many(
            cfg, p, x, last, max_len=s), mesh, (pspecs, P(), P()),
            (params, tok, row))
    if entry == "prefill_extend":
        prefix = jax.ShapeDtypeStruct(
            (cfg.num_layers, 2, b, cfg.num_heads, 8, cfg.head_dim),
            cfg.compute_dtype)
        return (lambda p, kv, x, last: gpt.prefill_extend(
            cfg, p, kv, x, last, prefix_len=8), mesh,
            (pspecs, P(), P(), P()), (params, prefix, i32(b, 8), row))
    paged = entry.endswith("_paged")
    cache = jax.eval_shape(
        lambda p: gpt.init_cache(cfg, p, b * s // page, page) if paged
        else gpt.init_cache(cfg, p, b, s), params)
    table = i32(b, s // page) if paged else None
    if entry.startswith("decode_verify"):
        return (lambda p, c, x, pos, tb: gpt.decode_verify(
            cfg, p, c, x, pos, tb), mesh, (pspecs, P(), P(), P(), P()),
            (params, cache, i32(b, t), row, table))
    if entry == "decode_step_lora":
        pool = jax.eval_shape(
            lambda p: gpt.init_lora_pool(cfg, p, 3, 2), params)
        return (lambda p, c, x, pos, pl, ids: gpt.decode_step(
            cfg, p, c, x, pos, lora=(pl, ids, 0.5)), mesh,
            (pspecs, P(), P(), P(), P(), P()),
            (params, cache, row, row, pool, row))
    return (lambda p, c, x, pos, tb: gpt.decode_step(
        cfg, p, c, x, pos, tb), mesh, (pspecs, P(), P(), P(), P()),
        (params, cache, row, row, table))


@pytest.mark.parametrize("entry", sorted(_ENTRY_POINTS))
def test_every_entry_point_runs_the_one_layer(devices8, monkeypatch, entry):
    """``gpt._layer`` is THE transformer layer and ``gpt._cache_attend``
    THE cache-attention core: every entry point's trace passes through
    the first (a scan traces its body once, remat may trace it again),
    and every cached forward — one column or T, contiguous or paged —
    through the second. A fifth copy of the layer would leave its entry
    point's count at zero."""
    calls = {"_layer": 0, "_cache_attend": 0}

    def counted(name):
        real = getattr(gpt, name)

        def wrapper(*args, **kwargs):
            calls[name] += 1
            return real(*args, **kwargs)

        monkeypatch.setattr(gpt, name, wrapper)

    counted("_layer")
    counted("_cache_attend")
    fn, mesh, in_specs, args = _entry_point(entry, devices8)
    jax.eval_shape(jax.shard_map(
        fn, mesh=mesh, in_specs=in_specs, out_specs=P(),
        check_vma=False), *args)
    assert calls["_layer"] >= 1
    assert (calls["_cache_attend"] >= 1) == _ENTRY_POINTS[entry]
