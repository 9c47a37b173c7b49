"""Transducer loss, checkpoint round-trip, RNN cells, weight norm.

Oracles: brute-force numpy DP for RNN-T; save/restore identity for
checkpoints; algebraic identities for weight norm.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from apex_tpu.checkpoint import load_checkpoint, save_checkpoint
from apex_tpu.contrib import transducer_joint, transducer_loss
from apex_tpu.reparameterization import (
    apply_weight_norm,
    remove_weight_norm,
    weight_norm_apply,
    weight_norm_init,
)
from apex_tpu.rnn import LSTM, gru_cell


def _ref_rnnt_loss(lp, tgt, T, U, blank=0):
    alpha = np.full((T, U + 1), -np.inf)
    alpha[0, 0] = 0.0
    for t in range(T):
        for u in range(U + 1):
            if t == 0 and u == 0:
                continue
            cands = []
            if t > 0:
                cands.append(alpha[t - 1, u] + lp[t - 1, u, blank])
            if u > 0:
                cands.append(alpha[t, u - 1] + lp[t, u - 1, tgt[u - 1]])
            alpha[t, u] = np.logaddexp.reduce(cands)
    return -(alpha[T - 1, U] + lp[T - 1, U, blank])


def test_transducer_loss_matches_dp_reference():
    rng = np.random.RandomState(0)
    B, T, U, V = 3, 5, 4, 7
    logits = rng.randn(B, T, U + 1, V).astype(np.float32)
    lp = jax.nn.log_softmax(jnp.asarray(logits), axis=-1)
    tgt = rng.randint(1, V, size=(B, U))
    f_len = np.array([5, 4, 3])
    y_len = np.array([4, 2, 3])
    out = transducer_loss(lp, jnp.asarray(tgt), jnp.asarray(f_len),
                          jnp.asarray(y_len))
    for i in range(B):
        ref = _ref_rnnt_loss(np.asarray(lp)[i], tgt[i], f_len[i], y_len[i])
        np.testing.assert_allclose(float(out[i]), ref, rtol=1e-4)


def test_transducer_loss_grads_finite():
    lp = jax.nn.log_softmax(
        jax.random.normal(jax.random.PRNGKey(0), (2, 4, 4, 5)), axis=-1)
    tgt = jnp.ones((2, 3), jnp.int32)
    g = jax.grad(lambda x: jnp.sum(transducer_loss(x, tgt)))(lp)
    assert np.all(np.isfinite(np.asarray(g)))


def test_transducer_joint():
    f = jnp.ones((2, 3, 4))
    g = 2.0 * jnp.ones((2, 5, 4))
    out = transducer_joint(f, g)
    assert out.shape == (2, 3, 5, 4)
    np.testing.assert_allclose(np.asarray(out), 3.0)


def test_checkpoint_roundtrip(tmp_path):
    state = {
        "params": {"w": jnp.arange(6.0).reshape(2, 3)},
        "step": jnp.int32(7),
        "nested": [jnp.ones((4,), jnp.bfloat16)],
    }
    p = save_checkpoint(str(tmp_path / "ckpt"), state, force_npz=True)
    like = jax.tree.map(jnp.zeros_like, state)
    back = load_checkpoint(p, like, force_npz=True)
    for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(back)):
        assert a.dtype == b.dtype
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32))


def test_lstm_runs_and_matches_manual_step():
    m = LSTM(3, 4)
    p = m.init(jax.random.PRNGKey(0))
    xs = jax.random.normal(jax.random.PRNGKey(1), (5, 2, 3))
    ys, (h, c) = m.apply(p, xs)
    assert ys.shape == (5, 2, 4)
    np.testing.assert_allclose(np.asarray(ys[-1]), np.asarray(h), rtol=1e-6)
    # GRU cell shape sanity
    h2 = gru_cell(xs[0], jnp.zeros((2, 4)),
                  jnp.zeros((3, 12)), jnp.zeros((4, 12)))
    assert h2.shape == (2, 4)


def test_weight_norm_identity():
    w = jax.random.normal(jax.random.PRNGKey(0), (4, 6))
    p = weight_norm_init(w)
    np.testing.assert_allclose(np.asarray(weight_norm_apply(p)),
                               np.asarray(w), rtol=1e-5)
    tree = {"layer": {"kernel": w, "bias": jnp.zeros((6,))}}
    wn = apply_weight_norm(tree)
    assert set(wn["layer"]["kernel"]) == {"g", "v"}
    back = remove_weight_norm(wn)
    np.testing.assert_allclose(np.asarray(back["layer"]["kernel"]),
                               np.asarray(w), rtol=1e-5)


def test_multihead_attn_class_wrappers():
    """SelfMultiheadAttn / EncdecMultiheadAttn at apex's class names wrap
    the functional blocks."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from apex_tpu.contrib.multihead_attn import (
        EncdecMultiheadAttn,
        SelfMultiheadAttn,
        encdec_attn,
        self_attn,
    )

    key = jax.random.PRNGKey(0)
    x = jax.random.normal(jax.random.fold_in(key, 1), (8, 2, 32))
    mem = jax.random.normal(jax.random.fold_in(key, 2), (6, 2, 32))

    layer = SelfMultiheadAttn(32, 4, include_norm_add=True)
    p = layer.init(key)
    np.testing.assert_allclose(
        np.asarray(layer(p, x)),
        np.asarray(self_attn(p, x, 4, include_norm_add=True)))

    enc = EncdecMultiheadAttn(32, 4)
    pe = enc.init(key)
    np.testing.assert_allclose(
        np.asarray(enc(pe, x, mem)),
        np.asarray(encdec_attn(pe, x, mem, 4)))


def test_fp16_optimizer_apex_ctor_shapes():
    """FP16_Optimizer accepts apex's constructor shapes."""
    import pytest as _pytest

    from apex_tpu.fp16_utils import FP16_Optimizer
    from apex_tpu.optimizers import fused_sgd

    o1 = FP16_Optimizer(fused_sgd(1e-2), 128.0)  # positional static scale
    assert float(o1.scaler.init_scale) == 128.0
    assert o1.scaler.growth_factor == 1.0
    o2 = FP16_Optimizer(fused_sgd(1e-2), static_loss_scale=64.0)
    assert float(o2.scaler.init_scale) == 64.0
    o3 = FP16_Optimizer(
        fused_sgd(1e-2), dynamic_loss_scale=True,
        dynamic_loss_args={"init_scale": 1024.0, "scale_window": 500})
    assert float(o3.scaler.init_scale) == 1024.0
    assert o3.scaler.growth_interval == 500
    o4 = FP16_Optimizer(fused_sgd(1e-2), dynamic_loss_scale=True)
    assert o4.scaler.growth_interval == 1000  # DynamicLossScaler default


def test_capabilities_registry():
    """Runtime capabilities registry replaces apex's build-time feature
    flags (SURVEY.md §5 'Config / flag system')."""
    import apex_tpu

    caps = apex_tpu.capabilities()
    for always in ("amp", "fused_optimizers", "flash_attention",
                   "transformer", "syncbn", "context_parallel"):
        assert caps[always] is True
    assert caps["backend"] == "cpu"  # conftest forces the CPU platform
    assert caps["pallas_native"] is False  # interpret mode off-TPU
    assert isinstance(caps["native_host_runtime"], bool)
    assert apex_tpu.has_capability("xentropy")
    assert not apex_tpu.has_capability("nonexistent_feature")


@pytest.mark.parametrize("env", ["/some/where/else", "", None])
def test_enable_compilation_cache_placement(monkeypatch, env):
    """The one cache-placement rule: a set JAX_COMPILATION_CACHE_DIR is
    the directory (JAX reads it itself, an empty value disabling the
    cache) and code names no other; an unset one gives the fixed
    <checkout>/.jax_cache."""
    import os

    from apex_tpu._capabilities import enable_compilation_cache

    named = []
    monkeypatch.setattr(jax.config, "update",
                        lambda key, value: named.append((key, value)))
    if env is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env)
    got = enable_compilation_cache()
    if env is None:
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        assert got == os.path.join(repo, ".jax_cache")
        assert named == [("jax_compilation_cache_dir", got)]
    else:
        assert got == env and named == []


def test_capabilities_repeated_access():
    """apex_tpu.capabilities stays the callable on every access (the
    submodule must not shadow the lazily-exported function)."""
    import apex_tpu

    first = apex_tpu.capabilities
    second = apex_tpu.capabilities
    assert callable(first) and callable(second)
    assert apex_tpu.capabilities()["amp"] is True
    assert apex_tpu.capabilities()["amp"] is True  # second call, same result


def test_transformer_layers_ln_wrapper():
    """apex/transformer/layers/layer_norm.py (U): get_layer_norm returns a
    working norm; FastLayerNorm and FusedLayerNorm are the same kernel on
    TPU (SURVEY.md 2.4 'merge with core LN kernel')."""
    import jax.numpy as jnp
    import numpy as np

    from apex_tpu.transformer import layers

    assert layers.FastLayerNorm is layers.FusedLayerNorm
    x = jnp.arange(24, dtype=jnp.float32).reshape(2, 12)
    y = layers.get_layer_norm(eps=1e-6, persist_layer_norm=True)(x)
    ref = (x - x.mean(-1, keepdims=True)) / jnp.sqrt(
        x.var(-1, keepdims=True) + 1e-6)
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)
    r = layers.get_layer_norm(rms=True)(x)
    rref = x / jnp.sqrt((x ** 2).mean(-1, keepdims=True) + 1e-5)
    np.testing.assert_allclose(np.asarray(r), np.asarray(rref),
                               rtol=1e-5, atol=1e-5)


def test_transformer_testing_helpers():
    """apex/transformer/testing (U) role: toy configs drive the real model
    stack; device helpers centralise the CPU-simulation backbone."""
    import jax

    from apex_tpu.models import gpt
    from apex_tpu.transformer import testing as ttesting

    cfg = ttesting.standalone_gpt_config(num_layers=1)
    params = gpt.init(cfg, jax.random.PRNGKey(0))
    assert params is not None
    bcfg = ttesting.standalone_bert_config()
    assert bcfg.hidden_size == 64
    assert len(ttesting.assert_devices(8)) == 8
