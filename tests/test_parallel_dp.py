"""Data-parallel runtime tests.

Parity model: apex tests/distributed/DDP + synced_batchnorm suites (U) on
the CPU-simulated mesh. Includes the overlap-equivalence regression (flat
bucketed reduce == per-tensor reduce) that replaces apex's
ddp_race_condition_test.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from apex_tpu import mesh as mx
from apex_tpu.parallel import (
    DistributedDataParallel,
    Reducer,
    SyncBatchNorm,
    allreduce_gradients,
    flat_dist_call,
    sync_batch_norm,
)


@pytest.fixture()
def dp8(devices8):
    return mx.build_mesh(tp=1, pp=1, devices=devices8)


def smap(f, mesh, in_specs, out_specs):
    return jax.jit(jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                                 out_specs=out_specs, check_vma=False))


def test_allreduce_gradients_average(dp8):
    grads = {"w": jnp.arange(8.0).reshape(8, 1), "b": jnp.ones((8, 2))}

    out = smap(lambda g: allreduce_gradients(g), dp8,
               ({"w": P("dp", None), "b": P("dp", None)},),
               {"w": P("dp", None), "b": P("dp", None)})(grads)
    # every shard's value becomes the mean over shards: w → mean(0..7)=3.5
    np.testing.assert_allclose(np.asarray(out["w"]), 3.5 * np.ones((8, 1)))
    np.testing.assert_allclose(np.asarray(out["b"]), np.ones((8, 2)))


def test_allreduce_fp32_upcast_keeps_dtype(dp8):
    g = jnp.ones((8, 4), jnp.bfloat16)
    out = smap(lambda g: allreduce_gradients(g, allreduce_always_fp32=True),
               dp8, (P("dp", None),), P("dp", None))(g)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(out, np.float32), 1.0)


def test_flat_dist_call_matches_per_tensor(dp8):
    """Overlap-equivalence regression: one flat-buffer reduce must equal
    per-tensor reduce exactly (apex ddp_race_condition_test analogue)."""
    tree = {
        "a": jnp.arange(8 * 3.0).reshape(8, 3),
        "b": jnp.arange(8 * 5.0).reshape(8, 5) * 0.1,
        "c": jnp.ones((8, 2), jnp.bfloat16),
    }
    specs = {k: P("dp", None) for k in tree}
    flat = smap(lambda t: flat_dist_call(t, op="pmean"), dp8, (specs,), specs)(tree)
    per = smap(lambda t: allreduce_gradients(t), dp8, (specs,), specs)(tree)
    for k in tree:
        np.testing.assert_array_equal(np.asarray(flat[k]), np.asarray(per[k]))


def test_flat_dist_call_broadcast(dp8):
    x = jnp.arange(8.0).reshape(8, 1)
    out = smap(lambda t: flat_dist_call(t, op="broadcast", src=2), dp8,
               (P("dp", None),), P("dp", None))(x)
    np.testing.assert_allclose(np.asarray(out), 2.0 * np.ones((8, 1)))


def test_ddp_wrap_and_no_sync_accumulation(dp8):
    """DDP-reduced grads == full-batch grads; two accumulated microbatches
    == one big batch (delay_allreduce semantics (U))."""
    params = {"w": jnp.array([[1.0], [2.0]])}  # (2, 1)
    x = jax.random.normal(jax.random.PRNGKey(0), (16, 2))
    y = jax.random.normal(jax.random.PRNGKey(1), (16, 1))

    def loss(p, x, y):
        return jnp.mean((x @ p["w"] - y) ** 2)

    ddp = DistributedDataParallel()
    grad_fn = jax.grad(loss)

    def step(p, x, y):
        return ddp.wrap_grad_fn(grad_fn)(p, x, y)

    g = smap(step, dp8, ({"w": P()}, P("dp", None), P("dp", None)),
             {"w": P()})(params, x, y)
    gref = jax.grad(loss)(params, x, y)
    np.testing.assert_allclose(np.asarray(g["w"]), np.asarray(gref["w"]),
                               rtol=1e-6, atol=1e-6)

    # accumulation: shard the batch in two halves per rank
    def step_accum(p, x1, y1, x2, y2):
        g1 = ddp.no_sync(grad_fn)(p, x1, y1)
        g = ddp.wrap_grad_fn(grad_fn)(p, x2, y2, accumulated=g1)
        return g

    g2 = smap(step_accum, dp8,
              ({"w": P()}, P("dp", None), P("dp", None), P("dp", None), P("dp", None)),
              {"w": P()})(params, x[:8], y[:8], x[8:], y[8:])
    # sum of two half-batch mean-grads = 2x grad of mean over half batches
    ref2 = jax.tree.map(jnp.add, jax.grad(loss)(params, x[:8], y[:8]),
                        jax.grad(loss)(params, x[8:], y[8:]))
    np.testing.assert_allclose(np.asarray(g2["w"]), np.asarray(ref2["w"]),
                               rtol=1e-6, atol=1e-6)


def test_reducer_broadcast(dp8):
    r = Reducer()
    x = jnp.arange(8.0).reshape(8, 1)
    out = smap(lambda t: r.broadcast(t), dp8, (P("dp", None),), P("dp", None))(x)
    np.testing.assert_allclose(np.asarray(out), 0.0 * np.ones((8, 1)))


# -- SyncBatchNorm ---------------------------------------------------------
def _bn_ref(x, scale, bias, eps=1e-5):
    # full-batch batchnorm over (N, H, W) for NCHW
    mean = x.mean(axis=(0, 2, 3))
    var = x.var(axis=(0, 2, 3))
    y = (x - mean[None, :, None, None]) / np.sqrt(var[None, :, None, None] + eps)
    return y * scale[None, :, None, None] + bias[None, :, None, None]


def test_syncbn_matches_full_batch(dp8):
    n, c, h, w = 16, 4, 3, 3
    x = jax.random.normal(jax.random.PRNGKey(2), (n, c, h, w))
    scale = jnp.array([1.0, 2.0, 0.5, 1.5])
    bias = jnp.array([0.0, 1.0, -1.0, 0.5])
    bn = SyncBatchNorm(c)
    params, state = bn.init()
    params = {"scale": scale, "bias": bias}

    def f(p, s, x):
        y, ns = bn.apply(p, s, x)
        return y, ns

    pspec, sspec = bn.specs
    y, ns = smap(f, dp8, (pspec, sspec, P("dp", None, None, None)),
                 (P("dp", None, None, None), sspec))(params, state, x)
    ref = _bn_ref(np.asarray(x), np.asarray(scale), np.asarray(bias))
    np.testing.assert_allclose(np.asarray(y), ref, rtol=1e-4, atol=1e-5)

    # running stats reflect the global batch
    np.testing.assert_allclose(np.asarray(ns["running_mean"]),
                               0.1 * np.asarray(x).mean((0, 2, 3)),
                               rtol=1e-4, atol=1e-5)


def test_syncbn_eval_uses_running_stats(dp8):
    c = 4
    bn = SyncBatchNorm(c)
    params, state = bn.init()
    state = {"running_mean": jnp.full((c,), 2.0), "running_var": jnp.full((c,), 4.0)}
    x = jnp.full((8, c, 2, 2), 4.0)

    pspec, sspec = bn.specs
    y, ns = smap(lambda p, s, x: bn.apply(p, s, x, training=False), dp8,
                 (pspec, sspec, P("dp", None, None, None)),
                 (P("dp", None, None, None), sspec))(params, state, x)
    np.testing.assert_allclose(np.asarray(y), (4.0 - 2.0) / np.sqrt(4.0 + 1e-5),
                               rtol=1e-5)
    np.testing.assert_allclose(np.asarray(ns["running_mean"]), 2.0)


def test_syncbn_channels_last(dp8):
    n, h, w, c = 16, 3, 3, 4
    x = jax.random.normal(jax.random.PRNGKey(3), (n, h, w, c))
    y, _, _ = smap(
        lambda x: sync_batch_norm(x, None, None, channel_axis=-1),
        dp8, (P("dp", None, None, None),), P("dp", None, None, None))(x)
    xn = np.asarray(x)
    ref = (xn - xn.mean((0, 1, 2))) / np.sqrt(xn.var((0, 1, 2)) + 1e-5)
    np.testing.assert_allclose(np.asarray(y), ref, rtol=1e-4, atol=1e-5)


def test_syncbn_grads_match_full_batch(dp8):
    n, c = 16, 3
    x = jax.random.normal(jax.random.PRNGKey(4), (n, c, 2, 2))
    scale = jnp.ones((c,))
    bias = jnp.zeros((c,))

    def loss_sharded(scale, bias, x):
        y, _, _ = sync_batch_norm(x, scale, bias)
        # global mean of y² → psum over dp of local sums / N
        return jax.lax.psum(jnp.sum(y ** 2), "dp") / (n * c * 4)

    def loss_ref(scale, bias, x):
        mean = x.mean((0, 2, 3), keepdims=True)
        var = x.var((0, 2, 3), keepdims=True)
        y = (x - mean) / jnp.sqrt(var + 1e-5)
        y = y * scale[None, :, None, None] + bias[None, :, None, None]
        return jnp.mean(y ** 2)

    # check_vma=True so psum transposes efficiently (replicated cotangents);
    # grads of replicated params come out correctly reduced.
    g = jax.jit(jax.shard_map(
        jax.grad(loss_sharded, argnums=(0, 1)), mesh=dp8,
        in_specs=(P(), P(), P("dp", None, None, None)),
        out_specs=(P(), P()),
        check_vma=True))(scale, bias, x)
    gref = jax.grad(loss_ref, argnums=(0, 1))(scale, bias, x)
    np.testing.assert_allclose(np.asarray(g[0]), np.asarray(gref[0]),
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(g[1]), np.asarray(gref[1]),
                               rtol=1e-4, atol=1e-5)


def test_syncbn_batch_weight_ragged(dp8):
    """A zero-padded shard with batch_weight == the unpadded statistics:
    the padded elements' mean² contribution is subtracted exactly from
    the two-pass centered sum."""
    import numpy as np

    x = jax.random.normal(jax.random.PRNGKey(0), (6, 3)) + 2.0  # mean>>0
    ref_mean = jnp.mean(x, axis=0)
    ref_var = jnp.mean((x - ref_mean) ** 2, axis=0)

    xp = jnp.concatenate([x, jnp.zeros((2, 3))])  # pad to 8 rows
    y, _, _ = sync_batch_norm(
        xp, None, None, axis=None, training=True, channel_axis=-1,
        batch_weight=jnp.float32(6.0))
    # recover the (mean, var) the call used from its normalized output
    got = (xp[:6] - y[:6] * jnp.sqrt(ref_var + 1e-5))
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(jnp.broadcast_to(ref_mean,
                                                           (6, 3))),
                               rtol=1e-4, atol=1e-4)
