"""Fused softmax-cross-entropy Pallas kernel with label smoothing.

TPU-native equivalent of apex contrib xentropy
(apex/contrib/csrc/xentropy/xentropy_kernel.cu (U),
``SoftmaxCrossEntropyLoss``). The fusion's point is memory: forward saves
only the per-row log-sum-exp (not the softmax), and backward recomputes
``softmax = exp(x - lse)`` from the logits — the reference's
"saves logits memory" trick, identical here.

Smoothed loss (reference formula): ``lse - (1-eps)*x[target] - eps*mean(x)``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from apex_tpu.kernels._utils import LANE, pick_block_rows, round_up, use_interpret, widen_f16


def _fwd_kernel(x_ref, t_ref, loss_ref, lse_ref, *, vocab: int,
                smoothing: float, ignore_index: int):
    x = x_ref[:].astype(jnp.float32)                     # (bm, Vp)
    t = t_ref[:]                                         # (bm, 1) int32
    col = lax.broadcasted_iota(jnp.int32, x.shape, 1)
    valid = col < vocab
    xm = jnp.where(valid, x, -jnp.inf)
    mx = jnp.max(xm, axis=-1, keepdims=True)
    lse = jnp.log(jnp.sum(jnp.where(valid, jnp.exp(x - mx), 0.0),
                          axis=-1, keepdims=True)) + mx
    predicted = jnp.sum(jnp.where(col == t, x, 0.0), axis=-1, keepdims=True)
    loss = lse - predicted
    if smoothing > 0.0:
        mean_x = jnp.sum(jnp.where(valid, x, 0.0), axis=-1, keepdims=True) / vocab
        loss = lse - (1.0 - smoothing) * predicted - smoothing * mean_x
    loss = jnp.where(t == ignore_index, 0.0, loss)
    loss_ref[:] = loss
    lse_ref[:] = lse


def _bwd_kernel(x_ref, t_ref, lse_ref, g_ref, dx_ref, *, vocab: int,
                smoothing: float, ignore_index: int):
    x = x_ref[:].astype(jnp.float32)
    t = t_ref[:]
    lse = lse_ref[:]
    g = g_ref[:]
    col = lax.broadcasted_iota(jnp.int32, x.shape, 1)
    valid = col < vocab
    softmax = jnp.where(valid, jnp.exp(x - lse), 0.0)
    onehot = (col == t).astype(jnp.float32)
    grad = softmax - (1.0 - smoothing) * onehot
    if smoothing > 0.0:
        grad = grad - smoothing / vocab
    grad = jnp.where(valid, grad, 0.0)
    grad = jnp.where(t == ignore_index, 0.0, grad)
    dx_ref[:] = (grad * g).astype(dx_ref.dtype)


def _prep(x2, rows, vocab):
    vp = round_up(vocab, LANE)
    bm = pick_block_rows(vp, n_buffers=3)
    rp = round_up(rows, bm)
    xp = jnp.pad(x2, ((0, rp - rows), (0, vp - vocab)))
    return xp, vp, bm, rp


def _run_fwd(x2, t2, smoothing: float, ignore_index: int):
    rows, vocab = x2.shape
    xp, vp, bm, rp = _prep(x2, rows, vocab)
    # padded rows get target = ignore_index → zero loss
    tp = jnp.full((rp, 1), ignore_index, jnp.int32).at[:rows].set(t2[:, None])
    grid = (rp // bm,)
    loss, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, vocab=vocab, smoothing=smoothing,
                          ignore_index=ignore_index),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, vp), lambda i: (i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((bm, 1), lambda i: (i, 0), memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((bm, 1), lambda i: (i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((bm, 1), lambda i: (i, 0), memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((rp, 1), jnp.float32),
            jax.ShapeDtypeStruct((rp, 1), jnp.float32),
        ],
        name="xentropy_fwd",
        interpret=use_interpret(),
    )(xp, tp)
    return loss[:rows, 0], lse[:rows]


def _run_bwd(x2, t2, lse, g, smoothing: float, ignore_index: int):
    rows, vocab = x2.shape
    xp, vp, bm, rp = _prep(x2, rows, vocab)
    tp = jnp.full((rp, 1), ignore_index, jnp.int32).at[:rows].set(t2[:, None])
    lsep = jnp.pad(lse, ((0, rp - rows), (0, 0)))
    gp = jnp.pad(g[:, None], ((0, rp - rows), (0, 0)))
    grid = (rp // bm,)
    dx = pl.pallas_call(
        functools.partial(_bwd_kernel, vocab=vocab, smoothing=smoothing,
                          ignore_index=ignore_index),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, vp), lambda i: (i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((bm, 1), lambda i: (i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((bm, 1), lambda i: (i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((bm, 1), lambda i: (i, 0), memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((bm, vp), lambda i: (i, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((rp, vp), x2.dtype),
        name="xentropy_bwd",
        interpret=use_interpret(),
    )(xp, tp, lsep, gp)
    return dx[:rows, :vocab]


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def softmax_cross_entropy(logits, target, label_smoothing: float = 0.0,
                          ignore_index: int = -100):
    """Per-token loss from ``logits [..., vocab]`` and int ``target [...]``.

    Drop-in for apex contrib ``SoftmaxCrossEntropyLoss`` (U): fused, label
    smoothing, ``ignore_index`` rows contribute zero loss and zero grad.
    """
    shape = target.shape
    logits, _ = widen_f16(logits)  # loss is fp32 either way
    loss, _ = _run_fwd(logits.reshape(-1, logits.shape[-1]),
                       target.reshape(-1).astype(jnp.int32),
                       float(label_smoothing), ignore_index)
    return loss.reshape(shape)


def _sce_fwd(logits, target, label_smoothing, ignore_index):
    orig_dtype = logits.dtype
    logits, _ = widen_f16(logits)
    x2 = logits.reshape(-1, logits.shape[-1])
    t2 = target.reshape(-1).astype(jnp.int32)
    loss, lse = _run_fwd(x2, t2, float(label_smoothing), ignore_index)
    # residuals must be JAX types — carry the pre-widening dtype in a
    # zero-size array
    dtype_tag = jnp.zeros((0,), orig_dtype)
    return loss.reshape(target.shape), (
        x2, t2, lse, logits.shape, target.shape, dtype_tag)


def _sce_bwd(label_smoothing, ignore_index, res, dy):
    x2, t2, lse, lshape, tshape, dtype_tag = res
    dx = _run_bwd(x2, t2, lse, dy.reshape(-1).astype(jnp.float32),
                  float(label_smoothing), ignore_index)
    # cotangent dtype must match the primal input's (f16 widened at entry)
    return (dx.reshape(lshape).astype(dtype_tag.dtype),
            np.zeros(tshape, dtype=jax.dtypes.float0))


softmax_cross_entropy.defvjp(_sce_fwd, _sce_bwd)
