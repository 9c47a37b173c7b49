"""Fused LayerNorm / RMSNorm Pallas kernels (forward + backward).

TPU-native equivalent of apex ``fused_layer_norm_cuda`` (csrc/
layer_norm_cuda{.cpp,_kernel.cu} (U)) and the contrib ``fast_layer_norm``
(apex/contrib/csrc/layer_norm (U)), unified: one kernel family covers
LayerNorm and RMSNorm ([era] FusedRMSNorm), affine or not, any hidden size
that fits VMEM row-blocks, fp32/bf16/fp16 I/O with fp32 statistics
(apex's ``MixedFused*`` behaviour is the default here — params may stay
fp32 with half I/O).

Differences from the CUDA design, by construction of the hardware:

- Apex computes Welford statistics to survive single-pass variance on long
  rows; here each row block is resident in VMEM so we use the masked
  two-moment form in fp32, which is exact enough at fp32 accumulation and
  keeps the VPU pipeline trivially vectorizable.
- The backward γ/β reduction (a cross-row sum) uses Pallas sequential-grid
  accumulation into a single output block instead of atomics/workspace
  buffers.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from apex_tpu.kernels._utils import (
    LANE,
    pick_block_rows,
    round_up,
    use_interpret,
    widen_f16,
)


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------

def _fwd_kernel(x_ref, w_ref, b_ref, y_ref, mean_ref, rstd_ref, *,
                hidden: int, eps: float, subtract_mean: bool):
    x = x_ref[:].astype(jnp.float32)                      # (bm, Hp)
    hp = x.shape[-1]
    mask = lax.broadcasted_iota(jnp.int32, (1, hp), 1) < hidden
    if subtract_mean:
        mean = jnp.sum(jnp.where(mask, x, 0.0), axis=-1, keepdims=True) / hidden
        diff = jnp.where(mask, x - mean, 0.0)
    else:
        mean = jnp.zeros((x.shape[0], 1), jnp.float32)
        diff = jnp.where(mask, x, 0.0)
    var = jnp.sum(diff * diff, axis=-1, keepdims=True) / hidden
    rstd = lax.rsqrt(var + eps)
    xhat = diff * rstd
    w = w_ref[:].astype(jnp.float32)
    b = b_ref[:].astype(jnp.float32)
    y_ref[:] = (xhat * w + b).astype(y_ref.dtype)
    mean_ref[:] = mean
    rstd_ref[:] = rstd


def _bwd_kernel(x_ref, w_ref, mean_ref, rstd_ref, dy_ref,
                dx_ref, dw_ref, db_ref, *, hidden: int, subtract_mean: bool):
    i = pl.program_id(0)
    x = x_ref[:].astype(jnp.float32)
    dy = dy_ref[:].astype(jnp.float32)
    hp = x.shape[-1]
    mask = lax.broadcasted_iota(jnp.int32, (1, hp), 1) < hidden
    mean = mean_ref[:]
    rstd = rstd_ref[:]
    xhat = jnp.where(mask, (x - mean) * rstd, 0.0)
    w = w_ref[:].astype(jnp.float32)
    wdy = jnp.where(mask, dy * w, 0.0)

    c1 = jnp.sum(wdy * xhat, axis=-1, keepdims=True) / hidden
    if subtract_mean:
        c2 = jnp.sum(wdy, axis=-1, keepdims=True) / hidden
    else:
        c2 = 0.0
    dx = (wdy - xhat * c1 - c2) * rstd
    dx_ref[:] = dx.astype(dx_ref.dtype)

    # γ/β partials: rows of this block, accumulated across the sequential
    # grid into one (1, Hp) output block (the csrc two-pass part-2 (U)).
    dw_part = jnp.sum(dy * xhat, axis=0, keepdims=True)
    db_part = jnp.sum(dy, axis=0, keepdims=True)

    @pl.when(i == 0)
    def _init():
        dw_ref[:] = dw_part
        db_ref[:] = db_part

    @pl.when(i != 0)
    def _acc():
        dw_ref[:] += dw_part
        db_ref[:] += db_part


# ---------------------------------------------------------------------------
# host-side wrappers
# ---------------------------------------------------------------------------

def _pad2d(x, rows, cols):
    r, c = x.shape
    if r == rows and c == cols:
        return x
    return jnp.pad(x, ((0, rows - r), (0, cols - c)))


def _fwd(x2, w, b, eps: float, subtract_mean: bool):
    rows, hidden = x2.shape
    hp = round_up(hidden, LANE)
    bm = pick_block_rows(hp)
    rp = round_up(rows, bm)
    xp = _pad2d(x2, rp, hp)
    wp = jnp.pad(w, (0, hp - hidden)).reshape(1, hp)
    bp = jnp.pad(b, (0, hp - hidden)).reshape(1, hp)
    grid = (rp // bm,)
    kernel = functools.partial(
        _fwd_kernel, hidden=hidden, eps=eps, subtract_mean=subtract_mean)
    y, mean, rstd = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, hp), lambda i: (i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, hp), lambda i: (0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, hp), lambda i: (0, 0), memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((bm, hp), lambda i: (i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((bm, 1), lambda i: (i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((bm, 1), lambda i: (i, 0), memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((rp, hp), x2.dtype),
            jax.ShapeDtypeStruct((rp, 1), jnp.float32),
            jax.ShapeDtypeStruct((rp, 1), jnp.float32),
        ],
        name="layer_norm_fwd",
        interpret=use_interpret(),
    )(xp, wp, bp)
    return y[:rows, :hidden], mean[:rows], rstd[:rows]


def _bwd(x2, w, mean, rstd, dy2, subtract_mean: bool):
    rows, hidden = x2.shape
    hp = round_up(hidden, LANE)
    bm = pick_block_rows(hp)
    rp = round_up(rows, bm)
    xp = _pad2d(x2, rp, hp)
    dyp = _pad2d(dy2, rp, hp)  # zero rows/cols contribute nothing to sums
    wp = jnp.pad(w, (0, hp - hidden)).reshape(1, hp)
    meanp = jnp.pad(mean, ((0, rp - rows), (0, 0)))
    rstdp = jnp.pad(rstd, ((0, rp - rows), (0, 0)))
    grid = (rp // bm,)
    kernel = functools.partial(_bwd_kernel, hidden=hidden, subtract_mean=subtract_mean)
    dx, dw, db = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, hp), lambda i: (i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, hp), lambda i: (0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((bm, 1), lambda i: (i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((bm, 1), lambda i: (i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((bm, hp), lambda i: (i, 0), memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((bm, hp), lambda i: (i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, hp), lambda i: (0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, hp), lambda i: (0, 0), memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((rp, hp), x2.dtype),
            jax.ShapeDtypeStruct((1, hp), jnp.float32),
            jax.ShapeDtypeStruct((1, hp), jnp.float32),
        ],
        name="layer_norm_bwd",
        interpret=use_interpret(),
    )(xp, wp, meanp, rstdp, dyp)
    return dx[:rows, :hidden], dw[0, :hidden], db[0, :hidden]


# ---------------------------------------------------------------------------
# public API (custom VJP)
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _norm(x, weight, bias, eps, subtract_mean):
    shape = x.shape
    hidden = shape[-1]
    x2 = x.reshape(-1, hidden)
    y, _, _ = _fwd(x2, weight, bias, eps, subtract_mean)
    return y.reshape(shape)


def _norm_fwd(x, weight, bias, eps, subtract_mean):
    shape = x.shape
    hidden = shape[-1]
    x2 = x.reshape(-1, hidden)
    y, mean, rstd = _fwd(x2, weight, bias, eps, subtract_mean)
    return y.reshape(shape), (x2, weight, mean, rstd, shape)


def _norm_bwd(eps, subtract_mean, res, dy):
    x2, weight, mean, rstd, shape = res
    dy2 = dy.reshape(-1, shape[-1])
    dx, dw, db = _bwd(x2, weight, mean, rstd, dy2, subtract_mean)
    dw = dw.astype(weight.dtype)
    if not subtract_mean:
        db = jnp.zeros_like(dw)
    return dx.reshape(shape), dw, db.astype(weight.dtype)


_norm.defvjp(_norm_fwd, _norm_bwd)


def layer_norm(x, weight: Optional[jnp.ndarray] = None,
               bias: Optional[jnp.ndarray] = None, *, eps: float = 1e-5):
    """Fused LayerNorm over the last axis (``FusedLayerNorm`` (U)).

    ``weight``/``bias`` default to identity affine. Statistics are fp32
    regardless of I/O dtype; params may be fp32 with half inputs
    (``MixedFusedLayerNorm`` (U) behaviour).
    """
    hidden = x.shape[-1]
    if weight is None:
        weight = jnp.ones((hidden,), jnp.float32)
    if bias is None:
        bias = jnp.zeros((hidden,), weight.dtype)
    x, was16 = widen_f16(x)
    weight, _ = widen_f16(weight)
    bias, _ = widen_f16(bias)
    y = _norm(x, weight, bias, float(eps), True)
    return y.astype(jnp.float16) if was16 else y


def rms_norm(x, weight: Optional[jnp.ndarray] = None, *, eps: float = 1e-5):
    """Fused RMSNorm over the last axis (``FusedRMSNorm`` [era] (U))."""
    hidden = x.shape[-1]
    if weight is None:
        weight = jnp.ones((hidden,), jnp.float32)
    x, was16 = widen_f16(x)
    weight, _ = widen_f16(weight)
    bias = jnp.zeros((hidden,), weight.dtype)  # after widening — no f16
    y = _norm(x, weight, bias, float(eps), False)
    return y.astype(jnp.float16) if was16 else y
