"""Flat-buffer multi-tensor kernels — the ``amp_C`` equivalent.

TPU-native re-design of apex's multi-tensor CUDA sweeps (csrc/
multi_tensor_{scale,axpby,l2norm,adam,sgd,adagrad}*.cu (U), dispatched via
csrc/multi_tensor_apply.cuh (U)). Where apex chunks a Python list of
hundreds of tensors on the fly, here the tensors are packed **once** into
padded flat buffers (apex_tpu.multi_tensor) and each op is a single Pallas
kernel sweeping one contiguous (rows, 128) view per dtype group — the same
"one launch for all params" property with zero per-step chunking logic.

Overflow detection (apex's ``_overflow_buf``) is an SMEM flag accumulated
across the sequential grid; the optimizer-state sweeps (adam etc.) take a
``grad_scale`` so amp's unscale folds into the update, exactly like apex's
scaler → FusedAdam pipeline (SURVEY.md §3.2).
"""

from __future__ import annotations

import functools
from typing import List, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from apex_tpu.kernels._utils import LANE, use_interpret, widen_f16


def _narrow(buf, dtype):
    """Cast a kernel output to the requested dtype when the kernel had to
    run widened (Mosaic has no f16)."""
    return buf if buf.dtype == dtype else buf.astype(dtype)

_MAX_BLOCK_ROWS = 512


def _view2d(buf: jnp.ndarray) -> jnp.ndarray:
    assert buf.ndim == 1 and buf.shape[0] % LANE == 0, buf.shape
    return buf.reshape(-1, LANE)


def _block_rows(rows: int) -> int:
    """Largest power-of-two divisor of ``rows`` up to the cap, so grid
    blocks tile exactly (no out-of-bounds pad reads that could poison the
    overflow flag)."""
    bm = 1
    while bm * 2 <= _MAX_BLOCK_ROWS and rows % (bm * 2) == 0:
        bm *= 2
    return bm


def _vspec(bm):
    return pl.BlockSpec((bm, LANE), lambda i: (i, 0), memory_space=pltpu.VMEM)


def _smem_spec(shape):
    return pl.BlockSpec(shape, lambda i: tuple(0 for _ in shape), memory_space=pltpu.SMEM)


# ---------------------------------------------------------------------------
# multi_tensor_scale: out = in * scale, with overflow detection
# ---------------------------------------------------------------------------

def _scale_kernel(s_ref, x_ref, o_ref, flag_ref):
    i = pl.program_id(0)
    x = x_ref[:].astype(jnp.float32)
    y = x * s_ref[0, 0]
    o_ref[:] = y.astype(o_ref.dtype)
    nonfinite = jnp.logical_not(jnp.isfinite(x).all())

    @pl.when(i == 0)
    def _():
        flag_ref[0, 0] = 0

    @pl.when(nonfinite)
    def _():
        flag_ref[0, 0] = 1


def scale_flat(bufs: Sequence[jnp.ndarray], scale) -> Tuple[List[jnp.ndarray], jnp.ndarray]:
    """``amp_C.multi_tensor_scale`` (U): scaled copies + found-inf flag.

    The unscale-with-overflow-check at the heart of the dynamic loss scaler
    (apex/amp/scaler.py ``unscale`` (U)); ``scale`` is a traced scalar.
    """
    s = jnp.asarray(scale, jnp.float32).reshape(1, 1)
    outs, flags = [], []
    for buf in bufs:
        want = buf.dtype
        buf, _ = widen_f16(buf)
        x2 = _view2d(buf)
        bm = _block_rows(x2.shape[0])
        out, flag = pl.pallas_call(
            _scale_kernel,
            grid=(x2.shape[0] // bm,),
            in_specs=[_smem_spec((1, 1)), _vspec(bm)],
            out_specs=[_vspec(bm), _smem_spec((1, 1))],
            out_shape=[
                jax.ShapeDtypeStruct(x2.shape, buf.dtype),
                jax.ShapeDtypeStruct((1, 1), jnp.int32),
            ],
            name="flat_scale",
            interpret=use_interpret(),
        )(s, x2)
        outs.append(_narrow(out.reshape(-1), want))
        flags.append(flag[0, 0])
    found_inf = jnp.stack(flags).sum() > 0
    return outs, found_inf


# ---------------------------------------------------------------------------
# multi_tensor_axpby: out = a*x + b*y, with overflow detection
# ---------------------------------------------------------------------------

def _axpby_kernel(s_ref, x_ref, y_ref, o_ref, flag_ref):
    i = pl.program_id(0)
    x = x_ref[:].astype(jnp.float32)
    y = y_ref[:].astype(jnp.float32)
    out = s_ref[0, 0] * x + s_ref[0, 1] * y
    o_ref[:] = out.astype(o_ref.dtype)
    nonfinite = jnp.logical_not(jnp.isfinite(out).all())

    @pl.when(i == 0)
    def _():
        flag_ref[0, 0] = 0

    @pl.when(nonfinite)
    def _():
        flag_ref[0, 0] = 1


def axpby_flat(a, xbufs: Sequence[jnp.ndarray], b, ybufs: Sequence[jnp.ndarray],
               out_dtype=None) -> Tuple[List[jnp.ndarray], jnp.ndarray]:
    """``amp_C.multi_tensor_axpby`` (U): fused a*x + b*y (master-grad
    accumulation path)."""
    s = jnp.stack([jnp.asarray(a, jnp.float32), jnp.asarray(b, jnp.float32)]).reshape(1, 2)
    outs, flags = [], []
    for xb, yb in zip(xbufs, ybufs):
        want = jnp.dtype(out_dtype) if out_dtype else xb.dtype
        xb, _ = widen_f16(xb)
        yb, _ = widen_f16(yb)
        x2, y2 = _view2d(xb), _view2d(yb)
        bm = _block_rows(x2.shape[0])
        dt = jnp.float32 if want == jnp.float16 else want
        out, flag = pl.pallas_call(
            _axpby_kernel,
            grid=(x2.shape[0] // bm,),
            in_specs=[_smem_spec((1, 2)), _vspec(bm), _vspec(bm)],
            out_specs=[_vspec(bm), _smem_spec((1, 1))],
            out_shape=[
                jax.ShapeDtypeStruct(x2.shape, dt),
                jax.ShapeDtypeStruct((1, 1), jnp.int32),
            ],
            name="flat_axpby",
            interpret=use_interpret(),
        )(s, x2, y2)
        outs.append(_narrow(out.reshape(-1), want))
        flags.append(flag[0, 0])
    found_inf = jnp.stack(flags).sum() > 0
    return outs, found_inf


# ---------------------------------------------------------------------------
# multi_tensor_l2norm: global L2 norm in one pass
# ---------------------------------------------------------------------------

def _sumsq_kernel(x_ref, acc_ref):
    i = pl.program_id(0)
    x = x_ref[:].astype(jnp.float32)
    part = jnp.sum(x * x)

    @pl.when(i == 0)
    def _():
        acc_ref[0, 0] = part

    @pl.when(i != 0)
    def _():
        acc_ref[0, 0] += part


def l2norm_flat(bufs: Sequence[jnp.ndarray]) -> jnp.ndarray:
    """``amp_C.multi_tensor_l2norm`` (U) global mode: ‖all buffers‖₂."""
    total = jnp.float32(0.0)
    for buf in bufs:
        buf, _ = widen_f16(buf)
        x2 = _view2d(buf)
        bm = _block_rows(x2.shape[0])
        acc = pl.pallas_call(
            _sumsq_kernel,
            grid=(x2.shape[0] // bm,),
            in_specs=[_vspec(bm)],
            out_specs=_smem_spec((1, 1)),
            out_shape=jax.ShapeDtypeStruct((1, 1), jnp.float32),
            name="flat_sumsq",
            interpret=use_interpret(),
        )(x2)
        total = total + acc[0, 0]
    return jnp.sqrt(total)


# ---------------------------------------------------------------------------
# multi_tensor_adam
# ---------------------------------------------------------------------------

def _adam_kernel(s_ref, p_ref, g_ref, m_ref, v_ref,
                 np_ref, nm_ref, nv_ref, *, adam_w_mode: bool,
                 out_is_delta: bool, grad_averaging: bool = True):
    lr = s_ref[0, 0]
    b1 = s_ref[0, 1]
    b2 = s_ref[0, 2]
    eps = s_ref[0, 3]
    wd = s_ref[0, 4]
    bc1 = s_ref[0, 5]   # 1 - b1^t  (1.0 when bias_correction off)
    bc2 = s_ref[0, 6]   # 1 - b2^t
    gscale = s_ref[0, 7]

    p = p_ref[:].astype(jnp.float32)
    g = g_ref[:].astype(jnp.float32) * gscale
    if not adam_w_mode:
        g = g + wd * p  # classic L2 regularization (apex adam_w_mode=False)
    # grad_averaging=False (LAMB stage-1 option (U)): accumulate the raw
    # grad into m instead of the (1-b1)-weighted average
    m = b1 * m_ref[:] + ((1.0 - b1) if grad_averaging else 1.0) * g
    v = b2 * v_ref[:] + (1.0 - b2) * g * g
    mhat = m / bc1
    vhat = v / bc2
    upd = mhat / (jnp.sqrt(vhat) + eps)
    if adam_w_mode:
        upd = upd + wd * p  # decoupled weight decay (AdamW)
    out = -lr * upd if out_is_delta else p - lr * upd
    np_ref[:] = out.astype(np_ref.dtype)
    nm_ref[:] = m
    nv_ref[:] = v


def adam_flat(p_bufs, g_bufs, m_bufs, v_bufs, *, lr, b1, b2, eps, weight_decay,
              bias_correction1, bias_correction2, grad_scale=1.0,
              adam_w_mode: bool = True, out_is_delta: bool = False,
              out_dtype=None, grad_averaging: bool = True):
    """``amp_C.multi_tensor_adam`` (U): one fused sweep updating params and
    both moments. All scalar hyperparams are traced (schedules compile into
    the same program)."""
    s = jnp.stack([
        jnp.asarray(lr, jnp.float32), jnp.asarray(b1, jnp.float32),
        jnp.asarray(b2, jnp.float32), jnp.asarray(eps, jnp.float32),
        jnp.asarray(weight_decay, jnp.float32),
        jnp.asarray(bias_correction1, jnp.float32),
        jnp.asarray(bias_correction2, jnp.float32),
        jnp.asarray(grad_scale, jnp.float32),
    ]).reshape(1, 8)
    kernel = functools.partial(_adam_kernel, adam_w_mode=adam_w_mode,
                               out_is_delta=out_is_delta,
                               grad_averaging=grad_averaging)
    new_p, new_m, new_v = [], [], []
    for pb, gb, mb, vb in zip(p_bufs, g_bufs, m_bufs, v_bufs):
        want = jnp.dtype(out_dtype) if out_dtype else pb.dtype
        pb, _ = widen_f16(pb)
        gb, _ = widen_f16(gb)
        p2, g2, m2, v2 = map(_view2d, (pb, gb, mb, vb))
        bm = _block_rows(p2.shape[0])
        dt = jnp.float32 if want == jnp.float16 else want
        np_, nm_, nv_ = pl.pallas_call(
            kernel,
            grid=(p2.shape[0] // bm,),
            in_specs=[_smem_spec((1, 8))] + [_vspec(bm)] * 4,
            out_specs=[_vspec(bm)] * 3,
            out_shape=[
                jax.ShapeDtypeStruct(p2.shape, dt),
                jax.ShapeDtypeStruct(m2.shape, jnp.float32),
                jax.ShapeDtypeStruct(v2.shape, jnp.float32),
            ],
            name="flat_adam",
            interpret=use_interpret(),
        )(s, p2, g2, m2, v2)
        new_p.append(_narrow(np_.reshape(-1), want))
        new_m.append(nm_.reshape(-1))
        new_v.append(nv_.reshape(-1))
    return new_p, new_m, new_v


# ---------------------------------------------------------------------------
# multi_tensor_sgd (momentum / dampening / nesterov / wd)
# ---------------------------------------------------------------------------

def _sgd_kernel(s_ref, p_ref, g_ref, m_ref, np_ref, nm_ref,
                *, nesterov: bool, out_is_delta: bool):
    lr = s_ref[0, 0]
    momentum = s_ref[0, 1]
    dampening = s_ref[0, 2]  # caller zeroes this on step 0 → buf = grad,
    wd = s_ref[0, 3]         # matching torch/apex first-step semantics
    gscale = s_ref[0, 4]

    p = p_ref[:].astype(jnp.float32)
    g = g_ref[:].astype(jnp.float32) * gscale + wd * p
    m = momentum * m_ref[:] + (1.0 - dampening) * g
    upd = g + momentum * m if nesterov else m
    out = -lr * upd if out_is_delta else p - lr * upd
    np_ref[:] = out.astype(np_ref.dtype)
    nm_ref[:] = m


def sgd_flat(p_bufs, g_bufs, m_bufs, *, lr, momentum, dampening, weight_decay,
             grad_scale=1.0, nesterov=False, out_is_delta=False):
    """``amp_C.multi_tensor_sgd`` (U).

    Torch/apex initialise the momentum buffer to the raw grad on the first
    step; with ``m=0`` that is equivalent to zeroing ``dampening`` on step
    0, which the caller does with a traced ``where`` — no recompile.
    """
    s = jnp.stack([
        jnp.asarray(lr, jnp.float32), jnp.asarray(momentum, jnp.float32),
        jnp.asarray(dampening, jnp.float32), jnp.asarray(weight_decay, jnp.float32),
        jnp.asarray(grad_scale, jnp.float32),
    ]).reshape(1, 5)
    kernel = functools.partial(_sgd_kernel, nesterov=nesterov,
                               out_is_delta=out_is_delta)
    new_p, new_m = [], []
    for pb, gb, mb in zip(p_bufs, g_bufs, m_bufs):
        want = pb.dtype
        pb, _ = widen_f16(pb)
        gb, _ = widen_f16(gb)
        p2, g2, m2 = map(_view2d, (pb, gb, mb))
        bm = _block_rows(p2.shape[0])
        np_, nm_ = pl.pallas_call(
            kernel,
            grid=(p2.shape[0] // bm,),
            in_specs=[_smem_spec((1, 5))] + [_vspec(bm)] * 3,
            out_specs=[_vspec(bm)] * 2,
            out_shape=[
                jax.ShapeDtypeStruct(p2.shape, pb.dtype),
                jax.ShapeDtypeStruct(m2.shape, jnp.float32),
            ],
            name="flat_sgd",
            interpret=use_interpret(),
        )(s, p2, g2, m2)
        new_p.append(_narrow(np_.reshape(-1), want))
        new_m.append(nm_.reshape(-1))
    return new_p, new_m


# ---------------------------------------------------------------------------
# multi_tensor_adagrad
# ---------------------------------------------------------------------------

def _adagrad_kernel(s_ref, p_ref, g_ref, h_ref, np_ref, nh_ref, *,
                    out_is_delta: bool):
    lr = s_ref[0, 0]
    eps = s_ref[0, 1]
    wd = s_ref[0, 2]
    gscale = s_ref[0, 3]
    p = p_ref[:].astype(jnp.float32)
    g = g_ref[:].astype(jnp.float32) * gscale + wd * p
    h = h_ref[:] + g * g
    upd = lr * g / (jnp.sqrt(h) + eps)
    out = -upd if out_is_delta else p - upd
    np_ref[:] = out.astype(np_ref.dtype)
    nh_ref[:] = h


def adagrad_flat(p_bufs, g_bufs, h_bufs, *, lr, eps, weight_decay,
                 grad_scale=1.0, out_is_delta=False):
    """``amp_C.multi_tensor_adagrad`` (U)."""
    s = jnp.stack([
        jnp.asarray(lr, jnp.float32), jnp.asarray(eps, jnp.float32),
        jnp.asarray(weight_decay, jnp.float32), jnp.asarray(grad_scale, jnp.float32),
    ]).reshape(1, 4)
    kernel = functools.partial(_adagrad_kernel, out_is_delta=out_is_delta)
    new_p, new_h = [], []
    for pb, gb, hb in zip(p_bufs, g_bufs, h_bufs):
        want = pb.dtype
        pb, _ = widen_f16(pb)
        gb, _ = widen_f16(gb)
        p2, g2, h2 = map(_view2d, (pb, gb, hb))
        bm = _block_rows(p2.shape[0])
        np_, nh_ = pl.pallas_call(
            kernel,
            grid=(p2.shape[0] // bm,),
            in_specs=[_smem_spec((1, 4))] + [_vspec(bm)] * 3,
            out_specs=[_vspec(bm)] * 2,
            out_shape=[
                jax.ShapeDtypeStruct(p2.shape, pb.dtype),
                jax.ShapeDtypeStruct(h2.shape, jnp.float32),
            ],
            name="flat_adagrad",
            interpret=use_interpret(),
        )(s, p2, g2, h2)
        new_p.append(_narrow(np_.reshape(-1), want))
        new_h.append(nh_.reshape(-1))
    return new_p, new_h
