"""Fused scaled(-masked) softmax Pallas kernels (forward + backward).

TPU-native equivalent of apex's megatron softmax extensions
(csrc/megatron/scaled_masked_softmax*.cu, scaled_upper_triang_masked_
softmax*.cu (U)): ``softmax(scale * x + mask)`` fused in one pass, with an
explicit-mask variant and a causal (upper-triangular) variant.

Where the CUDA kernels are templated per sequence length (hard caps at
2k/4k), the Pallas kernel row-blocks over VMEM and handles any key length
that fits a row block; there is no compile-time whitelist to outgrow.
Backward recomputes nothing: it consumes the saved softmax output, matching
the reference's ``backward(grad, softmax_results)`` contract.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from apex_tpu.kernels._utils import LANE, pick_block_rows, round_up, use_interpret, widen_f16

_NEG = -30000.0  # mask fill; reference uses -10000.0 for fp16


def _fwd_kernel(x_ref, m_ref, y_ref, *, scale: float, sk: int, causal: bool,
                bm: int):
    x = x_ref[0].astype(jnp.float32) * scale              # (bm, skp)
    skp = x.shape[-1]
    col = lax.broadcasted_iota(jnp.int32, (x.shape[0], skp), 1)
    valid = col < sk
    if causal:
        j = pl.program_id(1)
        row = lax.broadcasted_iota(jnp.int32, (x.shape[0], skp), 0) + j * bm
        valid = valid & (col <= row)
    if m_ref is not None:
        valid = valid & (m_ref[0] == 0)
    x = jnp.where(valid, x, _NEG)
    x = x - jnp.max(x, axis=-1, keepdims=True)
    e = jnp.exp(x)
    e = jnp.where(valid, e, 0.0)
    denom = jnp.sum(e, axis=-1, keepdims=True)
    # fully-masked rows (possible with padding masks) produce 0, not NaN
    y_ref[0] = (e / jnp.maximum(denom, 1e-30)).astype(y_ref.dtype)


def _bwd_kernel(y_ref, dy_ref, dx_ref, *, scale: float):
    y = y_ref[0].astype(jnp.float32)
    dy = dy_ref[0].astype(jnp.float32)
    inner = jnp.sum(y * dy, axis=-1, keepdims=True)
    dx_ref[0] = (scale * y * (dy - inner)).astype(dx_ref.dtype)


def _pad3(x, b2, rp, cp):
    pads = [(0, b2 - x.shape[0]), (0, rp - x.shape[1]), (0, cp - x.shape[2])]
    if all(p == (0, 0) for p in pads):
        return x
    return jnp.pad(x, pads)


def _run_fwd(x3, mask3, scale: float, causal: bool):
    nb, sq, sk = x3.shape
    skp = round_up(sk, LANE)
    bm = pick_block_rows(skp, n_buffers=4)
    bm = min(bm, round_up(sq, 8))
    sqp = round_up(sq, bm)
    xp = _pad3(x3, nb, sqp, skp)
    grid = (nb, sqp // bm)
    in_specs = [pl.BlockSpec((1, bm, skp), lambda i, j: (i, j, 0),
                             memory_space=pltpu.VMEM)]
    operands = [xp]
    if mask3 is not None:
        mp = _pad3(mask3.astype(jnp.int32), mask3.shape[0], sqp, skp)
        # mask has batch dim b while x has b*h rows: integer-divide the grid
        h = nb // mask3.shape[0]
        in_specs.append(
            pl.BlockSpec((1, bm, skp), lambda i, j: (i // h, j, 0),
                         memory_space=pltpu.VMEM))
        operands.append(mp)
        kernel = functools.partial(_fwd_kernel, scale=scale, sk=sk,
                                   causal=causal, bm=bm)
    else:
        kernel = functools.partial(
            lambda x_ref, y_ref, **kw: _fwd_kernel(x_ref, None, y_ref, **kw),
            scale=scale, sk=sk, causal=causal, bm=bm)
    y = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, bm, skp), lambda i, j: (i, j, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((nb, sqp, skp), x3.dtype),
        name="scaled_softmax_fwd",
        interpret=use_interpret(),
    )(*operands)
    return y[:, :sq, :sk]


def _run_bwd(y3, dy3, scale: float):
    nb, sq, sk = y3.shape
    skp = round_up(sk, LANE)
    bm = pick_block_rows(skp, n_buffers=4)
    bm = min(bm, round_up(sq, 8))
    sqp = round_up(sq, bm)
    yp = _pad3(y3, nb, sqp, skp)
    dyp = _pad3(dy3, nb, sqp, skp)
    grid = (nb, sqp // bm)
    spec = pl.BlockSpec((1, bm, skp), lambda i, j: (i, j, 0),
                        memory_space=pltpu.VMEM)
    dx = pl.pallas_call(
        functools.partial(_bwd_kernel, scale=scale),
        grid=grid,
        in_specs=[spec, spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((nb, sqp, skp), y3.dtype),
        name="scaled_softmax_bwd",
        interpret=use_interpret(),
    )(yp, dyp)
    return dx[:, :sq, :sk]


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _softmax(x3, mask3, scale: float, causal: bool):
    return _run_fwd(x3, mask3, scale, causal)


def _softmax_fwd(x3, mask3, scale, causal):
    y = _run_fwd(x3, mask3, scale, causal)
    return y, (y, None if mask3 is None else mask3.shape)


def _softmax_bwd(scale, causal, res, dy):
    y, mshape = res
    dx = _run_bwd(y, dy, scale)
    dmask = None if mshape is None else np.zeros(mshape, dtype=jax.dtypes.float0)
    return dx, dmask


_softmax.defvjp(_softmax_fwd, _softmax_bwd)


def scaled_masked_softmax(x, mask: Optional[jnp.ndarray] = None, *,
                          scale: float = 1.0, causal: bool = False):
    """``softmax(scale*x + mask)`` — ``ScaledMaskedSoftmax`` (U).

    ``x``: ``[b, h, sq, sk]`` (or any ``[..., sq, sk]``); ``mask``: boolean
    or 0/1, nonzero = masked out, any shape broadcastable to ``x`` over
    the leading/head/query dims (``[b, 1, sq, sk]``, ``[b, 1, 1, sk]``
    padding masks, ``[b, sq, sk]``, …). Softmax in fp32 regardless of
    I/O dtype. ``causal=True`` additionally composes the upper-triangular
    mask inside the kernel (no materialised triangle; square scores only,
    like the dedicated causal variant).
    """
    shape = x.shape
    sq, sk = shape[-2], shape[-1]
    if causal and sq != sk:
        raise ValueError(
            f"causal softmax requires square scores, got {sq}x{sk}")
    x, was16 = widen_f16(x)
    x3 = x.reshape(-1, sq, sk)
    m3 = None
    if mask is not None:
        m = jnp.asarray(mask)
        if m.ndim > x.ndim:
            raise ValueError(
                f"mask rank {m.ndim} exceeds scores rank {x.ndim}")
        if m.ndim == x.ndim - 1 and x.ndim >= 4 and m.shape[0] == shape[0]:
            m = m[:, None]  # legacy [b, sq, sk] over [b, h, sq, sk]
        while m.ndim < x.ndim:
            m = m[None]
        # Materialise sq/sk (cheap next to the scores) and any interior
        # broadcast dim, but keep *trailing* size-1 leading dims (head,
        # ...) unmaterialised: the kernel ratio-tiles them (mask block
        # index = i // (B_x / B_m)) without the h× mask copy.
        lead = m.shape[:-2]
        cut = len(lead)
        while cut > 0 and lead[cut - 1] == 1:
            cut -= 1
        tgt = shape[:cut] + (1,) * (len(lead) - cut) + (sq, sk)
        # incompatible masks fail here with jax's broadcast error; the
        # resulting batch prod(shape[:cut]) always divides x3's
        m3 = jnp.broadcast_to(m, tgt).reshape(-1, sq, sk)
    y = _softmax(x3, m3, float(scale), bool(causal)).reshape(shape)
    return y.astype(jnp.float16) if was16 else y


def scaled_upper_triang_masked_softmax(x, *, scale: float = 1.0):
    """Causal ``softmax(scale*x)`` over the last two dims —
    ``ScaledUpperTriangMaskedSoftmax`` (U). Requires ``sq == sk``."""
    shape = x.shape
    sq, sk = shape[-2], shape[-1]
    if sq != sk:
        raise ValueError(f"causal softmax requires square scores, got {sq}x{sk}")
    x, was16 = widen_f16(x)
    x3 = x.reshape(-1, sq, sk)
    y = _softmax(x3, None, float(scale), True).reshape(shape)
    return y.astype(jnp.float16) if was16 else y


#: generic_scaled_masked_softmax_cuda [era] (U) — the reference's third
#: variant lifts its seq-len-template and mask-broadcast restrictions;
#: the Pallas kernel never had them, so the generic name is the same op
#: (the CamelCase autograd-Function name lives in transformer.functional
#: with its siblings).
generic_scaled_masked_softmax = scaled_masked_softmax
