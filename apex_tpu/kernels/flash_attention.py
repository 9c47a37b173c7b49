"""Blockwise (flash) attention Pallas kernels — forward + backward.

TPU-native replacement for apex's attention extensions: contrib fmha
(CUTLASS fixed-seqlen ≤512, apex/contrib/csrc/fmha/* (U)) and
fast_multihead_attn (apex/contrib/csrc/multihead_attn/* (U)). Instead of
per-seqlen templates, one online-softmax blockwise kernel:

- forward: streams K/V blocks through VMEM, keeping running (max, sum,
  accumulator) per Q block — O(sq·d) memory, any sequence length;
- backward: recomputes P = exp(S - lse) per block from the saved per-row
  log-sum-exp (no sq×sk materialisation). Two strategies, numerically
  identical: a fused single sweep that recomputes S/P once per (j, i)
  block and produces dQ/dK/dV together (dQ accumulates in a full-length
  VMEM scratch — TPU grids are sequential, so the accumulation is
  race-free), used whenever that scratch fits VMEM; and a two-sweep
  fallback (dQ; dK/dV) for very long sequences, which recomputes S/P
  twice but needs only block-sized scratch. ``APEX_TPU_FLASH_BWD=
  fused|split|auto`` overrides the automatic choice (debugging/A-B).

Supports causal masking and per-batch key-padding lengths (the capability
behind fmha's var-seqlen batch packing). Softmax statistics are always
fp32; matmuls run in the input dtype on the MXU with fp32 accumulation.

Two data layouts share the block math:

- ``flash_attention`` — head-major ``[b, heads, s, head_dim]`` (the
  generic public API; any head_dim);
- ``flash_attention_bsh`` — lane-packed ``[b, s, hidden]`` (the model
  fast path): each grid cell owns a 128-lane group of ``128 // head_dim``
  heads, so at head_dim < 128 nothing in HBM is lane-padded and the model
  never transposes to head-major form. Implements the fused backward
  only; ``APEX_TPU_FLASH_BWD=split`` routes it through the head-major
  path so the override contract holds everywhere. Measured on the 355M
  GPT bench this layout is +15% whole-step (docs/DESIGN.md).
"""

from __future__ import annotations

import functools
import os
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from apex_tpu.kernels._utils import LANE, round_up, use_interpret, widen_f16

_NEG = -1e30
_LANES = 128  # stat scratch lane width
# default tile sizes; overridable per call (chosen from whole-step
# timings on v5e under an earlier runtime: 512x512 was fastest for both
# directions in-model; not re-measured on the current one)
_DEFAULT_BLOCK_Q = 512
_DEFAULT_BLOCK_K = 512
_DEFAULT_BLOCK_Q_BWD = 512
_DEFAULT_BLOCK_K_BWD = 512
# fused-backward dQ scratch budget: the single-sweep kernel keeps the
# whole (padded_seq, head_dim) fp32 dQ accumulator resident in VMEM;
# beyond this it falls back to the two-sweep backward
_FUSED_DQ_VMEM_BYTES = 4 * 1024 * 1024


def _row_ids(bq: int, width: int, i):
    return lax.broadcasted_iota(jnp.int32, (bq, width), 0) + i * bq


def _col_ids(bq: int, bk: int, j):
    return lax.broadcasted_iota(jnp.int32, (bq, bk), 1) + j * bk


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _online_update(s, valid, m_prev, l_prev, acc, v):
    """One online-softmax block update shared by both forward kernels:
    fold masked scores ``s`` into running (max, sum, accumulator).
    Returns (m_new, l_new, acc_new)."""
    m_cur = jnp.max(s, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    corr = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)
    p = jnp.where(valid, p, 0.0)                       # kill all-masked rows
    l_new = corr * l_prev + jnp.sum(p, axis=-1, keepdims=True)
    acc_new = acc * corr + jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    return m_new, l_new, acc_new


def _fwd_kernel(len_ref, segq_ref, segk_ref, q_ref, k_ref, v_ref, o_ref,
                lse_ref, acc_ref, m_ref, l_ref, *, scale, causal, bq, bk,
                sk, sq):
    i = pl.program_id(1)
    j = pl.program_id(2)
    nk = pl.num_programs(2)
    # SMEM reads + program_id must stay out of pl.when bodies: a traced
    # predicate becomes lax.cond in interpret mode, where program_id
    # can't lower
    blen = None if len_ref is None else len_ref[pl.program_id(0)]

    @pl.when(j == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, _NEG)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    compute = _causal_skip(causal, i, j, bq, bk)

    @pl.when(compute)
    def _block():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # (bq, bk)
        segs = (None if segq_ref is None
                else (segq_ref[:], segk_ref[:]))
        valid = _valid_cols(blen, i, j, causal=causal, bq=bq, bk=bk, sk=sk,
                            segs=segs)
        s = jnp.where(valid, s, _NEG)
        m_new, l_new, acc = _online_update(
            s, valid, m_ref[:, :1], l_ref[:, :1], acc_ref[:], v)
        acc_ref[:] = acc
        m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[:] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(j == nk - 1)
    def _finish():
        l = l_ref[:, :1]
        o_ref[0] = (acc_ref[:] / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)
        lse_ref[0] = m_ref[:] + jnp.log(jnp.maximum(l, 1e-30))


# ---------------------------------------------------------------------------
# backward: fused single sweep (default), or dQ sweep + dK/dV sweep
# ---------------------------------------------------------------------------

def _causal_skip(causal, i, j, bq, bk):
    """Block-level causal skip: K blocks entirely above the diagonal of
    q block ``i`` contribute nothing (shared by all four kernels)."""
    return (j * bk < (i + 1) * bq) if causal else True


def _valid_cols(blen, i, j, *, causal, bq, bk, sk, segs=None):
    """The composed (padding ∧ length ∧ segment ∧ causal) column mask
    for block (i, j) — the single source of masking truth for every
    kernel in this module (head-major and lane-packed, forward and
    backward). ``segs`` is an optional ``((1, bq), (1, bk))`` int32 pair
    of per-row/per-column segment ids: rows attend only to columns of
    the same segment (the cu_seqlens-style packed-batch masking of the
    reference's fmha var-seqlen path, apex/contrib/fmha (U))."""
    col = _col_ids(bq, bk, j)
    valid = col < sk
    if blen is not None:
        valid = valid & (col < blen)
    if segs is not None:
        seg_q, seg_k = segs
        valid = valid & (jnp.transpose(seg_q) == seg_k)
    if causal:
        valid = valid & (col <= _row_ids(bq, bk, i))
    return valid


def _p_ds(q, k, v, do, lse, delta, valid, *, scale):
    """Shared backward block math on block values: recompute
    P = exp(S - lse) under ``valid`` and the dS it induces. Every
    backward kernel (both layouts) routes through here.

    P and dS are computed in fp32 on the VPU but returned in the input
    dtype: the four downstream MXU dots (dP, dV, dK, dQ) then run at the
    native bf16 rate with fp32 accumulation (``preferred_element_type``)
    instead of as multi-pass fp32-emulated matmuls — the standard
    flash-attention backward numerics (fmha/flash-attn round P/dS to the
    IO dtype for exactly these products)."""
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale
    p = jnp.where(valid, jnp.exp(s - lse), 0.0)
    dp = jax.lax.dot_general(
        do, v, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    ds = (p * (dp - delta) * scale).astype(q.dtype)
    return p.astype(q.dtype), ds


def _bwd_p_ds(blen, segs, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
              i, j, *, scale, causal, bq, bk, sk):
    """Head-major backward block: read refs, apply the shared mask/math."""
    q = q_ref[0]
    k = k_ref[0]
    v = v_ref[0]
    do = do_ref[0]
    lse = lse_ref[0][:, :1]
    delta = delta_ref[0][:, :1]
    valid = _valid_cols(blen, i, j, causal=causal, bq=bq, bk=bk, sk=sk,
                        segs=segs)
    p, ds = _p_ds(q, k, v, do, lse, delta, valid, scale=scale)
    return q, k, do, p, ds


def _dq_kernel(len_ref, segq_ref, segk_ref, q_ref, k_ref, v_ref, do_ref,
               lse_ref, delta_ref, dq_ref, acc_ref, *, scale, causal, bq,
               bk, sk):
    i = pl.program_id(1)
    j = pl.program_id(2)
    nk = pl.num_programs(2)
    blen = None if len_ref is None else len_ref[pl.program_id(0)]

    @pl.when(j == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    compute = _causal_skip(causal, i, j, bq, bk)

    @pl.when(compute)
    def _block():
        segs = (None if segq_ref is None
                else (segq_ref[:], segk_ref[:]))
        _, k, _, _, ds = _bwd_p_ds(
            blen, segs, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
            i, j, scale=scale, causal=causal, bq=bq, bk=bk, sk=sk)
        acc_ref[:] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(j == nk - 1)
    def _finish():
        dq_ref[0] = acc_ref[:].astype(dq_ref.dtype)


def _dkv_kernel(len_ref, segq_ref, segk_ref, q_ref, k_ref, v_ref, do_ref,
                lse_ref, delta_ref, dk_ref, dv_ref, dk_acc, dv_acc, *,
                scale, causal, bq, bk, sk):
    j = pl.program_id(1)   # k block
    i = pl.program_id(2)   # q block (innermost sweep)
    nq = pl.num_programs(2)
    blen = None if len_ref is None else len_ref[pl.program_id(0)]

    @pl.when(i == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    compute = _causal_skip(causal, i, j, bq, bk)

    @pl.when(compute)
    def _block():
        segs = (None if segq_ref is None
                else (segq_ref[:], segk_ref[:]))
        q, _, do, p, ds = _bwd_p_ds(
            blen, segs, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
            i, j, scale=scale, causal=causal, bq=bq, bk=bk, sk=sk)
        dv_acc[:] += jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)           # (bk, d)
        dk_acc[:] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)           # (bk, d)

    @pl.when(i == nq - 1)
    def _finish():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _dqkv_kernel(len_ref, segq_ref, segk_ref, q_ref, k_ref, v_ref, do_ref,
                 lse_ref, delta_ref, dq_ref, dk_ref, dv_ref, dq_acc,
                 dk_acc, dv_acc, *, scale, causal, bq, bk, sk):
    """Fused backward: one S/P recompute per (j, i) block yields dQ, dK
    and dV together. Grid (bh, nk, nq) — k block outer, q block inner —
    so dK/dV reduce in block scratch exactly like ``_dkv_kernel``, while
    dQ accumulates into a full-length VMEM scratch across the outer k
    sweep (sequential grid ⇒ no races). Two of the seven per-block
    matmuls of the two-sweep backward (S and dP in the dQ sweep) are
    eliminated, and q/do/lse/delta are read once instead of twice."""
    j = pl.program_id(1)   # k block (outer)
    i = pl.program_id(2)   # q block (inner)
    nq = pl.num_programs(2)
    blen = None if len_ref is None else len_ref[pl.program_id(0)]

    @pl.when((j == 0) & (i == 0))
    def _init_dq():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    @pl.when(i == 0)
    def _init_dkv():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    rows = pl.dslice(i * bq, bq)
    compute = _causal_skip(causal, i, j, bq, bk)

    @pl.when(compute)
    def _block():
        segs = (None if segq_ref is None
                else (segq_ref[:], segk_ref[:]))
        q, k, do, p, ds = _bwd_p_ds(
            blen, segs, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
            i, j, scale=scale, causal=causal, bq=bq, bk=bk, sk=sk)
        dv_acc[:] += jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)           # (bk, d)
        dk_acc[:] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)           # (bk, d)
        dq_acc[rows] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)           # (bq, d)

    # dq out block (b, i) is flushed on every visit (i is the innermost
    # grid dim); write the running partial so every flush is valid — the
    # final (j = last k block) flush lands last and is the complete dQ
    dq_ref[0] = dq_acc[rows].astype(dq_ref.dtype)

    @pl.when(i == nq - 1)
    def _finish_dkv():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


# ---------------------------------------------------------------------------
# host-side plumbing
# ---------------------------------------------------------------------------

def _pad_qkv(x, sp, dp):
    b, s, d = x.shape
    if s == sp and d == dp:
        return x
    return jnp.pad(x, ((0, 0), (0, sp - s), (0, dp - d)))


def _fit_block(want: int, seq: int) -> int:
    """Largest tile ≤ ``want`` that doesn't pad ``seq`` by more than a
    quarter (misaligned lengths — the var-seqlen use case — would
    otherwise compute up to a whole masked-out extra tile)."""
    b = min(want, round_up(seq, 8))
    while b > 128 and round_up(seq, b) - seq > seq // 4:
        b //= 2
    return b


def _blocks(sq, sk, d, *, block_q=None, block_k=None):
    bq = _fit_block(block_q or _DEFAULT_BLOCK_Q, sq)
    bk = _fit_block(block_k or _DEFAULT_BLOCK_K, sk)
    dp = round_up(d, LANE)
    return bq, bk, dp


def _stat_spec(bq):
    return pl.BlockSpec((1, bq, _LANES), lambda b, i, j: (b, i, 0),
                        memory_space=pltpu.VMEM)


def _len_spec():
    # whole lengths array in SMEM: per-block scalar specs fail Mosaic's
    # tile-shape checks on real TPU (only exercised interpreted before);
    # kernels index it with pl.program_id(0)
    return pl.BlockSpec(memory_space=pltpu.SMEM)


def _run_fwd(q, k, v, lengths, segments, scale, causal, block_q=None,
             block_k=None, n_rep=1):
    bh, sq, d = q.shape
    sk = k.shape[1]
    bq, bk, dp = _blocks(sq, sk, d, block_q=block_q, block_k=block_k)
    sqp, skp = round_up(sq, bq), round_up(sk, bk)
    qp = _pad_qkv(q, sqp, dp)
    kp = _pad_qkv(k, skp, dp)
    vp = _pad_qkv(v, skp, dp)
    grid = (bh, sqp // bq, skp // bk)
    qspec = pl.BlockSpec((1, bq, dp), lambda b, i, j: (b, i, 0),
                         memory_space=pltpu.VMEM)
    kspec = pl.BlockSpec((1, bk, dp), lambda b, i, j: (b, j, 0),
                         memory_space=pltpu.VMEM)
    in_specs = [qspec, kspec, kspec]
    operands = [qp, kp, vp]
    if segments is not None:
        seg_q, seg_k = segments
        sqs, sks = _seg_specs(bq, bk, n_rep, "bij")
        in_specs = [sqs, sks] + in_specs
        operands = [_pad_seg(seg_q, sqp), _pad_seg(seg_k, skp)] + operands
    if lengths is not None:
        in_specs = [_len_spec()] + in_specs
        operands = [lengths.reshape(bh).astype(jnp.int32)] + operands
    kernel = _bind_aux(_fwd_kernel, lengths is not None,
                       segments is not None)
    out, lse = pl.pallas_call(
        functools.partial(kernel, scale=scale, causal=causal,
                          bq=bq, bk=bk, sk=sk, sq=sq),
        grid=grid,
        in_specs=in_specs,
        out_specs=[qspec, _stat_spec(bq)],
        out_shape=[
            jax.ShapeDtypeStruct((bh, sqp, dp), q.dtype),
            jax.ShapeDtypeStruct((bh, sqp, _LANES), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, dp), jnp.float32),
            pltpu.VMEM((bq, _LANES), jnp.float32),
            pltpu.VMEM((bq, _LANES), jnp.float32),
        ],
        name="flash_attn_fwd",
        interpret=use_interpret(),
    )(*operands)
    return out[:, :sq, :d], lse[:, :sq, :1]


def _bind_aux(kernel, has_len, has_seg):
    """Adapt a ``(len_ref, segq_ref, segk_ref, *refs)`` kernel to the
    subset of aux operands actually passed. Operand order when present:
    lengths first, then seg_q, seg_k, then the tensor refs."""
    if has_len and has_seg:
        return kernel
    if has_len:
        return lambda len_ref, *refs, **kw: kernel(
            len_ref, None, None, *refs, **kw)
    if has_seg:
        return lambda sq_ref, sk_ref, *refs, **kw: kernel(
            None, sq_ref, sk_ref, *refs, **kw)
    return lambda *refs, **kw: kernel(None, None, None, *refs, **kw)


def _seg_specs(bq, bk, n_rep, order):
    """Block specs for the per-row / per-column segment-id operands.
    The id arrays are ``[b, s]``; grid dim 0 runs over ``b * n_rep``
    (heads or lane-groups), so the index map divides it back down.
    ``order`` is "bij" for (b, q-block, k-block) grids and "bji" for
    (b, k-block, q-block) grids."""
    if order == "bij":
        qmap = lambda b, i, j: (_div(b, n_rep), i)     # noqa: E731
        kmap = lambda b, i, j: (_div(b, n_rep), j)     # noqa: E731
    else:
        qmap = lambda b, j, i: (_div(b, n_rep), i)     # noqa: E731
        kmap = lambda b, j, i: (_div(b, n_rep), j)     # noqa: E731
    return (pl.BlockSpec((1, bq), qmap, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, bk), kmap, memory_space=pltpu.VMEM))


def _pad_seg(seg, sp):
    """Pad a [b, s] segment-id array to [b, sp] with -1 (matches no
    real segment; padded columns are additionally masked by col < sk)."""
    b, s = seg.shape
    seg = seg.astype(jnp.int32)
    if s == sp:
        return seg
    return jnp.pad(seg, ((0, 0), (0, sp - s)), constant_values=-1)


def _run_bwd(q, k, v, do, lse, delta, lengths, segments, scale, causal,
             block_q=None, block_k=None, n_rep=1):
    bh, sq, d = q.shape
    sk = k.shape[1]
    bq, bk, dp = _blocks(sq, sk, d,
                         block_q=block_q or _DEFAULT_BLOCK_Q_BWD,
                         block_k=block_k or _DEFAULT_BLOCK_K_BWD)
    sqp, skp = round_up(sq, bq), round_up(sk, bk)
    qp, dop = _pad_qkv(q, sqp, dp), _pad_qkv(do, sqp, dp)
    kp, vp = _pad_qkv(k, skp, dp), _pad_qkv(v, skp, dp)
    # stats: (bh, sqp, LANES), lane-replicated; padded rows get lse=0,
    # delta=0 → p rows are harmless (their ds lands in padded dq rows)
    lsep = jnp.pad(lse, ((0, 0), (0, sqp - sq), (0, 0)))
    lsep = jnp.broadcast_to(lsep, (bh, sqp, _LANES))
    deltap = jnp.pad(delta, ((0, 0), (0, sqp - sq), (0, 0)))
    deltap = jnp.broadcast_to(deltap, (bh, sqp, _LANES))

    qspec = pl.BlockSpec((1, bq, dp), lambda b, i, j: (b, i, 0),
                         memory_space=pltpu.VMEM)
    kspec = pl.BlockSpec((1, bk, dp), lambda b, i, j: (b, j, 0),
                         memory_space=pltpu.VMEM)
    sspec = _stat_spec(bq)
    lens = None
    if lengths is not None:
        lens = lengths.reshape(bh).astype(jnp.int32)

    # (b, j, i)-ordered spec family, shared by the fused single sweep and
    # the two-sweep fallback's dK/dV pass (both run k blocks outermost)
    qspec2 = pl.BlockSpec((1, bq, dp), lambda b, j, i: (b, i, 0),
                          memory_space=pltpu.VMEM)
    kspec2 = pl.BlockSpec((1, bk, dp), lambda b, j, i: (b, j, 0),
                          memory_space=pltpu.VMEM)
    sspec2 = pl.BlockSpec((1, bq, _LANES), lambda b, j, i: (b, i, 0),
                          memory_space=pltpu.VMEM)
    lenspec2 = _len_spec()

    mode = os.environ.get("APEX_TPU_FLASH_BWD", "auto")
    if mode not in ("auto", "fused", "split"):
        raise ValueError(
            f"APEX_TPU_FLASH_BWD={mode!r}: expected auto, fused or split")
    fused = (mode == "fused" or
             (mode != "split" and sqp * dp * 4 <= _FUSED_DQ_VMEM_BYTES))
    segp = None
    if segments is not None:
        seg_q, seg_k = segments
        segp = (_pad_seg(seg_q, sqp), _pad_seg(seg_k, skp))

    if fused:
        # --- fused single sweep: grid (bh, nk, nq) -----------------------
        in_specs = [qspec2, kspec2, kspec2, qspec2, sspec2, sspec2]
        operands = [qp, kp, vp, dop, lsep, deltap]
        if segp is not None:
            sqs, sks = _seg_specs(bq, bk, n_rep, "bji")
            in_specs = [sqs, sks] + in_specs
            operands = list(segp) + operands
        if lens is not None:
            in_specs = [lenspec2] + in_specs
            operands = [lens] + operands
        kernel = _bind_aux(_dqkv_kernel, lens is not None,
                           segp is not None)
        dq, dk, dv = pl.pallas_call(
            functools.partial(kernel, scale=scale, causal=causal,
                              bq=bq, bk=bk, sk=sk),
            grid=(bh, skp // bk, sqp // bq),
            in_specs=in_specs,
            out_specs=[qspec2, kspec2, kspec2],
            out_shape=[
                jax.ShapeDtypeStruct((bh, sqp, dp), jnp.float32),
                jax.ShapeDtypeStruct((bh, skp, dp), jnp.float32),
                jax.ShapeDtypeStruct((bh, skp, dp), jnp.float32),
            ],
            scratch_shapes=[
                pltpu.VMEM((sqp, dp), jnp.float32),
                pltpu.VMEM((bk, dp), jnp.float32),
                pltpu.VMEM((bk, dp), jnp.float32),
            ],
            name="flash_attn_bwd",
            interpret=use_interpret(),
        )(*operands)
        return (dq[:, :sq, :d].astype(q.dtype),
                dk[:, :sk, :d].astype(k.dtype),
                dv[:, :sk, :d].astype(v.dtype))

    # --- dQ sweep: grid (bh, nq, nk) -------------------------------------
    in_specs = [qspec, kspec, kspec, qspec, sspec, sspec]
    operands = [qp, kp, vp, dop, lsep, deltap]
    if segp is not None:
        sqs, sks = _seg_specs(bq, bk, n_rep, "bij")
        in_specs = [sqs, sks] + in_specs
        operands = list(segp) + operands
    if lens is not None:
        in_specs = [_len_spec()] + in_specs
        operands = [lens] + operands
    dq_kernel = _bind_aux(_dq_kernel, lens is not None, segp is not None)
    dq = pl.pallas_call(
        functools.partial(dq_kernel, scale=scale, causal=causal,
                          bq=bq, bk=bk, sk=sk),
        grid=(bh, sqp // bq, skp // bk),
        in_specs=in_specs,
        out_specs=qspec,
        out_shape=jax.ShapeDtypeStruct((bh, sqp, dp), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bq, dp), jnp.float32)],
        name="flash_attn_bwd_dq",
        interpret=use_interpret(),
    )(*operands)

    # --- dK/dV sweep: grid (bh, nk, nq) ----------------------------------
    in_specs2 = [qspec2, kspec2, kspec2, qspec2, sspec2, sspec2]
    operands2 = [qp, kp, vp, dop, lsep, deltap]
    if segp is not None:
        sqs, sks = _seg_specs(bq, bk, n_rep, "bji")
        in_specs2 = [sqs, sks] + in_specs2
        operands2 = list(segp) + operands2
    if lens is not None:
        in_specs2 = [lenspec2] + in_specs2
        operands2 = [lens] + operands2
    dkv_kernel = _bind_aux(_dkv_kernel, lens is not None, segp is not None)
    dk, dv = pl.pallas_call(
        functools.partial(dkv_kernel, scale=scale, causal=causal,
                          bq=bq, bk=bk, sk=sk),
        grid=(bh, skp // bk, sqp // bq),
        in_specs=in_specs2,
        out_specs=[kspec2, kspec2],
        out_shape=[
            jax.ShapeDtypeStruct((bh, skp, dp), jnp.float32),
            jax.ShapeDtypeStruct((bh, skp, dp), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk, dp), jnp.float32),
            pltpu.VMEM((bk, dp), jnp.float32),
        ],
        name="flash_attn_bwd_dkv",
        interpret=use_interpret(),
    )(*operands2)
    return (dq[:, :sq, :d].astype(q.dtype),
            dk[:, :sk, :d].astype(k.dtype),
            dv[:, :sk, :d].astype(v.dtype))


# ---------------------------------------------------------------------------
# public API
# ---------------------------------------------------------------------------

def _aux_zeros(lengths, segments):
    """float0 cotangents for the integer aux operands (lengths, segs)."""
    import numpy as np

    dlen = None
    if lengths is not None:
        dlen = np.zeros(lengths.shape, dtype=jax.dtypes.float0)
    dseg = None
    if segments is not None:
        dseg = tuple(np.zeros(s.shape, dtype=jax.dtypes.float0)
                     for s in segments)
    return dlen, dseg


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9))
def _flash(q3, k3, v3, lengths, segs, scale, causal, block_q, block_k,
           n_rep):
    out, _ = _run_fwd(q3, k3, v3, lengths, segs, scale, causal, block_q,
                      block_k, n_rep)
    return out


def _flash_fwd(q3, k3, v3, lengths, segs, scale, causal, block_q, block_k,
               n_rep):
    out, lse = _run_fwd(q3, k3, v3, lengths, segs, scale, causal, block_q,
                        block_k, n_rep)
    # named so remat policies can pin the kernel's residuals: with
    # save_only_these_names("flash_out", "flash_lse") the backward replay
    # restores (out, lse) instead of re-running the forward kernel
    out = checkpoint_name(out, "flash_out")
    lse = checkpoint_name(lse, "flash_lse")
    return out, (q3, k3, v3, out, lse, lengths, segs)


def _flash_bwd(scale, causal, block_q, block_k, n_rep, res, do):
    q3, k3, v3, out, lse, lengths, segs = res
    delta = jnp.sum(out.astype(jnp.float32) * do.astype(jnp.float32),
                    axis=-1, keepdims=True)
    dq, dk, dv = _run_bwd(q3, k3, v3, do, lse, delta, lengths, segs, scale,
                          causal, block_q, block_k, n_rep)
    dlen, dseg = _aux_zeros(lengths, segs)
    return dq, dk, dv, dlen, dseg


_flash.defvjp(_flash_fwd, _flash_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9))
def _flash_with_lse(q3, k3, v3, lengths, segs, scale, causal, block_q,
                    block_k, n_rep):
    return _run_fwd(q3, k3, v3, lengths, segs, scale, causal, block_q,
                    block_k, n_rep)


def _flash_with_lse_fwd(q3, k3, v3, lengths, segs, scale, causal, block_q,
                        block_k, n_rep):
    out, lse = _run_fwd(q3, k3, v3, lengths, segs, scale, causal, block_q,
                        block_k, n_rep)
    out = checkpoint_name(out, "flash_out")
    lse = checkpoint_name(lse, "flash_lse")
    return (out, lse), (q3, k3, v3, out, lse, lengths, segs)


def _flash_with_lse_bwd(scale, causal, block_q, block_k, n_rep, res, cts):
    """Like ``_flash_bwd`` but the log-sum-exp is a live output with its
    own cotangent. Since d(lse)/ds_j = p_j, the dlse term folds into the
    existing kernel as ds_j = p_j (dp_j - (delta - dlse)) — the backward
    kernels run unchanged on an adjusted delta."""
    q3, k3, v3, out, lse, lengths, segs = res
    do, dlse = cts
    delta = jnp.sum(out.astype(jnp.float32) * do.astype(jnp.float32),
                    axis=-1, keepdims=True)
    delta = delta - dlse.astype(jnp.float32)
    dq, dk, dv = _run_bwd(q3, k3, v3, do, lse, delta, lengths, segs, scale,
                          causal, block_q, block_k, n_rep)
    dlen, dseg = _aux_zeros(lengths, segs)
    return dq, dk, dv, dlen, dseg


_flash_with_lse.defvjp(_flash_with_lse_fwd, _flash_with_lse_bwd)


def _seg_pair(segment_ids, kv_segment_ids, b, sq, sk):
    """Normalise the public segment-id arguments to an int32
    ``([b, sq], [b, sk])`` pair (or None)."""
    if segment_ids is None and kv_segment_ids is None:
        return None
    seg_q = jnp.asarray(
        segment_ids if segment_ids is not None else kv_segment_ids,
        jnp.int32)
    seg_k = jnp.asarray(
        kv_segment_ids if kv_segment_ids is not None else segment_ids,
        jnp.int32)
    if seg_q.shape != (b, sq) or seg_k.shape != (b, sk):
        raise ValueError(
            f"segment_ids {seg_q.shape} / kv_segment_ids {seg_k.shape} "
            f"must be [batch, seq] = ({b}, {sq}) / ({b}, {sk})")
    return seg_q, seg_k


def flash_attention_with_lse(
    q, k, v, *,
    causal: bool = False,
    scale: Optional[float] = None,
    kv_lengths: Optional[jnp.ndarray] = None,
    segment_ids: Optional[jnp.ndarray] = None,
    kv_segment_ids: Optional[jnp.ndarray] = None,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
):
    """Like :func:`flash_attention` but also returns the per-row
    log-sum-exp ``[b, heads, sq]`` (fp32) — the mergeable form blockwise/
    ring consumers need: partials ``(out_i, lse_i)`` over disjoint K/V
    shards combine exactly via softmax-weighted averaging on ``lse``.
    Fully differentiable in both outputs (the lse cotangent rides the
    same backward kernels)."""
    if q.ndim != 4:
        raise ValueError(f"expected [b, h, s, d], got {q.shape}")
    b, h, sq, d = q.shape
    sk = k.shape[2]
    if causal and sq != sk:
        raise ValueError("causal attention requires sq == sk")
    s = float(scale) if scale is not None else 1.0 / d ** 0.5
    q, was16 = widen_f16(q)
    k, _ = widen_f16(k)
    v, _ = widen_f16(v)
    lens = None
    if kv_lengths is not None:
        lens = jnp.repeat(jnp.asarray(kv_lengths, jnp.int32), h)
    segs = _seg_pair(segment_ids, kv_segment_ids, b, sq, sk)
    out, lse = _flash_with_lse(
        q.reshape(b * h, sq, d), k.reshape(b * h, sk, d),
        v.reshape(b * h, sk, d), lens, segs, s, causal, block_q, block_k, h)
    out = out.reshape(b, h, sq, d)
    lse = lse.reshape(b, h, sq)
    return (out.astype(jnp.float16) if was16 else out), lse


def flash_attention(
    q, k, v, *,
    causal: bool = False,
    scale: Optional[float] = None,
    kv_lengths: Optional[jnp.ndarray] = None,
    segment_ids: Optional[jnp.ndarray] = None,
    kv_segment_ids: Optional[jnp.ndarray] = None,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
):
    """Blockwise attention over ``[batch, heads, seq, head_dim]`` inputs.

    - ``causal``: upper-triangular masking (decoder self-attention).
    - ``scale``: softmax temperature; default ``1/sqrt(head_dim)``.
    - ``kv_lengths``: optional ``[batch]`` int — keys/values beyond the
      per-example length are masked (fmha var-seqlen capability (U)).
    - ``segment_ids`` (+ optional ``kv_segment_ids``): ``[batch, seq]``
      int — rows attend only to keys with the same id, i.e. several
      packed sequences per batch row are isolated from each other (the
      reference fmha's cu_seqlens var-seqlen batch packing (U)).
      Composes with ``causal`` (per-document causal) and
      ``kv_lengths``.
    - ``block_q``/``block_k``: tile-size overrides (defaults tuned for
      v5e; shrink for tiny VMEM budgets or very small head_dim).

    Returns attention output of the same shape/dtype as ``q``.
    """
    if q.ndim != 4:
        raise ValueError(f"expected [b, h, s, d], got {q.shape}")
    b, h, sq, d = q.shape
    sk = k.shape[2]
    if causal and sq != sk:
        raise ValueError("causal attention requires sq == sk")
    s = float(scale) if scale is not None else 1.0 / d ** 0.5
    q, was16 = widen_f16(q)
    k, _ = widen_f16(k)
    v, _ = widen_f16(v)
    q3 = q.reshape(b * h, sq, d)
    k3 = k.reshape(b * h, sk, d)
    v3 = v.reshape(b * h, sk, d)
    lens = None
    if kv_lengths is not None:
        lens = jnp.repeat(jnp.asarray(kv_lengths, jnp.int32), h)
    segs = _seg_pair(segment_ids, kv_segment_ids, b, sq, sk)
    out = _flash(q3, k3, v3, lens, segs, s, causal, block_q, block_k, h)
    out = out.reshape(b, h, sq, d)
    return out.astype(jnp.float16) if was16 else out


def mha(q, k, v, *, causal=False, scale=None, kv_lengths=None,
        segment_ids=None):
    """[b, s, h, d] layout convenience wrapper (fast_multihead_attn's
    self-attn data layout (U))."""
    out = flash_attention(
        jnp.swapaxes(q, 1, 2), jnp.swapaxes(k, 1, 2), jnp.swapaxes(v, 1, 2),
        causal=causal, scale=scale, kv_lengths=kv_lengths,
        segment_ids=segment_ids)
    return jnp.swapaxes(out, 1, 2)


# ---------------------------------------------------------------------------
# lane-packed [batch, seq, hidden] layout (model-native fast path)
# ---------------------------------------------------------------------------
#
# The [b, h, s, d] kernels above force the model to transpose activations
# into head-major form, and at head_dim < 128 every HBM tensor they touch
# (q/k/v, out, dq/dk/dv) is laid out 2x padded (64 lanes in a 128-lane
# tile); the lane-replicated stats buffers are worse. The packed variant
# removes all of it: operands stay in the model's [b, s, hidden] layout
# (hidden minormost — tile-exact), each grid cell owns one 128-lane GROUP
# of ``128 // head_dim`` heads and lane-slices the sub-heads in VMEM, and
# the softmax stats travel as [b*groups, G, seq] (seq on lanes, no
# replication). Measured on the 355M bench this removes ~2 GB of pure
# layout traffic per layer-step (see docs/DESIGN.md).

def _group_geometry(hidden: int, num_heads: int):
    """(head_dim, heads_per_group, n_groups) or None if ineligible."""
    if hidden % num_heads:
        return None
    d = hidden // num_heads
    if d > LANE or LANE % d or hidden % LANE:
        return None
    g = LANE // d
    return d, g, hidden // LANE


def _bwd_mode() -> str:
    mode = os.environ.get("APEX_TPU_FLASH_BWD", "auto")
    if mode not in ("auto", "fused", "split"):
        raise ValueError(
            f"APEX_TPU_FLASH_BWD={mode!r}: expected auto, fused or split")
    return mode


def flash_bsh_eligible(hidden: int, num_heads: int, seq: int,
                       block_q: Optional[int] = None) -> bool:
    """True iff ``flash_attention_bsh`` will actually run the lane-packed
    kernels for this shape — the single source of truth for every
    fallback condition (geometry, the fused-dQ VMEM budget, and an
    explicit ``APEX_TPU_FLASH_BWD=split`` override). Model-level
    dispatchers should consult this instead of re-deriving eligibility."""
    if _group_geometry(hidden, num_heads) is None:
        return False
    if _bwd_mode() == "split":
        return False
    bq = _fit_block(block_q or _DEFAULT_BLOCK_Q_BWD, seq)
    return round_up(seq, bq) * LANE * 4 <= _FUSED_DQ_VMEM_BYTES


def _fwd_kernel_bsh(len_ref, segq_ref, segk_ref, q_ref, k_ref, v_ref,
                    o_ref, lse_ref, acc_ref, m_ref, l_ref, *, scale,
                    causal, bq, bk, sk, d, g, n_grp):
    i = pl.program_id(1)
    j = pl.program_id(2)
    nk = pl.num_programs(2)
    blen = None if len_ref is None else len_ref[pl.program_id(0) // n_grp]

    @pl.when(j == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, _NEG)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    compute = _causal_skip(causal, i, j, bq, bk)

    @pl.when(compute)
    def _block():
        segs = (None if segq_ref is None
                else (segq_ref[:], segk_ref[:]))
        valid = _valid_cols(blen, i, j, causal=causal, bq=bq, bk=bk, sk=sk,
                            segs=segs)
        for sub in range(g):
            lanes = slice(sub * d, (sub + 1) * d)
            q = q_ref[0][:, lanes]
            k = k_ref[0][:, lanes]
            v = v_ref[0][:, lanes]
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale   # (bq, bk)
            s = jnp.where(valid, s, _NEG)
            m_new, l_new, acc = _online_update(
                s, valid, m_ref[:, sub:sub + 1], l_ref[:, sub:sub + 1],
                acc_ref[:, lanes], v)
            acc_ref[:, lanes] = acc
            m_ref[:, sub:sub + 1] = m_new
            l_ref[:, sub:sub + 1] = l_new

    @pl.when(j == nk - 1)
    def _finish():
        for sub in range(g):
            lanes = slice(sub * d, (sub + 1) * d)
            l = l_ref[:, sub:sub + 1]
            o_ref[0, :, lanes] = (
                acc_ref[:, lanes] / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)
            lse = m_ref[:, sub:sub + 1] + jnp.log(jnp.maximum(l, 1e-30))
            lse_ref[0, sub:sub + 1, :] = jnp.transpose(lse)   # (1, bq)


def _dqkv_kernel_bsh(len_ref, segq_ref, segk_ref, q_ref, k_ref, v_ref,
                     do_ref, lse_ref, delta_ref, dq_ref, dk_ref, dv_ref,
                     dq_acc, dk_acc, dv_acc, *, scale, causal, bq, bk, sk,
                     d, g, n_grp):
    """Packed-layout fused backward — the ``_dqkv_kernel`` strategy (one
    S/P recompute per (j, i) block yields dQ/dK/dV; dQ rides a
    full-length VMEM scratch across the outer k sweep) applied per
    lane-group sub-head."""
    j = pl.program_id(1)   # k block (outer)
    i = pl.program_id(2)   # q block (inner)
    nq = pl.num_programs(2)

    @pl.when((j == 0) & (i == 0))
    def _init_dq():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    @pl.when(i == 0)
    def _init_dkv():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    rows = pl.dslice(i * bq, bq)
    blen = None if len_ref is None else len_ref[pl.program_id(0) // n_grp]
    compute = _causal_skip(causal, i, j, bq, bk)

    @pl.when(compute)
    def _block():
        segs = (None if segq_ref is None
                else (segq_ref[:], segk_ref[:]))
        valid = _valid_cols(blen, i, j, causal=causal, bq=bq, bk=bk, sk=sk,
                            segs=segs)
        for sub in range(g):
            lanes = slice(sub * d, (sub + 1) * d)
            q = q_ref[0][:, lanes]
            k = k_ref[0][:, lanes]
            v = v_ref[0][:, lanes]
            do = do_ref[0][:, lanes]
            lse = jnp.transpose(lse_ref[0][sub:sub + 1, :])    # (bq, 1)
            delta = jnp.transpose(delta_ref[0][sub:sub + 1, :])
            p, ds = _p_ds(q, k, v, do, lse, delta, valid, scale=scale)
            dv_acc[:, lanes] += jax.lax.dot_general(
                p, do, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)           # (bk, d)
            dk_acc[:, lanes] += jax.lax.dot_general(
                ds, q, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)           # (bk, d)
            dq_acc[rows, lanes] += jax.lax.dot_general(
                ds, k, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)           # (bq, d)

    # dq out block (bg, i) is flushed on every visit (i innermost); the
    # final (j = last) flush writes the complete dQ — see _dqkv_kernel
    dq_ref[0] = dq_acc[rows].astype(dq_ref.dtype)

    @pl.when(i == nq - 1)
    def _finish_dkv():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _pad_seq(x, sp):
    b, s, h = x.shape
    if s == sp:
        return x
    return jnp.pad(x, ((0, 0), (0, sp - s), (0, 0)))


def _div(a, n):
    """Truncating div/rem for index maps (indices are non-negative;
    Python ``//`` lowers to a floor-division select chain Pallas index
    maps reject)."""
    return lax.div(a, jnp.int32(n))


def _rem(a, n):
    return lax.rem(a, jnp.int32(n))


def _bsh_specs(bq, bk, n_grp):
    """Block specs over [b, s, hidden] operands and [b*n_grp, G, sq]
    stats, grid (b*n_grp, nq, nk) — dim0 picks (batch, lane-group)."""
    qspec = pl.BlockSpec(
        (1, bq, LANE), lambda bg, i, j: (_div(bg, n_grp), i, _rem(bg, n_grp)),
        memory_space=pltpu.VMEM)
    kspec = pl.BlockSpec(
        (1, bk, LANE), lambda bg, i, j: (_div(bg, n_grp), j, _rem(bg, n_grp)),
        memory_space=pltpu.VMEM)
    lenspec = _len_spec()
    return qspec, kspec, lenspec


def _run_fwd_bsh(q, k, v, lengths, segments, scale, causal, d, g, n_grp,
                 block_q=None, block_k=None):
    b, sq, hidden = q.shape
    sk = k.shape[1]
    bq = _fit_block(block_q or _DEFAULT_BLOCK_Q, sq)
    bk = _fit_block(block_k or _DEFAULT_BLOCK_K, sk)
    sqp, skp = round_up(sq, bq), round_up(sk, bk)
    qp = _pad_seq(q, sqp)
    kp, vp = _pad_seq(k, skp), _pad_seq(v, skp)
    qspec, kspec, lenspec = _bsh_specs(bq, bk, n_grp)
    lse_spec = pl.BlockSpec((1, g, bq), lambda bg, i, j: (bg, 0, i),
                            memory_space=pltpu.VMEM)
    in_specs = [qspec, kspec, kspec]
    operands = [qp, kp, vp]
    if segments is not None:
        seg_q, seg_k = segments
        sqs, sks = _seg_specs(bq, bk, n_grp, "bij")
        in_specs = [sqs, sks] + in_specs
        operands = [_pad_seg(seg_q, sqp), _pad_seg(seg_k, skp)] + operands
    if lengths is not None:
        in_specs = [lenspec] + in_specs
        operands = [lengths.reshape(b).astype(jnp.int32)] + operands
    kernel = _bind_aux(_fwd_kernel_bsh, lengths is not None,
                       segments is not None)
    out, lse = pl.pallas_call(
        functools.partial(kernel, scale=scale, causal=causal,
                          bq=bq, bk=bk, sk=sk, d=d, g=g, n_grp=n_grp),
        grid=(b * n_grp, sqp // bq, skp // bk),
        in_specs=in_specs,
        out_specs=[qspec, lse_spec],
        out_shape=[
            jax.ShapeDtypeStruct((b, sqp, hidden), q.dtype),
            jax.ShapeDtypeStruct((b * n_grp, g, sqp), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, LANE), jnp.float32),
            pltpu.VMEM((bq, g), jnp.float32),
            pltpu.VMEM((bq, g), jnp.float32),
        ],
        name="flash_attn_fwd_bsh",
        interpret=use_interpret(),
    )(*operands)
    return out[:, :sq], lse[:, :, :sq]


def _run_bwd_bsh(q, k, v, do, lse, delta, lengths, segments, scale, causal,
                 d, g, n_grp, block_q=None, block_k=None):
    b, sq, hidden = q.shape
    sk = k.shape[1]
    bq = _fit_block(block_q or _DEFAULT_BLOCK_Q_BWD, sq)
    bk = _fit_block(block_k or _DEFAULT_BLOCK_K_BWD, sk)
    sqp, skp = round_up(sq, bq), round_up(sk, bk)
    qp, dop = _pad_seq(q, sqp), _pad_seq(do, sqp)
    kp, vp = _pad_seq(k, skp), _pad_seq(v, skp)
    lsep = jnp.pad(lse, ((0, 0), (0, 0), (0, sqp - sq)))
    deltap = jnp.pad(delta, ((0, 0), (0, 0), (0, sqp - sq)))

    # (bg, j, i)-ordered specs: k blocks outer (dK/dV reduce in block
    # scratch), q blocks inner (dQ rides the full-length scratch)
    qspec2 = pl.BlockSpec(
        (1, bq, LANE), lambda bg, j, i: (_div(bg, n_grp), i, _rem(bg, n_grp)),
        memory_space=pltpu.VMEM)
    kspec2 = pl.BlockSpec(
        (1, bk, LANE), lambda bg, j, i: (_div(bg, n_grp), j, _rem(bg, n_grp)),
        memory_space=pltpu.VMEM)
    sspec2 = pl.BlockSpec((1, g, bq), lambda bg, j, i: (bg, 0, i),
                          memory_space=pltpu.VMEM)
    lenspec2 = _len_spec()
    in_specs = [qspec2, kspec2, kspec2, qspec2, sspec2, sspec2]
    operands = [qp, kp, vp, dop, lsep, deltap]
    if segments is not None:
        seg_q, seg_k = segments
        sqs, sks = _seg_specs(bq, bk, n_grp, "bji")
        in_specs = [sqs, sks] + in_specs
        operands = [_pad_seg(seg_q, sqp), _pad_seg(seg_k, skp)] + operands
    if lengths is not None:
        in_specs = [lenspec2] + in_specs
        operands = [lengths.reshape(b).astype(jnp.int32)] + operands
    kernel = _bind_aux(_dqkv_kernel_bsh, lengths is not None,
                       segments is not None)
    dq, dk, dv = pl.pallas_call(
        functools.partial(kernel, scale=scale, causal=causal,
                          bq=bq, bk=bk, sk=sk, d=d, g=g, n_grp=n_grp),
        grid=(b * n_grp, skp // bk, sqp // bq),
        in_specs=in_specs,
        out_specs=[qspec2, kspec2, kspec2],
        out_shape=[
            jax.ShapeDtypeStruct((b, sqp, hidden), q.dtype),
            jax.ShapeDtypeStruct((b, skp, hidden), k.dtype),
            jax.ShapeDtypeStruct((b, skp, hidden), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((sqp, LANE), jnp.float32),
            pltpu.VMEM((bk, LANE), jnp.float32),
            pltpu.VMEM((bk, LANE), jnp.float32),
        ],
        name="flash_attn_bwd_bsh",
        interpret=use_interpret(),
    )(*operands)
    return dq[:, :sq], dk[:, :sk], dv[:, :sk]


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9))
def _flash_bsh(q, k, v, lengths, segs, scale, causal, geom, block_q,
               block_k):
    out, _ = _run_fwd_bsh(q, k, v, lengths, segs, scale, causal, *geom,
                          block_q=block_q, block_k=block_k)
    return out


def _flash_bsh_fwd(q, k, v, lengths, segs, scale, causal, geom, block_q,
                   block_k):
    out, lse = _run_fwd_bsh(q, k, v, lengths, segs, scale, causal, *geom,
                            block_q=block_q, block_k=block_k)
    # same residual names as the [b,h,s,d] path so remat policies
    # (save_only_these_names) pin them identically
    out = checkpoint_name(out, "flash_out")
    lse = checkpoint_name(lse, "flash_lse")
    return out, (q, k, v, out, lse, lengths, segs)


def _flash_bsh_bwd(scale, causal, geom, block_q, block_k, res, do):
    q, k, v, out, lse, lengths, segs = res
    d, g, n_grp = geom
    b, sq, hidden = q.shape
    # per-head delta = sum_d(out * do): [b, s, n_grp, g] → [b*n_grp, g, s]
    prod = (out.astype(jnp.float32) * do.astype(jnp.float32)).reshape(
        b, sq, n_grp * g, d).sum(axis=-1)
    delta = jnp.transpose(prod.reshape(b, sq, n_grp, g), (0, 2, 3, 1))
    delta = delta.reshape(b * n_grp, g, sq)
    dq, dk, dv = _run_bwd_bsh(q, k, v, do, lse, delta, lengths, segs, scale,
                              causal, d, g, n_grp, block_q, block_k)
    dlen, dseg = _aux_zeros(lengths, segs)
    return dq, dk, dv, dlen, dseg


_flash_bsh.defvjp(_flash_bsh_fwd, _flash_bsh_bwd)


def flash_attention_bsh(
    q, k, v, *,
    num_heads: int,
    causal: bool = False,
    scale: Optional[float] = None,
    kv_lengths: Optional[jnp.ndarray] = None,
    segment_ids: Optional[jnp.ndarray] = None,
    kv_segment_ids: Optional[jnp.ndarray] = None,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
):
    """Blockwise attention over ``[batch, seq, hidden]`` inputs — the
    layout-native fast path (no head-major transposes, no head_dim < 128
    lane padding). ``hidden = num_heads * head_dim`` with heads laid out
    contiguously (head-major lanes). Falls back to the [b, h, s, d]
    kernel for geometries the lane-group packing can't express
    (head_dim > 128 or not a power-of-two divisor of 128, hidden not a
    multiple of 128) and for sequences whose fused-backward dQ scratch
    exceeds VMEM budget.

    Returns attention output of the same shape/dtype as ``q``.
    """
    if q.ndim != 3:
        raise ValueError(f"expected [b, s, hidden], got {q.shape}")
    b, sq, hidden = q.shape
    sk = k.shape[1]
    if causal and sq != sk:
        raise ValueError("causal attention requires sq == sk")
    if hidden % num_heads:
        raise ValueError(
            f"hidden={hidden} not divisible by num_heads={num_heads}")
    d_head = hidden // num_heads
    s = float(scale) if scale is not None else 1.0 / d_head ** 0.5
    # the packed kernels implement only the fused single-sweep backward;
    # an explicit =split override routes through the head-major path
    # (where _run_bwd honours it), keeping the documented A/B contract
    if not flash_bsh_eligible(hidden, num_heads, sq, block_q):
        # reshape to head-major and use the generic path
        def split(x):
            return jnp.transpose(
                x.reshape(x.shape[0], x.shape[1], num_heads, d_head),
                (0, 2, 1, 3))
        out = flash_attention(
            split(q), split(k), split(v), causal=causal, scale=s,
            kv_lengths=kv_lengths, segment_ids=segment_ids,
            kv_segment_ids=kv_segment_ids, block_q=block_q,
            block_k=block_k)
        return jnp.transpose(out, (0, 2, 1, 3)).reshape(b, sq, hidden)
    geom = _group_geometry(hidden, num_heads)  # non-None: eligible above
    q, was16 = widen_f16(q)
    k, _ = widen_f16(k)
    v, _ = widen_f16(v)
    lens = None
    if kv_lengths is not None:
        lens = jnp.asarray(kv_lengths, jnp.int32)
    segs = _seg_pair(segment_ids, kv_segment_ids, b, sq, sk)
    out = _flash_bsh(q, k, v, lens, segs, s, causal, geom, block_q, block_k)
    return out.astype(jnp.float16) if was16 else out
