"""DeepSeek-V3.2 (``model_type: deepseek_v32``) in plain float32
``jax.numpy``: the architecture as published, for comparison only.

Nothing here comes from the program: no cache, no kernels, no paging,
nothing imported from ``apex_tpu``. One sequence at a time, a full
causal forward; every matmul under
``jax.default_matmul_precision("highest")``. Weights arrive in whatever
dtype the caller holds them and are up-cast a piece at a time, and
attention runs over blocks of queries and groups of heads, so a 20k-token
sequence at the published widths fits beside the weights on one chip.

The layer (``x`` the residual stream, ``h = RMSNorm(x)``, no biases):

- MLA. ``cQ = RMSNorm(W_qa h)``; ``q = W_qb cQ`` -> heads x (nope + rope);
  ``[cKV ; kR] = W_kva h``; ``cKV <- RMSNorm(cKV)``; rotary on ``kR`` (one
  for all heads) and on ``q_rope``; ``k = [W_uk cKV ; kR]``, ``v = W_uv
  cKV``; ``softmax(q.k * scale)`` over the selected keys only, ``scale =
  (nope + rope)^-1/2 * m^2``, ``m = 0.1 ln(factor) + 1`` (YaRN).
- Indexer. ``qI = W_iq cQ`` (heads x dim), ``kI = LayerNorm(W_ik h)``,
  rotary on the first ``rope`` numbers of both, ``w = W_iw h * heads^-1/2
  * dim^-1/2``; ``I(t, s) = sum_j w_j ReLU(qI_j(t) . kI(s))``; the selected
  set of query ``t`` is the ``min(topk, t + 1)`` largest ``I(t, s <= t)``,
  ties to the lower position.
- Feed-forward. The first ``first_k_dense`` layers SwiGLU; after them
  sigmoid router scores over all experts (float32), selection on score +
  bias (group-limited: a group's score is the sum of its two largest,
  the best ``topk_group`` groups, the best ``top_k`` experts inside
  them), weights renormalised over the selected and scaled; only the
  experts ``held = (first, count)`` are computed, plus the shared expert.

Departures from the published model, each also in the configuration
file: the indexer runs in the sequence's precision (published: FP8
after a Hadamard rotation of ``qI`` and ``kI``, which is orthogonal and
cancels in the dot product); the multi-token-prediction module is left
out; rotary layouts are assumed (interleaved pairs in MLA, half-split in
the indexer), and so is the indexer LayerNorm's epsilon.

``variant`` computes a deliberately wrong model, to show that the
comparison discriminates: ``"recent"`` attends the most recent ``topk``
positions instead of the indexer's choice, ``"no_relu"`` drops the
indexer's ReLU, ``"no_renorm"`` leaves the routed weights
unnormalised. ``round_to`` rounds every matmul operand to that dtype
first (the nearest precision below the configuration's).
"""

from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

F32 = jnp.float32
NEG = -1e30


def _mm(a, b, round_to=None):
    """``a @ b`` in float32 at the highest precision; operands first
    rounded to ``round_to`` when given."""
    if round_to is not None:
        a, b = a.astype(round_to), b.astype(round_to)
    return jnp.matmul(a.astype(F32), b.astype(F32),
                      precision=lax.Precision.HIGHEST)


def _ein(spec, a, b, round_to=None):
    if round_to is not None:
        a, b = a.astype(round_to), b.astype(round_to)
    return jnp.einsum(spec, a.astype(F32), b.astype(F32),
                      precision=lax.Precision.HIGHEST)


def rms_norm(x, w, eps):
    x = x.astype(F32)
    return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * w.astype(F32)


def layer_norm(x, w, b, eps):
    x = x.astype(F32)
    mu = jnp.mean(x, -1, keepdims=True)
    d = x - mu
    return d * lax.rsqrt(jnp.mean(d * d, -1, keepdims=True) + eps) \
        * w.astype(F32) + b.astype(F32)


def yarn_inv_freq(dim: int, theta: float, factor: float, original: int,
                  beta_fast: float, beta_slow: float):
    """YaRN's per-pair inverse frequencies: the published rotary
    frequencies, divided by ``factor`` where a pair turns fewer than
    ``beta_slow`` times over the original horizon, kept where it turns
    more than ``beta_fast`` times, a linear ramp between."""
    def correction_dim(rotations):
        return dim * math.log(original / (rotations * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(correction_dim(beta_fast)), 0)
    high = min(math.ceil(correction_dim(beta_slow)), dim - 1)
    pairs = jnp.arange(0, dim, 2, dtype=F32) / dim
    extra = 1.0 / theta ** pairs
    inter = extra / factor
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=F32) - low)
                    / max(high - low, 1e-3), 0.0, 1.0)
    return inter * ramp + extra * (1.0 - ramp)


def yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def rope_interleaved(x, angles):
    """Pairs ``(x[2i], x[2i+1])`` rotated by ``angles[..., i]``."""
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin],
                     -1).reshape(x.shape)


def rope_half(x, angles):
    """Pairs ``(x[i], x[i + d/2])`` rotated by ``angles[..., i]``."""
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    d = x.shape[-1] // 2
    a, b = x[..., :d], x[..., d:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def select_mask(scores, k: int):
    """``scores [q, S]`` (``NEG`` where not allowed) -> the boolean mask
    of each row's ``k`` largest entries, ties to the lower position;
    rows with fewer than ``k`` allowed entries keep them all."""
    k = min(k, scores.shape[-1])
    kth = lax.top_k(scores, k)[0][:, -1:]
    above = scores > kth
    tied = scores == kth
    need = k - jnp.sum(above, -1, keepdims=True)
    return (above | (tied & (jnp.cumsum(tied, -1) <= need))) \
        & (scores > NEG / 2)


#: rows of the stream a feed-forward handles at a time (it is
#: row-independent; blocks only bound the float32 intermediates)
ROW_BLOCK = 2048


def _by_rows(fn, x):
    """``fn`` over ``x [S, ...]`` in blocks of ``ROW_BLOCK`` rows."""
    n = x.shape[0]
    if n <= ROW_BLOCK:
        return fn(x)
    n_blk = -(-n // ROW_BLOCK)
    xp = jnp.pad(x, ((0, n_blk * ROW_BLOCK - n),) + ((0, 0),) * (x.ndim - 1))
    out = lax.map(fn, xp.reshape((n_blk, ROW_BLOCK) + x.shape[1:]))
    return out.reshape((n_blk * ROW_BLOCK,) + out.shape[2:])[:n]


def swiglu(x, p, round_to=None):
    def rows(xb):
        g = _mm(xb, p["gate"], round_to)
        u = _mm(xb, p["up"], round_to)
        return _mm(jax.nn.silu(g) * u, p["down"], round_to)

    return _by_rows(rows, x)


def route(h, router, *, top_k: int, n_group: int, topk_group: int,
          scale: float, variant: Optional[str] = None):
    """``h [T, hidden]`` -> ``(experts [T, top_k], weights [T, top_k])``
    over ALL the published experts."""
    s = jax.nn.sigmoid(_mm(h, router["kernel"]))
    n = s.shape[-1]
    choice = s + router["bias"].astype(F32)
    groups = choice.reshape(-1, n_group, n // n_group)
    g_score = jnp.sum(lax.top_k(groups, 2)[0], -1)
    g_keep = lax.top_k(g_score, topk_group)[1]
    g_mask = jnp.zeros_like(g_score, bool).at[
        jnp.arange(g_score.shape[0])[:, None], g_keep].set(True)
    choice = jnp.where(jnp.repeat(g_mask, n // n_group, -1), choice, NEG)
    experts = lax.top_k(choice, top_k)[1]
    w = jnp.take_along_axis(s, experts, -1)
    if variant != "no_renorm":
        w = w / (jnp.sum(w, -1, keepdims=True) + 1e-20)
    return experts, w * scale


def moe(h, p, held: Tuple[int, int], kw, variant=None, round_to=None):
    """The routed layer's result as the chip holding experts
    ``held = (first, count)`` computes it: its own experts' part plus
    the shared expert. Every held expert is applied to every row and
    weighted by the row's gate for it, zero where the row did not
    choose it."""
    first, count = held

    def rows(hb):
        experts, w = route(hb, p["router"], top_k=kw["top_k"],
                           n_group=kw["n_group"],
                           topk_group=kw["topk_group"],
                           scale=kw["routed_scale"], variant=variant)

        def add(y, xs):
            i, e = xs
            gate = jnp.sum(jnp.where(experts == first + i, w, 0.0), -1)
            g = _mm(hb, e["gate"], round_to)
            u = _mm(hb, e["up"], round_to)
            return y + gate[:, None] * _mm(jax.nn.silu(g) * u, e["down"],
                                           round_to), None

        y, _ = lax.scan(add, jnp.zeros(hb.shape, F32),
                        (jnp.arange(count), p["experts"]))
        return y

    return swiglu(h, p["shared"], round_to) + _by_rows(rows, h)


def attention(h, p, pos, kw, variant=None, round_to=None,
              q_block: int = 128, head_group: int = 16):
    """MLA over the indexer's selection for one sequence ``h [S,
    hidden]`` at positions ``pos [S]`` -> ``[S, hidden]``."""
    S = h.shape[0]
    a, ix = p["attn"], p["index"]
    n_h, nope, rope, v_d = (kw["heads"], kw["nope"], kw["rope"], kw["v"])
    inv = yarn_inv_freq(rope, kw["theta"], kw["factor"], kw["original"],
                        kw["beta_fast"], kw["beta_slow"])
    ang = pos.astype(F32)[:, None] * inv[None]              # [S, rope/2]
    c_q = rms_norm(_mm(h, a["q_a"], round_to), a["q_norm"], kw["eps"])
    kv = _mm(h, a["kv_a"], round_to)
    c_kv = rms_norm(kv[:, :kw["kv_rank"]], a["kv_norm"], kw["eps"])
    k_r = rope_interleaved(kv[:, kw["kv_rank"]:], ang)      # [S, rope]
    m = yarn_mscale(kw["factor"], kw["mscale_all_dim"])
    scale = (nope + rope) ** -0.5 * m * m

    # the indexer's scores, a block of queries at a time, and its mask
    i_h, i_d = kw["index_heads"], kw["index_dim"]
    k_i = layer_norm(_mm(h, ix["wk"], round_to), ix["k_norm"]["scale"],
                     ix["k_norm"]["bias"], kw["index_eps"])
    k_i = jnp.concatenate([rope_half(k_i[:, :rope], ang), k_i[:, rope:]],
                          -1)
    w_i = _mm(h, ix["weights_proj"], round_to) * (i_h ** -0.5
                                                  * i_d ** -0.5)
    n_blk = -(-S // q_block)
    pad = n_blk * q_block - S
    blocks = lambda x: jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1)
                               ).reshape((n_blk, q_block) + x.shape[1:])

    def mask_of(xs):
        cb, ab, wb, pb = xs          # a block of c_q, ang, w_i, pos
        qb = _mm(cb, ix["wq_b"], round_to).reshape(-1, i_h, i_d)
        qb = jnp.concatenate([rope_half(qb[..., :rope], ab[:, None]),
                              qb[..., rope:]], -1)
        s = _ein("qjd,sd->qjs", qb, k_i, round_to)
        if variant != "no_relu":
            s = jax.nn.relu(s)
        score = jnp.einsum("qjs,qj->qs", s, wb,
                           precision=lax.Precision.HIGHEST)
        causal = pos[None, :] <= pb[:, None]
        if variant == "recent":
            return causal & (pos[None, :] > pb[:, None] - kw["topk"])
        return select_mask(jnp.where(causal, score, NEG), kw["topk"])

    masks = lax.map(mask_of, (blocks(c_q), blocks(ang), blocks(w_i),
                              blocks(pos)))

    out = jnp.zeros((S, kw["hidden"]), F32)
    head_group = min(head_group, n_h)
    d_q = nope + rope
    for g in range(0, n_h, head_group):
        hs = slice(g, g + head_group)
        q = _mm(c_q, a["q_b"][:, g * d_q:(g + head_group) * d_q],
                round_to).reshape(S, head_group, d_q)
        q = jnp.concatenate([q[..., :nope],
                             rope_interleaved(q[..., nope:], ang[:, None])],
                            -1)
        # a["w_uk"] [heads, nope, rank], a["w_uv"] [heads, rank, v]
        k = jnp.concatenate([
            _ein("sc,hnc->shn", c_kv, a["w_uk"][hs], round_to),
            jnp.broadcast_to(k_r[:, None], (S, head_group, rope))], -1)
        v = _ein("sc,hcv->shv", c_kv, a["w_uv"][hs], round_to)

        def ctx_of(xs):
            qb, mb = xs
            s = _ein("qhd,shd->hqs", qb, k, round_to) * scale
            pr = jax.nn.softmax(jnp.where(mb[None], s, NEG), -1)
            return _ein("hqs,shv->qhv", pr, v, round_to)

        ctx = lax.map(ctx_of, (blocks(q), masks))
        ctx = ctx.reshape(n_blk * q_block, head_group * v_d)[:S]
        out = out + _mm(ctx, a["o"][g * v_d:(g + head_group) * v_d],
                        round_to)
    return out


def layer_forward(x, p, pos, *, kw: Dict[str, Any], held: Tuple[int, int],
                  variant=None, round_to=None):
    """One layer on the residual stream ``x [S, hidden]``: ``p`` holds
    ``ln1``, ``ln2``, ``attn``, ``index`` and either ``ffn`` (a dense
    layer) or ``moe`` (a routed one)."""
    h = rms_norm(x, p["ln1"], kw["eps"])
    x = x + attention(h, p, pos, kw, variant, round_to)
    h = rms_norm(x, p["ln2"], kw["eps"])
    if "ffn" in p:
        return x + swiglu(h, p["ffn"], round_to)
    return x + moe(h, p["moe"], held, kw, variant, round_to)


def embed(params, tokens):
    return params["embed"][tokens].astype(F32)


def head_logprobs(params, x, rows, *, kw: Dict[str, Any], round_to=None):
    """Log-softmax over the vocabulary rows held (the untied head) of
    the final-norm hidden states at positions ``rows [n]``."""
    h = rms_norm(x[rows], params["norm"], kw["eps"])
    return jax.nn.log_softmax(_mm(h, params["head"].T, round_to), -1)


def token_logprobs(params, tokens, rows=None, *, kw: Dict[str, Any],
                   held: Tuple[int, int], variant=None, round_to=None):
    """``tokens [S]`` -> float32 log-probabilities ``[n, vocab]`` of the
    token after each position of ``rows`` (default: every position).
    ``params["layers"]`` is a list of per-layer trees in layer order."""
    pos = jnp.arange(tokens.shape[0], dtype=jnp.int32)
    x = embed(params, tokens)
    for p in params["layers"]:
        x = layer_forward(x, p, pos, kw=kw, held=held, variant=variant,
                          round_to=round_to)
    return head_logprobs(params, x, pos if rows is None else rows, kw=kw,
                         round_to=round_to)
