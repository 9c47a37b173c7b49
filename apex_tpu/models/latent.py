"""The latent-attention mixer with a learned sparse selection, and the
layer built on it (DeepSeek-V3.2's block) — serving path only.

``GPTConfig.latent`` (a :class:`LatentConfig`) switches ``models/gpt``'s
layer from fused-QKV multi-head attention to this mixer:
:func:`cache_attend` is the ``attend`` that ``gpt._layer`` is handed
against the cache, :func:`init` builds the parameters; the layer
itself (norm, residual, feed-forward), the layer scan and the entry
points (``gpt.decode_step(s)``, ``gpt.prefill_paged``) are ``gpt``'s
own, shared with the fused-QKV mixer.

A layer, on the residual stream ``x`` with ``h = RMSNorm(x)``:

- **MLA** (``apex.mla.proj``): ``cQ = RMSNorm(h W_qa)``; ``q = cQ W_qb``
  -> heads x (nope + rope); ``[cKV ; kR] = h W_kva``, ``cKV`` RMS-normed,
  rotary (YaRN, interleaved pairs) on ``kR`` and ``q_rope``. The cache
  row of a token is ``[cKV ; kR]``. Attention is always the absorbed
  form: ``q_abs = W_uk^T q_nope`` scores one ``rank + rope``-wide row a
  key for all heads, and ``W_uv`` is applied to the weighted sum.
- **Indexer** (``apex.dsa.index``): ``qI = cQ W_iq`` (heads x dim),
  ``kI = LayerNorm(h W_ik)`` (cached beside the row), rotary (half-split)
  on the first ``rope`` numbers of both, ``w = h W_iw / sqrt(heads *
  dim)``; ``I(t, s) = sum_j w_j ReLU(qI_j(t) . kI(s))`` over ``s <= t``,
  computed a block of keys at a time so that no ``[heads, queries,
  keys]`` array outlives a block.
- **Selection** (``apex.dsa.select``): the ``min(topk, t + 1)`` largest
  ``I(t, .)``, exactly, ties to the lower position (``lax.top_k``'s
  rule).
- **Sparse attention** (``apex.mla.sparse_attn``): the selected rows
  gathered through the page table, softmax over them alone.
- **Feed-forward**: SwiGLU in the first ``dense_layers`` layers, the
  dropless routed layer (``transformer.moe.routed_ffn``) after them.

The cache is a pytree of two planes that page together under one block
table — ``ckv [L, 1, pages, 1, P, row_store]`` (attended; ``rank +
rope`` numbers padded to whole lanes) and ``ki
[L, 1, pages, 1, P, index_dim]`` (only scored) — in the rank-6 layout of
the per-head K/V cache (slot/page dim 2, horizon dim 4), plus ``counts
int32 [4]``, the routed layer's running totals (pairs routed, pairs
held, held experts hit, held experts offered), which ride the cache because the cache is what
every program already carries and donates.

Departures from the published model: the indexer runs in the compute
dtype (published: FP8 after a Hadamard rotation that cancels in the dot
product); no multi-token-prediction module; bf16 cache only.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax

from apex_tpu.transformer import moe as moe_mod

#: bytes one block of gathered rows or of per-head index scores may take
_BLOCK_BYTES = 256 * 2 ** 20


@dataclasses.dataclass(frozen=True)
class LatentConfig:
    """Widths of the mixer and of the two feed-forward kinds (defaults:
    DeepSeek-V3.2 as published). ``GPTConfig`` gives hidden size, heads,
    layers, vocabulary rows held and the served horizon."""

    routed: moe_mod.RoutedConfig
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    index_n_heads: int = 64
    index_head_dim: int = 128
    index_topk: int = 2048
    rope_theta: float = 10000.0
    rope_factor: float = 40.0
    rope_original: int = 4096
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale_all_dim: float = 1.0
    rms_eps: float = 1e-6
    index_ln_eps: float = 1e-6
    dense_layers: int = 1
    dense_ffn: int = 18432
    expert_ffn: int = 2048
    #: the query up-projections (MLA's and the indexer's) are drawn this
    #: much wider than ``init_std``: random weights otherwise give a
    #: near-uniform softmax, in which a wrong selection hides
    attn_init_gain: float = 1.0
    router_bias_std: float = 0.01

    @property
    def row_dim(self) -> int:
        """Numbers in a token's attended cache row, ``[cKV ; kR]``."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def row_store(self) -> int:
        """Width the row is stored at: padded with zeros to whole lanes
        of 128. The TPU compiler otherwise lays a plane ``[.., P, 576]``
        out with the POSITIONS on the lanes and relays all of it at
        every program's entry and exit (AOT, PR 31), and a gather of
        640-wide rows is faster than one of 576 (5.2 against 7.1 ms for
        128 x 2048 rows, my chip run, PR 31)."""
        return -(-self.row_dim // 128) * 128

    @property
    def inv_freq(self) -> tuple:
        """Per-pair inverse rotary frequencies under YaRN: divided by
        the factor where a pair turns fewer than ``beta_slow`` times
        over the original horizon, kept above ``beta_fast`` turns, a
        linear ramp between."""
        dim, theta = self.qk_rope_head_dim, self.rope_theta
        correction_dim = lambda turns: dim * math.log(
            self.rope_original / (turns * 2 * math.pi)) / (
            2 * math.log(theta))
        low = max(math.floor(correction_dim(self.rope_beta_fast)), 0)
        high = min(math.ceil(correction_dim(self.rope_beta_slow)), dim - 1)
        out = []
        for i in range(dim // 2):
            extra = 1.0 / theta ** (2 * i / dim)
            ramp = 0.0 if self.rope_factor <= 1 else min(max(
                (i - low) / max(high - low, 1e-3), 0.0), 1.0)
            out.append(extra / self.rope_factor * ramp
                       + extra * (1.0 - ramp))
        return tuple(out)

    @property
    def softmax_scale(self) -> float:
        m = 1.0 if self.rope_factor <= 1 else (
            0.1 * self.rope_mscale_all_dim * math.log(self.rope_factor)
            + 1.0)
        return (self.qk_nope_head_dim + self.qk_rope_head_dim) ** -0.5 \
            * m * m


def check(cfg) -> None:
    """What the mixer does not compose with, loudly."""
    lc = cfg.latent
    if cfg.num_layers <= lc.dense_layers:
        raise ValueError(
            f"num_layers={cfg.num_layers} leaves no routed layer after "
            f"the {lc.dense_layers} dense ones")
    for name, bad in (("num_experts", cfg.num_experts),
                      ("sequence_parallel", cfg.sequence_parallel),
                      ("context_parallel", cfg.context_parallel),
                      ("fsdp", cfg.fsdp), ("causal=False", not cfg.causal)):
        if bad:
            raise ValueError(f"the latent mixer does not take {name}")
    if cfg.kv_cache_dtype not in ("auto", "bf16"):
        raise ValueError(
            f"the latent cache is stored in the compute dtype only, not "
            f"kv_cache_dtype={cfg.kv_cache_dtype!r} (an int8/fp8 latent "
            f"row is ROADMAP R3)")


# ---------------------------------------------------------------------------
# parameters
# ---------------------------------------------------------------------------

def _attn_init(cfg, key):
    lc, h, dt = cfg.latent, cfg.hidden_size, cfg.param_dtype
    n_h, nope, rope = cfg.num_heads, lc.qk_nope_head_dim, lc.qk_rope_head_dim
    r, v = lc.kv_lora_rank, lc.v_head_dim
    k = jax.random.split(key, 10)
    norm = lambda key, shape, std=cfg.init_std: (
        std * jax.random.normal(key, shape, jnp.float32)).astype(dt)
    wide = cfg.init_std * lc.attn_init_gain
    return {
        "ln1": {"scale": jnp.ones((h,), dt)},
        "ln2": {"scale": jnp.ones((h,), dt)},
        "attn": {
            "q_a": norm(k[0], (h, lc.q_lora_rank)),
            "q_norm": jnp.ones((lc.q_lora_rank,), dt),
            "q_b": norm(k[1], (lc.q_lora_rank, n_h * (nope + rope)), wide),
            "kv_a": norm(k[2], (h, r + rope)),
            "kv_norm": jnp.ones((r,), dt),
            # W_kvb, split per head: [heads, nope, rank] scores the
            # latent (absorbed into q), [heads, rank, v] lifts the sum
            "w_uk": norm(k[3], (n_h, nope, r)),
            "w_uv": norm(k[4], (n_h, r, v)),
            "o": norm(k[5], (n_h * v, h)),
        },
        "index": {
            "wq_b": norm(k[6], (lc.q_lora_rank,
                                lc.index_n_heads * lc.index_head_dim),
                         wide),
            "wk": norm(k[7], (h, lc.index_head_dim)),
            "k_norm": {"scale": jnp.ones((lc.index_head_dim,), dt),
                       "bias": jnp.zeros((lc.index_head_dim,), dt)},
            "weights_proj": norm(k[8], (h, lc.index_n_heads)),
        },
    }, k[9]


def _swiglu_init(cfg, key, lead, ffn):
    h, dt = cfg.hidden_size, cfg.param_dtype
    k = jax.random.split(key, 3)
    norm = lambda key, shape: (cfg.init_std * jax.random.normal(
        key, shape, jnp.float32)).astype(dt)
    return {"gate": norm(k[0], lead + (h, ffn)),
            "up": norm(k[1], lead + (h, ffn)),
            "down": norm(k[2], lead + (ffn, h))}


def _dense_layer_init(cfg, key):
    p, key = _attn_init(cfg, key)
    p["ffn"] = _swiglu_init(cfg, key, (), cfg.latent.dense_ffn)
    return p


def _moe_layer_init(cfg, key):
    lc = cfg.latent
    p, key = _attn_init(cfg, key)
    k = jax.random.split(key, 4)
    n_all, held = lc.routed.num_experts, lc.routed.experts_held[1]
    p["moe"] = {
        "router": {
            "kernel": (cfg.init_std * jax.random.normal(
                k[0], (cfg.hidden_size, n_all), jnp.float32)
            ).astype(cfg.param_dtype),
            # the load-correcting bias: selection only, float32
            "bias": lc.router_bias_std * jax.random.normal(
                k[1], (n_all,), jnp.float32)},
        "experts": _swiglu_init(cfg, k[2], (held,), lc.expert_ffn),
        "shared": _swiglu_init(cfg, k[3], (), lc.expert_ffn),
    }
    return p


def init(cfg, key) -> Any:
    """The parameter tree: an untied embedding and head over the
    vocabulary rows held, and two homogeneous stacks of layers —
    ``dense_layers [Kd, ...]`` (SwiGLU) and ``moe_layers [L - Kd, ...]``
    (routed) — each scanned."""
    check(cfg)
    lc, h, dt = cfg.latent, cfg.hidden_size, cfg.param_dtype
    # billions of numbers: drawn by the device's own bit generator
    # (the counter-based default takes 44 s for this model's 4.6 G on a
    # v5e, my chip run, PR 31); the same key gives the same weights on
    # the same platform
    data = jax.random.key_data(key) if jnp.issubdtype(
        key.dtype, jax.dtypes.prng_key) else key
    key = jax.random.wrap_key_data(
        jnp.concatenate([data, data]).astype(jnp.uint32), impl="rbg")
    k = jax.random.split(key, 4)
    norm = lambda key, shape: (cfg.init_std * jax.random.normal(
        key, shape, jnp.float32)).astype(dt)
    n_moe = cfg.num_layers - lc.dense_layers
    return {
        "embedding": {"word": {"table": norm(k[0], (cfg.vocab_size, h))}},
        "head": {"kernel": norm(k[1], (cfg.vocab_size, h))},
        "final_ln": {"scale": jnp.ones((h,), dt)},
        "dense_layers": jax.vmap(lambda kk: _dense_layer_init(cfg, kk))(
            jax.random.split(k[2], lc.dense_layers)),
        "moe_layers": jax.vmap(lambda kk: _moe_layer_init(cfg, kk))(
            jax.random.split(k[3], n_moe)),
    }


def param_specs(cfg) -> Any:
    """Everything replicated: the mixer runs on ``tp == 1`` (attention
    whole on every chip is the deployment's own layout)."""
    from jax.sharding import PartitionSpec as P

    shapes = jax.eval_shape(lambda: init(cfg, jax.random.PRNGKey(0)))
    return jax.tree.map(lambda _: P(), shapes)


#: the parameter tree's layer stacks, in layer order
STACKS = ("dense_layers", "moe_layers")


def layer_stacks(cfg, params):
    """``[(stacked layer parameters, index of the stack's first
    layer)]`` in layer order."""
    return [(params[STACKS[0]], 0),
            (params[STACKS[1]], cfg.latent.dense_layers)]


# ---------------------------------------------------------------------------
# the cache
# ---------------------------------------------------------------------------

def init_cache(cfg, batch: int, max_len: int):
    lc, dt = cfg.latent, cfg.compute_dtype
    lead = (cfg.num_layers, 1, batch, 1, max_len)
    return {"ckv": jnp.zeros(lead + (lc.row_store,), dt),
            "ki": jnp.zeros(lead + (lc.index_head_dim,), dt),
            "counts": jnp.zeros((4,), jnp.int32)}


def cache_specs(cfg):
    from jax.sharding import PartitionSpec as P

    return {"ckv": P(), "ki": P(), "counts": P()}


# ---------------------------------------------------------------------------
# pieces
# ---------------------------------------------------------------------------

def rms_norm(x, scale, eps):
    """RMSNorm in float32, back in ``x``'s dtype."""
    x32 = x.astype(jnp.float32)
    y = x32 * lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True) + eps)
    return (y * scale.astype(jnp.float32)).astype(x.dtype)


def _ln(x, scale, bias, eps):
    x32 = x.astype(jnp.float32)
    d = x32 - jnp.mean(x32, -1, keepdims=True)
    y = d * lax.rsqrt(jnp.mean(d * d, -1, keepdims=True) + eps)
    return (y * scale.astype(jnp.float32)
            + bias.astype(jnp.float32)).astype(x.dtype)


def _rope_pairs(x, cos, sin):
    """Interleaved layout: ``(x[2i], x[2i+1])`` is pair ``i``."""
    x = x.astype(jnp.float32)
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin],
                     -1).reshape(x.shape)


def _rope_halves(x, cos, sin):
    """Half-split layout: ``(x[i], x[i + d/2])`` is pair ``i``."""
    x = x.astype(jnp.float32)
    d = x.shape[-1] // 2
    a, b = x[..., :d], x[..., d:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def embed(cfg, params, tokens):
    return jnp.take(params["embedding"]["word"]["table"], tokens,
                    axis=0).astype(cfg.compute_dtype)


def lm_head(cfg, params, h):
    """``h [b, hidden]`` (pre-norm) -> float32 logits over the
    vocabulary rows held (untied head)."""
    h = rms_norm(h, params["final_ln"]["scale"], cfg.latent.rms_eps)
    return jnp.einsum("bh,vh->bv", h,
                      params["head"]["kernel"].astype(cfg.compute_dtype),
                      preferred_element_type=jnp.float32)


def project(cfg, p, h, pos):
    """Everything the mixer makes of the normed stream ``h [b, T,
    hidden]`` at positions ``pos [b, T]``: the absorbed query ``q [b, T,
    heads, row_store]``, the cache ``row [b, T, row_store]`` (both
    zero past ``rank + rope``), and
    the indexer's ``q_i [b, T, ih, id]``, ``k_i [b, T, id]``, ``w_i [b,
    T, ih]`` (float32)."""
    lc, a, ix = cfg.latent, p["attn"], p["index"]
    b, t, _ = h.shape
    dt = h.dtype
    nope, rope, r = lc.qk_nope_head_dim, lc.qk_rope_head_dim, lc.kv_lora_rank
    ang = pos.astype(jnp.float32)[..., None] * jnp.asarray(
        lc.inv_freq, jnp.float32)
    cos, sin = jnp.cos(ang), jnp.sin(ang)            # [b, T, rope/2]
    with jax.named_scope("apex.mla.proj"):
        c_q = rms_norm(h @ a["q_a"], a["q_norm"], lc.rms_eps)
        q = (c_q @ a["q_b"]).reshape(b, t, cfg.num_heads, nope + rope)
        kv = h @ a["kv_a"]
        pad = lc.row_store - lc.row_dim
        row = jnp.concatenate([
            rms_norm(kv[..., :r], a["kv_norm"], lc.rms_eps),
            _rope_pairs(kv[..., r:], cos, sin).astype(dt),
            jnp.zeros((b, t, pad), dt)], -1)
        q = jnp.concatenate([
            jnp.einsum("bthn,hnc->bthc", q[..., :nope], a["w_uk"]),
            _rope_pairs(q[..., nope:], cos[:, :, None],
                        sin[:, :, None]).astype(dt),
            jnp.zeros((b, t, cfg.num_heads, pad), dt)], -1)
    with jax.named_scope("apex.dsa.index"):
        q_i = (c_q @ ix["wq_b"]).reshape(b, t, lc.index_n_heads,
                                         lc.index_head_dim)
        q_i = jnp.concatenate([
            _rope_halves(q_i[..., :rope], cos[:, :, None],
                         sin[:, :, None]).astype(dt), q_i[..., rope:]], -1)
        k_i = _ln(h @ ix["wk"], ix["k_norm"]["scale"],
                  ix["k_norm"]["bias"], lc.index_ln_eps)
        k_i = jnp.concatenate([
            _rope_halves(k_i[..., :rope], cos, sin).astype(dt),
            k_i[..., rope:]], -1)
        w_i = (h @ ix["weights_proj"]).astype(jnp.float32) * (
            lc.index_n_heads ** -0.5 * lc.index_head_dim ** -0.5)
    return {"q": q, "row": row, "q_i": q_i, "k_i": k_i, "w_i": w_i}


def _pow2_at_most(n: int, cap: int) -> int:
    """The largest power of two that divides ``n`` and is <= ``cap``
    (at least 1)."""
    out = 1
    while out * 2 <= cap and n % (out * 2) == 0:
        out *= 2
    return out


def index_scores(lc, q_i, w_i, ki, layer, table):
    """``I(t, s)`` for every cached position ``s`` of each row: ``[b, T,
    max_pages * P]`` float32, a block of whole pages at a time (one
    page gather, one product, ReLU and the heads' weighted sum) so that
    the per-head scores live for one block only."""
    b, t, n_h, _ = q_i.shape
    mp, page = table.shape[1], ki.shape[4]
    ppb = _pow2_at_most(mp, max(1, _BLOCK_BYTES // (b * t * n_h * 4 * page)))

    def block(_, tb):                                 # tb [b, ppb]
        k = ki[layer, 0, tb, 0].reshape(b, ppb * page, -1)
        s = jnp.einsum("btjd,bsd->btjs", q_i, k,
                       preferred_element_type=jnp.float32)
        # the heads' weighted sum in float32 on the vector unit (a
        # float32 einsum would round both sides to bfloat16 first)
        return None, jnp.sum(jax.nn.relu(s) * w_i[..., None], axis=2)

    _, out = lax.scan(block, None,
                      table.reshape(b, mp // ppb, ppb).transpose(1, 0, 2))
    return out.transpose(1, 2, 0, 3).reshape(b, t, mp * page)


#: query rows one exact top-k call takes (128 rows of 32 768 scores cost
#: 1.9 ms on a v5e, 1 024 rows 35 ms and not 15: my chip run, PR 31)
_SELECT_ROWS = 128


def select(scores, pos, k: int):
    """The ``min(k, pos + 1)`` largest ``scores [b, T, S]`` over ``s <=
    pos [b, T]`` -> ``(idx [b, T, k'] int32, valid [b, T, k'])``, ``k' =
    min(k, S)``; exact, ties to the lower position."""
    b, t, s_max = scores.shape
    k = min(k, s_max)
    allowed = jnp.arange(s_max, dtype=jnp.int32) <= pos[..., None]
    masked = jnp.where(allowed, scores, -jnp.inf).reshape(b * t, s_max)
    rows = _pow2_at_most(b * t, _SELECT_ROWS)
    vals, idx = lax.map(lambda x: lax.top_k(x, k),
                        masked.reshape(b * t // rows, rows, s_max))
    return (idx.reshape(b, t, k).astype(jnp.int32),
            vals.reshape(b, t, k) > -jnp.inf)


def sparse_attention(lc, q, ckv, layer, table, idx, valid, w_uv):
    """Softmax attention of the absorbed queries ``q [b, T, heads,
    row]`` over the rows ``idx [b, T, k]`` of each query's own
    selection, gathered from the plane through the page table, a block
    of queries at a time -> ``[b, T, heads * v]``."""
    b, t, n_h, row = q.shape
    k, n_pages, page = idx.shape[-1], ckv.shape[2], ckv.shape[4]
    tq = _pow2_at_most(t, max(1, _BLOCK_BYTES // (b * k * row * 2)))
    rows_of = jnp.arange(b)[:, None, None]
    scale = jnp.asarray(lc.softmax_scale, jnp.float32)
    # every layer's pages as one list of rows (a view: the plane is
    # row-major): a one-index row gather is a fifth faster on the chip
    # than one over (layer, page, offset) (my chip run, PR 31)
    flat = ckv.reshape(-1, row)

    def block(xs):
        qb, ib, vb = xs                   # [b, tq, H, row], [b, tq, k]
        pg = layer * n_pages + table[rows_of, ib // page]
        rows = jnp.take(flat, pg * page + ib % page, axis=0)
        s = jnp.einsum("bthc,btkc->bthk", qb, rows,
                       preferred_element_type=jnp.float32) * scale
        pr = jax.nn.softmax(jnp.where(vb[:, :, None], s, -1e30), -1)
        return jnp.einsum("bthk,btkc->bthc", pr.astype(qb.dtype), rows)

    blocks = lambda x: jnp.moveaxis(
        x.reshape((b, t // tq, tq) + x.shape[2:]), 1, 0)
    out = lax.map(block, (blocks(q), blocks(idx), blocks(valid)))
    out = jnp.moveaxis(out, 0, 1).reshape(b, t, n_h, row)
    out = jnp.einsum("bthc,hcv->bthv", out[..., :lc.kv_lora_rank], w_uv)
    return out.reshape(b, t, -1)


def columns(x, pos, live):
    """The layer scan's arguments in this mixer's shapes: ``x [b, T,
    hidden]`` (one decoded token a row: ``T`` = 1), ``pos [b, T]`` from
    a scalar or per-row start, ``live [b, T]`` (or None)."""
    if x.ndim == 2:
        x = x[:, None]
    b, t = x.shape[:2]
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32).reshape(-1, 1),
                           (b, 1)) + jnp.arange(t, dtype=jnp.int32)[None]
    if live is not None:
        live = jnp.broadcast_to(live.reshape(b, -1), (b, t))
    return x, pos, live


def cache_attend(cfg, p, h, cache, layer, pos, table=None):
    """THE cache attention of this mixer, the ``attend`` that
    ``gpt._layer`` is handed: project the normed stream ``h [b, T, hidden]``, write
    the new tokens' rows and index keys into layer ``layer`` of both
    planes at ``pos [b, T]``, score every cached position, select, and
    attend the selection. Returns ``(ctx [b, T, heads * v], cache)``.

    ``table [b, max_pages]`` maps each row's horizon onto pages of the
    pool; without it the cache is contiguous, row ``i`` being page
    ``i`` of horizon ``S``. Columns at or past the horizon are dropped.
    A chunk's queries see its own earlier tokens through the cache: the
    write comes first, and ``s <= pos`` is the causal mask."""
    lc = cfg.latent
    pr = project(cfg, p, h, pos)
    ckv, ki = cache["ckv"], cache["ki"]
    n_pages, page = ckv.shape[2], ckv.shape[4]
    if table is None:
        table = jnp.arange(pos.shape[0], dtype=jnp.int32)[:, None]
    s_max = table.shape[1] * page
    with jax.named_scope("apex.mla.cache_write"):
        pg = jnp.take_along_axis(
            table, jnp.minimum(pos // page, table.shape[1] - 1), axis=1)
        pg = jnp.where(pos < s_max, pg, n_pages)      # past it: dropped
        ckv = ckv.at[layer, 0, pg, 0, pos % page].set(pr["row"],
                                                      mode="drop")
        ki = ki.at[layer, 0, pg, 0, pos % page].set(pr["k_i"],
                                                    mode="drop")
    with jax.named_scope("apex.dsa.index"):
        scores = index_scores(lc, pr["q_i"], pr["w_i"], ki, layer, table)
    with jax.named_scope("apex.dsa.select"):
        idx, valid = select(scores, pos, lc.index_topk)
    with jax.named_scope("apex.mla.sparse_attn"):
        ctx = sparse_attention(lc, pr["q"], ckv, layer, table, idx, valid,
                               p["attn"]["w_uv"])
    return ctx, {**cache, "ckv": ckv, "ki": ki}


def cast_layer(cfg, layer_p):
    """Matmul weights to the compute dtype; the router (float32 product,
    float32 bias) stays as stored."""
    cast = lambda t: jax.tree.map(
        lambda x: x.astype(cfg.compute_dtype)
        if jnp.issubdtype(x.dtype, jnp.floating) else x, t)
    out = cast({k: v for k, v in layer_p.items() if k != "moe"})
    if "moe" in layer_p:
        m = layer_p["moe"]
        out["moe"] = {"router": m["router"], "experts": cast(m["experts"]),
                      "shared": cast(m["shared"])}
    return out
