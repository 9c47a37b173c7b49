"""Mixture-of-experts layer with expert parallelism over the ``ep`` axis.

No reference analogue: SURVEY.md §2.5 marks EP "absent" in apex — this is
a beyond-parity component, built because the ``ep`` mesh axis is where a
TPU framework scales FFN capacity past what TP can hold.

Design (GShard/Switch, the canonical TPU formulation):

- **Router** runs in fp32 (softmax over expert logits is the one place
  MoE numerics are fragile), top-1 (Switch) or top-2 (GShard) selection
  with the top-2 gates renormalised to sum to 1.
- **Dispatch/combine are one-hot einsums**, not gathers: a ``[slots,
  E, C]`` dispatch tensor contracted on the MXU. Scatter/gather-free —
  static shapes, no data-dependent control flow, XLA fuses the one-hot
  construction into the contraction.
- **Capacity** ``C = ceil(top_k · tokens · capacity_factor / E)`` bounds
  each expert's buffer; tokens past an expert's capacity are *dropped*
  (contribute zero for that slot — Switch semantics). Slot-major
  priority: every token's first choice is placed before any token's
  second choice.
- **Expert parallelism**: experts shard over ``ep``; each rank dispatches
  its local tokens into a ``[E, C, h]`` buffer and one ``all_to_all``
  (ICI) regroups it to ``[E_local, R·C, h]`` so each rank runs only its
  own experts' FFNs, batched in a single 3D einsum. A second
  ``all_to_all`` routes outputs back. With ``R`` ranks the per-rank FLOP
  and memory cost is 1/R of the dense-MoE layer — the reason ep exists.
- **Load-balance aux loss** (Switch): ``E · Σ_e f_e · P_e`` with ``f_e``
  the fraction of assignments routed to expert ``e`` (pre-capacity) and
  ``P_e`` the mean router probability. Computed over the rank's local
  tokens; average it over dp/ep with the main loss.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax import lax

from apex_tpu.mesh.topology import AXIS_EP


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    """Shape/routing config for one MoE FFN layer."""

    num_experts: int
    hidden_size: int
    ffn_hidden_size: Optional[int] = None  # default 4 * hidden
    top_k: int = 2                # 1 = Switch, 2 = GShard
    capacity_factor: float = 1.25
    aux_loss_coef: float = 0.01
    param_dtype: Any = jnp.float32
    compute_dtype: Any = jnp.bfloat16
    axis: Optional[str] = AXIS_EP  # None → dense (no expert parallelism)
    #: "einsum" → GShard one-hot contractions (MXU, O(tokens·E·C·h) —
    #: quadratic in tokens since C ∝ tokens/E; fine small, dominates the
    #: experts' own FLOPs at scale); "gather" → scatter-add/take into the
    #: expert buffers, O(tokens·k·h) (the production-TPU-MoE layout);
    #: "auto" → gather once the dispatch contraction would out-FLOP the
    #: expert FFNs. Numerics identical (each buffer cell is written by at
    #: most one assignment either way).
    dispatch: str = "auto"

    def __post_init__(self):
        if not 1 <= self.top_k <= self.num_experts:
            raise ValueError(
                f"top_k={self.top_k} must be in [1, num_experts="
                f"{self.num_experts}]")
        if self.dispatch not in ("auto", "einsum", "gather"):
            raise ValueError(
                f"dispatch={self.dispatch!r} must be 'auto', 'einsum' "
                "or 'gather'")

    @property
    def ffn(self) -> int:
        return self.ffn_hidden_size or 4 * self.hidden_size

    def capacity(self, n_tokens: int) -> int:
        return max(1, math.ceil(
            self.top_k * n_tokens * self.capacity_factor / self.num_experts))


def init_moe(cfg: MoEConfig, key) -> dict:
    """Global (unsharded) params. Shard the expert-stacked leaves with
    ``PartitionSpec("ep")`` on dim 0; the router stays replicated."""
    h, f, e = cfg.hidden_size, cfg.ffn, cfg.num_experts
    kr, k1, k2 = jax.random.split(key, 3)
    dt = cfg.param_dtype
    init = jax.nn.initializers.normal(0.02)
    return {
        "router": {"kernel": init(kr, (h, e), dt)},
        "experts": {
            "w1": init(k1, (e, h, f), dt),
            "b1": jnp.zeros((e, f), dt),
            "w2": init(k2, (e, f, h), dt),
            "b2": jnp.zeros((e, h), dt),
        },
    }


def moe_pspecs(P):
    """PartitionSpecs for :func:`init_moe` params (pass ``PartitionSpec``)."""
    return {
        "router": {"kernel": P()},
        "experts": {"w1": P("ep"), "b1": P("ep"),
                    "w2": P("ep"), "b2": P("ep")},
    }


def _route(cfg: MoEConfig, router_kernel, x):
    """fp32 routing. Returns (gates [n,k], expert_idx [n,k], probs [n,E])."""
    logits = x.astype(jnp.float32) @ router_kernel.astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, idx = lax.top_k(probs, cfg.top_k)
    if cfg.top_k > 1:
        gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
    return gates, idx, probs


def moe_ffn(cfg: MoEConfig, params: dict, x):
    """Apply the MoE FFN to local tokens ``x [n, hidden]``.

    Inside ``shard_map`` with ``cfg.axis`` bound, ``params["experts"]``
    leaves are the rank-local expert shard; with ``cfg.axis=None`` (or the
    axis absent) the layer is a dense MoE on one device. Returns
    ``(y [n, hidden], aux_loss scalar)``; callers fold
    ``cfg.aux_loss_coef * aux_loss`` into the objective.

    Capacity is sized from the *local* token count, so R ranks give each
    expert ``R·C`` total slots — the same budget as the dense layer on
    the full batch (drops can differ at the margin: the cap is enforced
    per source rank).
    """
    n, h = x.shape
    E = cfg.num_experts
    ranks = 1
    if cfg.axis is not None:
        try:
            ranks = lax.axis_size(cfg.axis)
        except NameError:  # axis not bound: dense path
            ranks = 1
    e_loc = params["experts"]["w1"].shape[0]
    if e_loc * ranks != E:
        raise ValueError(
            f"experts shard {e_loc} x {ranks} ranks != num_experts {E}")
    C = cfg.capacity(n)

    gates, idx, probs = _route(cfg, params["router"]["kernel"], x)

    # Slot-major assignment order: flatten [n, k] → [k*n] so slot 0 of
    # every token outranks any slot 1 when competing for capacity.
    oh = jax.nn.one_hot(idx, E, dtype=jnp.int32)          # [n, k, E]
    ohf = oh.transpose(1, 0, 2).reshape(cfg.top_k * n, E)  # [k*n, E]
    pos_in_expert = jnp.cumsum(ohf, axis=0) - ohf          # [k*n, E]
    pos = jnp.sum(pos_in_expert * ohf, axis=-1)            # [k*n]
    keep = pos < C  # every slot is routed (top_k indices are in-range)

    cdt = cfg.compute_dtype
    impl = cfg.dispatch
    if impl == "auto":
        # dispatch contraction FLOPs 2·k·n·E·C·h vs expert FFN FLOPs
        # ~4·k·n·h·f: prefer the MXU einsum until it costs more than the
        # experts themselves
        impl = "einsum" if E * C <= 2 * cfg.ffn else "gather"
    gflat = gates.astype(cdt).T.reshape(cfg.top_k * n)      # slot-major

    if impl == "einsum":
        # dispatch tensor [slots, E, C] — one-hot contractions, no scatters
        disp = (ohf.astype(cdt)[:, :, None]
                * jax.nn.one_hot(pos, C, dtype=cdt)[:, None, :]
                * keep.astype(cdt)[:, None, None])
        # collapse slots to token granularity: every (e, c) cell is owned
        # by at most one (token, slot) assignment, so the slot-sum is exact
        disp_tok = disp.reshape(cfg.top_k, n, E, C).sum(0)   # [n, E, C]
        expert_in = jnp.einsum("tec,th->ech", disp_tok, x.astype(cdt))
    elif impl == "gather":
        # scatter-add into the flat [E*C, h] buffer; dropped slots route
        # out of bounds and mode="drop" discards them. Each cell receives
        # at most one slot, so this is a permutation, not a reduction.
        e_of_slot = idx.T.reshape(cfg.top_k * n)             # [S]
        slot_cell = jnp.where(keep, e_of_slot * C + pos, E * C)
        xs = jnp.broadcast_to(x.astype(cdt), (cfg.top_k, n, h)).reshape(
            cfg.top_k * n, h)
        expert_in = jnp.zeros((E * C, h), cdt).at[slot_cell].add(
            xs, mode="drop").reshape(E, C, h)
    else:
        raise ValueError(f"unknown dispatch {cfg.dispatch!r}")

    if ranks > 1:
        # [E, C, h] → [E_loc, R*C, h]: rank r keeps experts [r*E_loc, ...)
        expert_in = lax.all_to_all(
            expert_in, cfg.axis, split_axis=0, concat_axis=1, tiled=True)

    w = params["experts"]
    hid = jnp.einsum("ech,ehf->ecf", expert_in, w["w1"].astype(cdt))
    hid = jax.nn.gelu(hid + w["b1"].astype(cdt)[:, None, :])
    out = jnp.einsum("ecf,efh->ech", hid, w["w2"].astype(cdt))
    out = out + w["b2"].astype(cdt)[:, None, :]

    if ranks > 1:
        out = lax.all_to_all(
            out, cfg.axis, split_axis=1, concat_axis=0, tiled=True)

    if impl == "einsum":
        comb_tok = (disp * gflat[:, None, None]).reshape(
            cfg.top_k, n, E, C).sum(0)                       # [n, E, C]
        y = jnp.einsum("tec,ech->th", comb_tok, out).astype(x.dtype)
    else:
        picked = out.reshape(E * C, h).at[slot_cell].get(
            mode="fill", fill_value=0)                       # [S, h]
        y = (picked * (gflat * keep.astype(cdt))[:, None]).reshape(
            cfg.top_k, n, h).sum(0).astype(x.dtype)

    # Switch load-balance loss over local tokens (pre-capacity fractions).
    f = jnp.mean(ohf.reshape(cfg.top_k, n, E).astype(jnp.float32), axis=(0, 1))
    p = jnp.mean(probs, axis=0)
    aux = E * jnp.sum(f * p)
    return y, aux


# ---------------------------------------------------------------------------
# the dropless routed layer: sigmoid scores, group-limited top-k, a share
# of the experts held here (DeepSeek-V3's layer; serving path). The
# capacity-factor layer above stays for the trainer.
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class RoutedConfig:
    """One dropless routed layer as ONE of the chips that share it runs
    it: the router scores all ``num_experts`` and picks ``top_k`` of
    them for every token, and this chip computes the part of the result
    that its own experts ``experts_held = (first, count)`` give, plus
    the shared expert. Nothing stands in for the absent chips. No token
    is dropped and no capacity exists: the (token, expert) pairs held
    here are sorted by expert and multiplied group by group."""

    num_experts: int
    experts_held: tuple            # (first, count)
    top_k: int
    n_group: int
    topk_group: int
    routed_scale: float

    def __post_init__(self):
        first, count = self.experts_held
        if not (0 <= first and count >= 1
                and first + count <= self.num_experts):
            raise ValueError(
                f"experts_held={self.experts_held} outside the "
                f"{self.num_experts} routed experts")
        if self.num_experts % self.n_group \
                or not 1 <= self.topk_group <= self.n_group:
            raise ValueError(
                f"n_group={self.n_group} must divide num_experts="
                f"{self.num_experts} and hold topk_group="
                f"{self.topk_group}")
        if self.top_k > self.topk_group * (self.num_experts
                                           // self.n_group):
            raise ValueError(
                f"top_k={self.top_k} experts do not fit in "
                f"{self.topk_group} groups")


def routed_select(rcfg: RoutedConfig, router, x):
    """``x [n, hidden]`` -> ``(experts [n, top_k] int32, weights [n,
    top_k] float32)`` over ALL the experts. Scores are the sigmoid of
    the float32 router product; the choice is made on score + bias (the
    bias corrects the load and never weights the result): a group's
    score is the sum of its two largest, the best ``topk_group`` groups
    stay, the best ``top_k`` experts inside them win; the winners'
    scores are renormalised to sum to 1 and scaled."""
    e, g = rcfg.num_experts, rcfg.n_group
    s = jax.nn.sigmoid(jnp.matmul(
        x.astype(jnp.float32), router["kernel"].astype(jnp.float32),
        precision=lax.Precision.HIGHEST))
    choice = s + router["bias"].astype(jnp.float32)
    g_score = jnp.sum(lax.top_k(choice.reshape(-1, g, e // g), 2)[0], -1)
    kept = lax.top_k(g_score, rcfg.topk_group)[1]
    g_mask = jnp.any(kept[:, :, None] == jnp.arange(g)[None, None], 1)
    choice = jnp.where(jnp.repeat(g_mask, e // g, -1), choice, -jnp.inf)
    experts = lax.top_k(choice, rcfg.top_k)[1].astype(jnp.int32)
    w = jnp.take_along_axis(s, experts, -1)
    w = w / (jnp.sum(w, -1, keepdims=True) + 1e-20) * rcfg.routed_scale
    return experts, w


def swiglu(x, p):
    """``(silu(x gate) * (x up)) down``."""
    h = jax.nn.silu(x @ p["gate"]) * (x @ p["up"])
    return h @ p["down"]


def grouped_swiglu(x, experts, sizes):
    """``x [m, hidden]`` whose rows are sorted by expert, ``sizes [E]``
    rows for each of the ``E`` experts held (rows past their sum are
    nobody's and come out as garbage the caller drops) through each
    row's own SwiGLU: three grouped products, no padding to a
    capacity."""
    from apex_tpu.kernels.grouped_matmul import grouped_matmul as dot

    h = jax.nn.silu(dot(x, experts["gate"], sizes)) \
        * dot(x, experts["up"], sizes)
    return dot(h, experts["down"], sizes)


def routed_ffn(rcfg: RoutedConfig, p, x, live=None):
    """The layer on ``x [n, hidden]`` -> ``(y [n, hidden], counts)``.
    ``p``: ``router {kernel [hidden, E_all], bias [E_all]}``, ``experts
    {gate, up [E_held, hidden, ffn], down [E_held, ffn, hidden]}``,
    ``shared {gate, up, down}``. ``live [n] bool`` marks the rows that
    are real tokens (padding is still computed: shapes are static; it
    is only kept out of the counts). ``counts`` is int32 ``[4]``: pairs
    routed (``live rows x top_k``), pairs held here, held experts that
    at least one live row chose, and held experts offered (their
    number, in a forward with a live row)."""
    n, k = x.shape[0], rcfg.top_k
    first, held = rcfg.experts_held
    with jax.named_scope("apex.moe.route"):
        experts, w = routed_select(rcfg, p["router"], x)
        # the pairs, flat and sorted by expert; the experts of other
        # chips sort behind the last one held and form no group
        local = experts.reshape(-1) - first
        mine = (local >= 0) & (local < held)
        key = jnp.where(mine, local, held)
        order = jnp.argsort(key, stable=True)
        sizes = jnp.sum(key[:, None] == jnp.arange(held)[None], 0,
                        dtype=jnp.int32)
        rows = order // k
    with jax.named_scope("apex.moe.experts"):
        out = grouped_swiglu(jnp.take(x, rows, axis=0), p["experts"],
                             sizes)
        gate = jnp.where(jnp.take(mine, order),
                         jnp.take(w.reshape(-1), order), 0.0)
        # garbage rows (nobody's) may hold anything, NaN included
        out = jnp.where(gate[:, None] != 0, out.astype(jnp.float32)
                        * gate[:, None], 0.0)
        # back to token order (the sort undone) and the k parts summed
        y = jnp.sum(jnp.take(out, jnp.argsort(order), axis=0
                             ).reshape(n, k, -1), 1)
    with jax.named_scope("apex.moe.shared"):
        y = y.astype(x.dtype) + swiglu(x, p["shared"])
    lv = jnp.ones((n,), bool) if live is None else live.reshape(-1)
    mine_live = mine.reshape(n, k) & lv[:, None]
    hit = jnp.any((local.reshape(n, k)[..., None] == jnp.arange(held))
                  & mine_live[..., None], (0, 1))
    counts = jnp.stack([jnp.sum(lv) * k, jnp.sum(mine_live), jnp.sum(hit),
                        jnp.any(lv) * held]).astype(jnp.int32)
    return y, counts
