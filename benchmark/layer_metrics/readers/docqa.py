"""Readers of the ``serve_docqa`` kind: the work DeepSeek-V3.2's layer
needed inside the window (the scheduler's and the device's counts, the
benchmark's own record of decoded tokens) against the chip's peaks and
against the device time of the scopes that did it.

Counts cover the whole window and the device trace a slice of it, so
both are taken per second (as ``readers/device._roofline`` does): the
window is a steady closed loop. A reader that finds no count or no
region returns None, and the metric is left out of the line.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Sequence

from benchmark.harness import docqa_work as work
from benchmark.layer_metrics.readers import phases, regions
from benchmark.layer_metrics.readers.spans import _sections


def _per_s(ev: Dict[str, Any], name: str) -> Optional[float]:
    n = phases.count_total(ev, name)
    return None if n is None else n / ev["window"]["seconds"]


def count_share(ev: Dict[str, Any], of: str, per: Sequence[str],
                scale: float = 100.0) -> Optional[float]:
    """``scale * of / sum(per)`` over the counts inside the window."""
    n = phases.count_total(ev, of)
    parts = [phases.count_total(ev, p) for p in per]
    if n is None or any(p is None for p in parts) or not sum(parts):
        return None
    return scale * n / sum(parts)


def _decode_tokens(ev: Dict[str, Any]):
    """``(decoded tokens, cache positions they had filled)`` inside the
    window, from the benchmark's own stamps (``ServeJob._account``)."""
    lo, hi = ev["window"]["start"], ev["window"]["end"]
    reads = [n for t, n in ev.get("decode_reads") or [] if lo <= t < hi]
    toks = ev.get("decode_tokens_in_window")
    if not reads or not toks:
        return None
    return toks, float(sum(reads))


def serve_mfu(ev: Dict[str, Any]) -> Optional[float]:
    """FLOPs the window's question and answer tokens needed — matrices
    with each token's share of held experts, the head once per emitted
    token, the indexer's (query, key) pairs and the attended keys the
    scheduler counted — over the window at the bf16 peak, in
    percent."""
    s = ev["shape"]
    filled = phases.count_total(ev, "prefix.tokens_prefilled")
    scored = phases.count_total(ev, "dsa.keys_scored")
    attended = phases.count_total(ev, "dsa.keys_attended")
    dec = _decode_tokens(ev)
    if None in (filled, scored, attended, dec) or ev.get("peaks") is None:
        return None
    emitted = ev["tokens_in_window"]
    need = ((filled + dec[0]) * work.matrix_flops_per_token(s)
            + emitted * work.head_flops(s)
            + scored * work.index_flops_per_pair(s)
            + attended * work.attend_flops_per_key(s))
    return 100.0 * need / (ev["window"]["seconds"]
                           * ev["peaks"]["bf16_flops_per_s"])


def _own_seconds_per_s(ev, keep) -> Optional[float]:
    """Own device seconds of the operations ``keep(operation)`` picks,
    per second of the traced slice; None where the trace carries no
    region or nothing is picked."""
    found = regions._own_and_busy(ev)
    if found is None:
        return None
    lo, hi = regions.window(regions.scoped_trace(ev))
    secs = [own for e, own in found[0] if keep(e)]
    if not secs or hi <= lo:
        return None
    return sum(secs) / (hi - lo)


def decode_hbm_roofline(ev: Dict[str, Any], program: str,
                        dispatch: str) -> Optional[float]:
    """Bytes the window's decode steps needed — every step the
    non-expert weights and the held experts that were hit, every
    decoded token its row's index keys and its attended latent rows in
    every layer — at the HBM bandwidth, over the step program's device
    time, in percent."""
    s = ev["shape"]
    dec = _decode_tokens(ev)
    busy = _own_seconds_per_s(
        ev, lambda e: program in e[3].split(" ")[0])
    hit = count_share(ev, "moe.experts_hit", ["moe.experts_offered"], 1.0)
    steps = len(_sections(ev, (dispatch,))) * ev.get("decode_chunk", 0)
    if None in (dec, busy, hit) or not steps or ev.get("peaks") is None:
        return None
    toks, positions = dec
    attended = min(s["topk"], positions / toks) * toks
    need = (steps * (work.non_expert_weight_bytes(s)
                     + hit * work.moe_layers(s) * s["experts_held"]
                     * work.expert_bytes(s))
            + s["layers"] * (positions * work.index_key_bytes(s)
                             + attended * work.latent_row_bytes(s)))
    return 100.0 * need / ev["window"]["seconds"] / (
        ev["peaks"]["hbm_bytes_per_s"] * busy)


def _roofline(ev, names, flops_per_s, bytes_per_s) -> Optional[float]:
    took = _own_seconds_per_s(
        ev, lambda e: (regions.regions_of(e[4]) or [""])[-1] in names)
    if not took or ev.get("peaks") is None:
        return None
    least = max(flops_per_s / ev["peaks"]["bf16_flops_per_s"],
                bytes_per_s / ev["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least / took


def index_roofline(ev: Dict[str, Any], regions: Sequence[str]
                   ) -> Optional[float]:
    """The indexer: a product and a weighted sum for every (query, key)
    pair scored, and every forward's rows reading their context's index
    keys once a layer (a question's tokens share one read)."""
    s = ev["shape"]
    scored = _per_s(ev, "dsa.keys_scored")
    dec = _decode_tokens(ev)
    rows = _per_s(ev, "prefill.rows")
    shared = _per_s(ev, "prefix.tokens_shared")
    filled = _per_s(ev, "prefix.tokens_prefilled")
    if None in (scored, dec, rows, shared, filled):
        return None
    positions = dec[1] / ev["window"]["seconds"] + shared + filled
    return _roofline(ev, regions, scored * work.index_flops_per_pair(s),
                     s["layers"] * positions * work.index_key_bytes(s))


def sparse_attn_roofline(ev: Dict[str, Any], regions: Sequence[str]
                         ) -> Optional[float]:
    """Attention over the selection: every attended key scored and
    summed by every head, its latent row read once for its query."""
    s = ev["shape"]
    attended = _per_s(ev, "dsa.keys_attended")
    if attended is None:
        return None
    return _roofline(ev, regions, attended * work.attend_flops_per_key(s),
                     attended * work.latent_row_bytes(s))


def experts_roofline(ev: Dict[str, Any], regions: Sequence[str]
                     ) -> Optional[float]:
    """The grouped products: three matrices for every pair held, every
    expert that was hit read once a forward."""
    s = ev["shape"]
    held, hit = _per_s(ev, "moe.pairs_held"), _per_s(ev, "moe.experts_hit")
    if held is None or hit is None:
        return None
    return _roofline(ev, regions, held * 2.0 * work.expert_params(s),
                     hit * work.expert_bytes(s))
