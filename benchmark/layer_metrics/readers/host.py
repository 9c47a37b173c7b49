"""Readers of the benchmark's own host-clock records."""

from __future__ import annotations

from typing import Any, Dict, Optional

from benchmark.harness import flops, stats


def step_ms_p50(ev: Dict[str, Any]) -> Optional[float]:
    """Median time between the completions of consecutive steps."""
    t = ev.get("step_done_at") or []
    gaps = [b - a for a, b in zip(t, t[1:])]
    mid = stats.median(gaps)
    return None if mid is None else mid * 1e3


def loader_wait_share(ev: Dict[str, Any]) -> Optional[float]:
    """Time inside ``TokenLoader.next()`` over the window, in percent."""
    if "loader_s" not in ev:
        return None
    return 100.0 * sum(ev["loader_s"]) / ev["window"]["seconds"]


def train_mfu(ev: Dict[str, Any]) -> Optional[float]:
    """Model FLOP/s utilisation: tokens a step over the median step
    time (the profiler's start and stop stall a traced window, so not
    tokens over the window) times the FLOPs a token needs
    (harness/flops.py; recompute not counted) over chips times the
    published bf16 peak, in percent."""
    step_ms = step_ms_p50(ev)
    if ev.get("peaks") is None or not step_ms:
        return None
    rate = ev["tokens_per_step"] / (step_ms * 1e-3)
    per_token = flops.train_flops_per_token(ev["shape"], ev["seq"])
    return 100.0 * rate * per_token / (
        ev["chips"] * ev["peaks"]["bf16_flops_per_s"])


def percentile_ms(ev: Dict[str, Any], key: str, q: float
                  ) -> Optional[float]:
    """The ``q``-th percentile of a list of seconds, in milliseconds."""
    v = stats.percentile(ev.get(key) or [], q)
    return None if v is None else v * 1e3


def tokens_per_s(ev: Dict[str, Any]) -> Optional[float]:
    """Output tokens streamed inside the window over the window."""
    if "tokens_in_window" not in ev:
        return None
    return ev["tokens_in_window"] / ev["window"]["seconds"]
