"""Readers of the profiler trace (harness/trace.py). A kernel is found
by regular expressions over the operation names and scopes the trace
prints; where the trace names no such operation the reader returns None
and the metric is left out of the line, never printed as 0."""

from __future__ import annotations

from typing import Any, Dict, Optional, Sequence

from benchmark.harness import flops, stats, trace
from benchmark.layer_metrics.readers import host


def _kernel_over(ev: Dict[str, Any], patterns: Sequence[str], whole
                 ) -> Optional[float]:
    """The kernel's device seconds over ``whole(trace)`` seconds."""
    tr = ev.get("trace")
    if not tr or not tr["devices"]:
        return None
    secs, base = trace.kernel_seconds(tr, patterns), whole(tr)
    if secs is None or not base:
        return None
    return secs / base


def kernel_share(ev: Dict[str, Any], patterns: Sequence[str]
                 ) -> Optional[float]:
    """Device time of the kernel over device busy time, in percent."""
    share = _kernel_over(ev, patterns, trace.busy_seconds)
    return None if share is None else 100.0 * share


def _roofline(ev, patterns, needed_per_s: float, peak: str
              ) -> Optional[float]:
    """The least time the chip could take for the work a second of the
    window needs, over the kernel's device time in a second of the
    traced slice, in percent."""
    share = _kernel_over(ev, patterns, trace.window_seconds)
    if not share or ev.get("peaks") is None:
        return None
    return 100.0 * (needed_per_s / ev["peaks"][peak]) / share


def flash_roofline_train(ev: Dict[str, Any], patterns: Sequence[str]
                         ) -> Optional[float]:
    """Forward and backward attention FLOPs every trained sequence
    needs (causal, at the real length), per chip, against the bf16
    peak."""
    step_ms = host.step_ms_p50(ev)
    if not step_ms:
        return None
    seq = ev["seq"]
    # sequences a second from the median step time: the profiler's
    # start and stop stall a traced window
    seqs_per_s = ev["tokens_per_step"] / seq / (step_ms * 1e-3)
    needed = flops.flash_flops([seq], ev["shape"], backward=True)
    return _roofline(ev, patterns, needed * seqs_per_s / ev["chips"],
                     "bf16_flops_per_s")


def flash_roofline_prefill(ev: Dict[str, Any], patterns: Sequence[str]
                           ) -> Optional[float]:
    """Forward attention FLOPs the prompts admitted inside the window
    need at their real lengths (bucket padding is not needed work)."""
    lo, hi = ev["window"]["start"], ev["window"]["end"]
    lens = [n for t, n in ev.get("admitted") or [] if lo <= t < hi]
    if not lens:
        return None
    needed = flops.flash_flops(lens, ev["shape"], backward=False)
    return _roofline(ev, patterns, needed / ev["window"]["seconds"],
                     "bf16_flops_per_s")


def decode_attn_roofline(ev: Dict[str, Any], patterns: Sequence[str]
                         ) -> Optional[float]:
    """Cache bytes the decode steps inside the window had to read (the
    benchmark's own count of filled positions per emitted token)
    against the HBM bandwidth."""
    lo, hi = ev["window"]["start"], ev["window"]["end"]
    positions = [n for t, n in ev.get("decode_reads") or [] if lo <= t < hi]
    if not positions:
        return None
    needed = flops.decode_attn_bytes(positions, ev["shape"])
    return _roofline(ev, patterns, needed / ev["window"]["seconds"],
                     "hbm_bytes_per_s")


def collective_share(ev: Dict[str, Any], exposed: bool = False
                     ) -> Optional[float]:
    """Device 0's time in collective operations (or, ``exposed``, the
    part of it with no other operation running) over its busy time, in
    percent."""
    tr = ev.get("trace")
    if not tr or not tr["devices"]:
        return None
    both = trace.collective_seconds(tr)
    busy = stats.union_seconds((e[1], e[2]) for e in tr["devices"][0])
    if both is None or not busy:
        return None
    return 100.0 * both[1 if exposed else 0] / busy
