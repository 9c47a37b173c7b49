"""Readers of counters: the recompile sentinel's, the compiler's plan."""

from __future__ import annotations

from typing import Any, Dict, Optional


def value(ev: Dict[str, Any], key: str, scale: float = 1.0
          ) -> Optional[float]:
    v = ev.get(key)
    return None if v is None else float(v) * scale
