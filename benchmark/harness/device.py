"""Device gate and the table of peaks.

A measurement path that finds no TPU, too few chips, or a chip that is
not in ``peaks.json`` fails here, before anything is timed. There is no
CPU fallback: ``--tiny-cpu`` (the sandbox-only rehearsal argument) gets a
device record whose platform says ``cpu`` and the runner then prints no
number under a device metric's name.
"""

from __future__ import annotations

import json
import os
from typing import Any, Dict, List, Optional

HERE = os.path.dirname(os.path.abspath(__file__))


class GateError(SystemExit):
    """The run may not be measured on this machine."""

    def __init__(self, msg: str):
        super().__init__(f"benchmark: {msg}")


def load_peaks() -> Dict[str, Dict[str, Any]]:
    with open(os.path.join(HERE, "peaks.json")) as f:
        return json.load(f)


def gate(chips: int, *, tiny_cpu: bool) -> Dict[str, Any]:
    """Name the device and refuse what may not be measured. Returns
    ``{"platform", "kind", "count", "devices", "peaks"}`` — ``devices``
    are the first ``chips`` devices, the only ones the cell may use."""
    import jax

    devs: List[Any] = jax.devices()
    dev = devs[0]
    out = {"platform": dev.platform, "kind": dev.device_kind,
           "count": chips, "devices": devs[:chips], "peaks": None}
    if len(devs) < chips:
        raise GateError(
            f"the cell needs {chips} chip(s), JAX sees {len(devs)} "
            f"{dev.platform} device(s)")
    if tiny_cpu:
        return out
    if dev.platform != "tpu":
        raise GateError(
            f"no accelerator: jax.devices()[0].platform is "
            f"{dev.platform!r}; a CPU run is never a measurement")
    from apex_tpu.kernels._utils import use_interpret

    if use_interpret():
        raise GateError("Pallas kernels would run interpreted on this "
                        "platform (APEX_TPU_FORCE_INTERPRET?)")
    peaks = load_peaks().get(dev.device_kind)
    if peaks is None:
        raise GateError(
            f"device kind {dev.device_kind!r} is not in "
            f"benchmark/harness/peaks.json; add its published peaks "
            f"with their source, never a default")
    out["peaks"] = peaks
    return out


def memory_peak_bytes(devices, plan_bytes: Optional[int]) -> Dict[str, int]:
    """``memory_peak_bytes``: ``peak_bytes_in_use`` of the fullest chip,
    as the runtime reports it. On this runtime that reading leaves out
    most of a running program's temporaries (PERF.md), so where the job
    has the compiler's plan of its largest program it is reported beside
    it as ``memory_plan_bytes``."""
    out = {"memory_peak_bytes": max(
        int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
        for d in devices)}
    if plan_bytes:
        out["memory_plan_bytes"] = int(plan_bytes)
    return out


def plan_bytes(compiled) -> int:
    """HBM one device needs to run ``compiled`` (``jit(...).lower(...)
    .compile()``): arguments + outputs + temporaries + code, less what
    is donated in place."""
    m = compiled.memory_analysis()
    return int(m.argument_size_in_bytes + m.output_size_in_bytes
               + m.temp_size_in_bytes + m.generated_code_size_in_bytes
               - m.alias_size_in_bytes)
