"""Test backbone: simulate an 8-device mesh on CPU.

Apex emulates multi-node topology by spawning one NCCL process per local GPU
(apex/transformer/testing/distributed_test_base.py (U)). On the XLA side we
do strictly better (SURVEY.md §4): force the host platform to expose 8
virtual CPU devices and run every distributed test single-process on a real
``jax.sharding.Mesh``. Must run before any jax backend is initialised.
"""

import os

import re

_flags = os.environ.get("XLA_FLAGS", "")
_m = re.search(r"--xla_force_host_platform_device_count=(\d+)", _flags)
if _m is None or int(_m.group(1)) < 8:
    if _m is not None:
        _flags = _flags.replace(_m.group(0), "")
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_threefry_partitionable", True)

# Persistent compile cache, placed by the rule every entry point shares
# (apex_tpu._capabilities.enable_compilation_cache): test models are
# tiny, so XLA compile time dominates the CPU-mesh suite — a warm cache
# halves wall time, provided even sub-second compiles are kept.
from apex_tpu._capabilities import enable_compilation_cache  # noqa: E402

if enable_compilation_cache():
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)

import pytest  # noqa: E402


@pytest.fixture(scope="session")
def devices8():
    devs = jax.devices()
    assert len(devs) >= 8, (
        "tests require 8 simulated devices; conftest must run before backend init"
    )
    return devs[:8]


# --- tier-1 marker audit -----------------------------------------------------
#
# The tier-1 run (-m 'not slow') has a hard wall-clock budget
# (ROADMAP.md). A test that quietly grows past ~60 s belongs behind the
# `slow` marker — this hook turns such a test's own PASSING report into
# a failure naming it, so the budget stays honest as suites grow
# instead of eroding one slow test at a time. Tunable/disable-able via
# APEX_TPU_TIER1_BUDGET_S (0 disables — e.g. profiling runs under a
# debugger, where wall time means nothing).
#
# The audit only arms on a WARM compile cache: per-test wall time
# includes XLA compiles, and a cold .jax_cache (fresh clone, wiped
# cache — the suite is ~25 min cold vs ~10 min warm) would spuriously
# fail compile-heavy tests that are well inside budget warm. An
# explicit APEX_TPU_TIER1_BUDGET_S overrides the heuristic either way.
#
# Static sibling: the TIER1-COST lint rule (apex_tpu.analysis) flags
# the known expensive *pattern* — a test calling Engine.warmup()
# without the slow marker — before the budget is ever spent; this hook
# stays as the backstop for everything the pattern can't see. The pair
# is kept honest by tests/test_static_analysis.py (lint battery over
# tests/, allowlist pinned) and test_marker_audit.py (this predicate).


def _compile_cache_warm(min_entries: int = 500) -> bool:
    d = jax.config.jax_compilation_cache_dir
    try:
        return d is not None and len(os.listdir(d)) >= min_entries
    except OSError:
        return False


TIER1_BUDGET_S = (
    float(os.environ["APEX_TPU_TIER1_BUDGET_S"])
    if "APEX_TPU_TIER1_BUDGET_S" in os.environ
    else (60.0 if _compile_cache_warm() else 0.0))


def audit_overtime(duration_s: float, has_slow_marker: bool,
                   budget_s: float = TIER1_BUDGET_S) -> bool:
    """THE audit predicate (unit-tested in test_marker_audit.py): an
    unmarked test over the budget is an offender; slow-marked tests are
    exempt at any duration, and a non-positive budget disables the
    audit."""
    return budget_s > 0 and duration_s > budget_s and not has_slow_marker


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_makereport(item, call):
    outcome = yield
    rep = outcome.get_result()
    if rep.when != "call" or not rep.passed:
        return  # only audit tests that would otherwise pass
    if audit_overtime(rep.duration,
                      item.get_closest_marker("slow") is not None):
        rep.outcome = "failed"
        rep.longrepr = (
            f"tier-1 marker audit: {item.nodeid} took "
            f"{rep.duration:.1f}s > {TIER1_BUDGET_S:.0f}s without "
            f"@pytest.mark.slow — mark it slow (it runs in the soak "
            f"tier) or make it faster; the tier-1 budget is a hard "
            f"timeout (ROADMAP.md). Set APEX_TPU_TIER1_BUDGET_S to "
            f"tune/disable.")
