"""apex_tpu.analysis — the static linter's own battery.

Three layers:

1. the merge gates: the full-tree run (``apex_tpu bench.py examples``,
   every rule) and the tests-tree TIER1-COST run are clean, fast
   (<15 s — pure-Python AST, no compile), and the active-suppression
   count is pinned so it can only go down;
2. per-rule positive/negative pairs over synthetic trees — every rule
   must FIRE on its synthetic violation and stay SILENT on the clean
   twin (a linter that cannot fire is indistinguishable from one that
   works);
3. the suppression mechanism itself: justified noqa silences and is
   counted, bare noqa is a finding, unused noqa is a finding, and a
   disabled rule's suppressions are out of scope for the run.

No jax/numpy anywhere in the analyzer (pinned by the purged-import
subprocess test at the bottom, same pattern as serving.api's).
"""

import os
import subprocess
import sys
import textwrap
import time

import pytest

from apex_tpu.analysis import parse_abi_versions
from apex_tpu.analysis.core import run_analysis, summary_dict

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: the allowlist pin (satellite contract: this number may only go
#: DOWN; new suppressions need to displace an old one or justify a
#: bump here with the review that approved it)
#: 25 -> 24 (fleet-router PR): test_fleet.py's shared tiny-replica
#: builder `_mk_sched` added one def-line suppression (same shape as
#: test_paged_cache's `_mk_engine`), displaced by slow-marking the
#: prefix-registration contract test (its two suppressions removed);
#: tier-1 runtime offset by slow-marking variant-redundant serving
#: oracles (see the `fleet-router tier-1 offset` markers)
#: 24 -> 22 (multi-tenant PR): test_tenancy.py's shared adapter-engine
#: builder `_mk_engine` added one def-line suppression, displaced by
#: slow-marking the two-engine scheduler prefix-detection composition
#: (its two suppressions removed) and the spec×constrained composition
#: (one removed) — see the `multi-tenant tier-1 offset` markers
#: 22 -> 21 (slo-observatory PR): test_slo.py is host-only (no warmup,
#: no new suppressions); the quantized+prefix+guard composition in
#: test_kv_cache was slow-marked as the tier-1 runtime offset and its
#: one suppression removed — see the `slo-observatory tier-1 offset`
#: marker
#: 21 -> 21 (durable-journal PR): test_journal.py's crash-recovery
#: oracle added one warmed-engine suppression, displaced by
#: slow-marking test_kv_cache's pool-reset-on-failed-insert corner
#: (register/match/admission stay tier-1 via the hit-parity oracle) —
#: see the `durable-journal tier-1 offset` marker
MAX_ACTIVE_SUPPRESSIONS = 21


def _rules_of(result):
    return sorted({f.rule for f in result.findings})


def _synth(tmp_path, files, targets=None, rules=None):
    (tmp_path / "pyproject.toml").write_text("[project]\nname='synth'\n")
    for rel, body in files.items():
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(body))
    targets = targets or sorted({r.split("/")[0] for r in files})
    targets = [str(tmp_path / t) for t in targets]
    return run_analysis(targets, root=str(tmp_path), rules=rules)


# --------------------------------------------------------------------------
# merge gates
# --------------------------------------------------------------------------


def test_full_tree_clean_and_fast():
    t0 = time.monotonic()
    res = run_analysis(
        [os.path.join(REPO, "apex_tpu"), os.path.join(REPO, "bench.py"),
         os.path.join(REPO, "examples")], root=REPO)
    elapsed = time.monotonic() - t0
    assert not res.findings, "\n".join(f.render() for f in res.findings)
    assert res.exit_code == 0
    # pure-Python AST over ~16k lines; a budget blowout means someone
    # added quadratic work, not that the tree got bigger
    assert elapsed < 15.0, f"analysis took {elapsed:.1f}s (budget 15s)"
    s = summary_dict(res)
    assert s["exit_code"] == 0 and s["counts"] == {}


def test_tests_tree_tier1_battery_clean_and_pinned():
    res = run_analysis([os.path.join(REPO, "tests")], root=REPO,
                       rules=["TIER1-COST"])
    assert not res.findings, "\n".join(f.render() for f in res.findings)
    active = len(res.suppressions_used)
    # upper bound only: reaching zero (every warmup test slow-marked or
    # restructured) is the contract's ideal end state, not a failure
    assert active <= MAX_ACTIVE_SUPPRESSIONS, (
        f"{active} active TIER1-COST suppressions vs pin "
        f"{MAX_ACTIVE_SUPPRESSIONS} — the allowlist only shrinks; "
        f"mark new warmup tests slow or displace an old suppression")


def test_changed_mode_git_failure_is_a_usage_error(tmp_path):
    # a failed git query must not read as "nothing changed" — that
    # would let the pre-commit gate pass without linting anything
    (tmp_path / "pyproject.toml").write_text("[project]\nname='x'\n")
    (tmp_path / "mod.py").write_text("X = 1\n")
    with pytest.raises(ValueError, match="--changed"):
        run_analysis([str(tmp_path / "mod.py")], root=str(tmp_path),
                     changed_only=True)


def test_suppression_in_bench_visible_to_partial_runs(tmp_path):
    # METRIC-DRIFT anchors doc-side findings in bench.py; a justified
    # suppression there must silence them even when bench.py is not a
    # target of the (--changed-style) partial run
    files = {
        "apex_tpu/__init__.py": "",
        "apex_tpu/serving/__init__.py": "",
        "apex_tpu/serving/sched.py": '''
            def wire(registry):
                registry.counter("serving_ok_total", "")
        ''',
        "bench.py":
            'K = "serving_ghost_total"  # apex: noqa[METRIC-DRIFT]: trajectory key, deliberately unregistered\n',
        "docs/API.md": "`serving_ok_total`\n",
    }
    res = _synth(tmp_path, files, targets=["apex_tpu"])
    assert not res.findings, "\n".join(f.render() for f in res.findings)


def test_overlapping_targets_analyze_each_file_once(tmp_path):
    # `analysis pkg pkg/mod.py` must not load mod.py twice — that would
    # double every per-target finding and the pinned suppressions.active
    # count (the shrink-only contract number)
    (tmp_path / "pyproject.toml").write_text("[project]\nname='x'\n")
    pkg = tmp_path / "pkg"
    pkg.mkdir()
    (pkg / "__init__.py").write_text("")
    (pkg / "mod.py").write_text(
        'def f():\n'
        '    """See apex/amp/scaler.py."""  # apex: noqa[CITATION]: synthetic\n')
    res = run_analysis([str(pkg), str(pkg / "mod.py")],
                       root=str(tmp_path))
    assert res.files == 2, res.files
    assert len(res.suppressions_used) == 1
    assert not res.findings, "\n".join(f.render() for f in res.findings)
    # a stale noqa must surface exactly once, not once per duplicate
    (pkg / "mod.py").write_text(
        'X = 1  # apex: noqa[CITATION]: synthetic stale\n')
    res = run_analysis([str(pkg), str(pkg / "mod.py")],
                       root=str(tmp_path))
    assert [f.rule for f in res.findings] == ["NOQA-UNUSED"], \
        "\n".join(f.render() for f in res.findings)


def test_missing_target_is_a_usage_error(tmp_path):
    # a nonexistent target must be exit 2, not a 0-files "clean" exit 0
    # from the merge gate itself (the CLI's relative default targets run
    # from the wrong cwd are exactly this shape)
    with pytest.raises(ValueError, match="does not exist"):
        run_analysis([str(tmp_path / "nope")], root=str(tmp_path))
    from apex_tpu.analysis.__main__ import main
    assert main([str(tmp_path / "nope")]) == 2


def test_repo_abi_versions_parse_and_agree():
    cpp, py = parse_abi_versions(REPO)
    assert cpp is not None and py is not None and cpp == py


# --------------------------------------------------------------------------
# TRACER-LEAK
# --------------------------------------------------------------------------


_TRACER_BAD = '''
    import jax
    import numpy as np

    def leaky(x, n):
        if x > 0:            # if on tracer
            return int(x)    # coercion
        y = np.asarray(x)    # numpy on tracer
        return x.item() + n  # .item on tracer

    j = jax.jit(leaky, static_argnums=(1,))
'''

_TRACER_CLEAN = '''
    import jax
    import jax.numpy as jnp

    def fine(cfg, x, masks=None):
        if cfg:                      # static (untainted at call sites)
            x = x + 1
        if masks is not None:        # structural — is-None is static
            x = jnp.where(masks, x, 0)
        if "k" in {"k": 1}:          # key membership is structure
            pass
        b = x.shape[0]               # shape access is static
        if b > 2:
            x = x * 2
        return jnp.sum(x)

    wrap = lambda f: jax.jit(jax.shard_map(f))
    g = wrap(lambda x: fine(3, x))
'''


def test_tracer_leak_fires_on_synthetic_violations(tmp_path):
    res = _synth(tmp_path, {"pkg/mod.py": _TRACER_BAD,
                            "pkg/__init__.py": ""})
    leaks = [f for f in res.findings if f.rule == "TRACER-LEAK"]
    msgs = " | ".join(f.message for f in leaks)
    assert len(leaks) == 4, msgs
    assert "int()" in msgs and ".item()" in msgs \
        and "np.asarray" in msgs and "`if`" in msgs


def test_tracer_leak_static_escapes_stay_clean(tmp_path):
    res = _synth(tmp_path, {"pkg/mod.py": _TRACER_CLEAN,
                            "pkg/__init__.py": ""})
    assert "TRACER-LEAK" not in _rules_of(res), \
        "\n".join(f.render() for f in res.findings)


def test_tracer_leak_walks_cross_module_calls(tmp_path):
    # the jit site lives in a.py; the leak lives in the apex_tpu
    # package module it calls — the walk must cross the import
    res = _synth(tmp_path, {
        "apex_tpu/__init__.py": "",
        "apex_tpu/helper.py": '''
            def inner(cfg, v):
                if cfg:
                    return v          # cfg stays static
                return float(v)       # v is traced -> leak
        ''',
        "pkg/__init__.py": "",
        "pkg/a.py": '''
            import jax
            from apex_tpu import helper

            def entry(v):
                return helper.inner(False, v)

            j = jax.jit(entry)
        ''',
    }, targets=None)
    leaks = [f for f in res.findings if f.rule == "TRACER-LEAK"]
    assert [f.path for f in leaks] == ["apex_tpu/helper.py"], \
        "\n".join(f.render() for f in res.findings)
    assert "float()" in leaks[0].message


def test_tracer_leak_sees_aliased_jit_spellings(tmp_path):
    # `import jax as j` call sites and `from jax import jit as J`
    # decorators are the same entry point as the literal `jax.jit` —
    # modgraph shares rules/compiled.py's alias-aware jit_call_names,
    # so the two discoveries cannot drift apart again
    res = _synth(tmp_path, {
        "pkg/__init__.py": "",
        "pkg/via_module_alias.py": '''
            import jax as j

            def f(x):
                return int(x)      # leak under j.jit

            g = j.jit(f)
        ''',
        "pkg/via_decorator_alias.py": '''
            from jax import jit as J

            @J
            def h(x):
                return float(x)    # leak under aliased decorator
        ''',
    })
    leaks = sorted(f.path for f in res.findings
                   if f.rule == "TRACER-LEAK")
    assert leaks == ["pkg/via_decorator_alias.py",
                     "pkg/via_module_alias.py"], \
        "\n".join(f.render() for f in res.findings)


# --------------------------------------------------------------------------
# USE-AFTER-DONATE
# --------------------------------------------------------------------------


_DONATE_BAD = '''
    import jax

    class Eng:
        def __init__(self):
            self._step = jax.jit(lambda c, s: (c, s),
                                 donate_argnums=(0, 1))

        def bad_read(self):
            out = self._step(self.cache, self.state)   # no rebind
            return self.cache                          # read-after
'''

_DONATE_CLEAN = '''
    import jax

    class Eng:
        def __init__(self):
            self._step = jax.jit(lambda p, c, s: (c, s),
                                 donate_argnums=(1, 2))

        def good(self):
            self.cache, self.state = self._step(
                self.params, self.cache, self.state)   # rebind-at-dispatch
            return self.cache                          # rebound: fine
'''


def test_use_after_donate_sees_jit_import_alias(tmp_path):
    # `from jax import jit as J` must be the same entry point as
    # `jax.jit` — kept consistent with modgraph's import-aware matcher
    res = _synth(tmp_path, {"pkg/mod.py": '''
        from jax import jit as J

        class Eng:
            def __init__(self):
                self._step = J(lambda c: c, donate_argnums=(0,))

            def bad(self):
                out = self._step(self.cache)   # no rebind
                return self.cache
    ''', "pkg/__init__.py": ""})
    hits = [f for f in res.findings if f.rule == "USE-AFTER-DONATE"]
    assert len(hits) == 2, "\n".join(f.render() for f in res.findings)


def test_use_after_donate_fires(tmp_path):
    res = _synth(tmp_path, {"pkg/mod.py": _DONATE_BAD,
                            "pkg/__init__.py": ""})
    hits = [f for f in res.findings if f.rule == "USE-AFTER-DONATE"]
    msgs = " ".join(f.message for f in hits)
    # 2 unrebound donations (cache, state) + 1 read-after-donate
    assert len(hits) == 3, "\n".join(f.render() for f in hits)
    assert "does not rebind" in msgs and "read before being rebound" in msgs


def test_rebind_at_dispatch_is_clean(tmp_path):
    res = _synth(tmp_path, {"pkg/mod.py": _DONATE_CLEAN,
                            "pkg/__init__.py": ""})
    assert "USE-AFTER-DONATE" not in _rules_of(res), \
        "\n".join(f.render() for f in res.findings)


# --------------------------------------------------------------------------
# RECOMPILE-HAZARD
# --------------------------------------------------------------------------


_HAZARD_BAD = '''
    import jax

    def f(x, n):
        return x

    g = jax.jit(f, static_argnums=(1,))

    def call(xs):
        return g(f"{xs}", len(xs))
'''


def test_recompile_hazard_fires(tmp_path):
    res = _synth(tmp_path, {"pkg/mod.py": _HAZARD_BAD,
                            "pkg/__init__.py": ""})
    hits = [f for f in res.findings if f.rule == "RECOMPILE-HAZARD"]
    msgs = " ".join(f.message for f in hits)
    assert len(hits) == 2, "\n".join(f.render() for f in hits)
    assert "f-string" in msgs and "len(...)" in msgs


def test_recompile_hazard_named_args_clean(tmp_path):
    res = _synth(tmp_path, {"pkg/mod.py": '''
        import jax

        def f(x, n):
            return x

        g = jax.jit(f, static_argnums=(1,))

        def call(xs, k):
            return g(xs, k)     # names, not per-call-fresh displays
    ''', "pkg/__init__.py": ""})
    assert "RECOMPILE-HAZARD" not in _rules_of(res)


# --------------------------------------------------------------------------
# PAGE-TABLE-STATIC
# --------------------------------------------------------------------------


def test_page_table_static_fires_on_request_derived_shape(tmp_path):
    res = _synth(tmp_path, {"pkg/mod.py": '''
        import numpy as np

        def admit(self, prompt, max_tokens):
            # the recompile-hazard class this rule exists for: table
            # geometry measured from the live request
            self._tables = np.zeros(
                (self.slots, len(prompt) // self.page_size), np.int32)
            pages = np.full((prompt.size // 4,), 0, np.int32)
            return pages
    ''', "pkg/__init__.py": ""})
    hits = [f for f in res.findings if f.rule == "PAGE-TABLE-STATIC"]
    msgs = "\n".join(f.render() for f in hits)
    assert len(hits) == 2, msgs
    assert any("len(...)" in f.message and "_tables" in f.message
               for f in hits), msgs
    assert any(".size" in f.message and "pages" in f.message
               for f in hits), msgs


def test_page_table_static_clean_on_config_shapes(tmp_path):
    res = _synth(tmp_path, {"pkg/mod.py": '''
        import numpy as np

        def build(self, ecfg):
            # config-derived constants: the blessed spelling
            max_pages = -(-ecfg.max_seq_len // ecfg.page_size)
            self._tables = np.full((ecfg.slots, max_pages), 0, np.int32)
            row_pages = np.zeros((max_pages,), np.int32)
            # table CONTENTS from request data are fine — tables are
            # data; only shapes are constrained
            row_pages[:len(self.shared)] = self.shared
            # non-table arrays may size from data (other rules' turf)
            buf = np.zeros((len(self.queue),), np.int32)
            return row_pages, buf
    ''', "pkg/__init__.py": ""})
    assert "PAGE-TABLE-STATIC" not in _rules_of(res), \
        "\n".join(f.render() for f in res.findings)


# --------------------------------------------------------------------------
# HOST-TIER-STATIC
# --------------------------------------------------------------------------


def test_host_tier_static_fires_on_live_derived_shape(tmp_path):
    res = _synth(tmp_path, {"pkg/mod.py": '''
        import numpy as np

        def park(self, act, payload):
            # the swap-recompile class this rule exists for: host
            # mirror geometry measured from the live conversation
            host_buf = np.zeros(
                (len(act.pages), self.page_size), np.float32)
            self._swap_rows = np.full((payload.size,), 0, np.int32)
            return host_buf
    ''', "pkg/__init__.py": ""})
    hits = [f for f in res.findings if f.rule == "HOST-TIER-STATIC"]
    msgs = "\n".join(f.render() for f in hits)
    assert len(hits) == 2, msgs
    assert any("len(...)" in f.message and "host_buf" in f.message
               for f in hits), msgs
    assert any(".size" in f.message and "_swap_rows" in f.message
               for f in hits), msgs


def test_host_tier_static_clean_on_rung_shapes(tmp_path):
    res = _synth(tmp_path, {"pkg/mod.py": '''
        import numpy as np

        def build(self, ecfg):
            # rung-derived constants: the blessed spelling
            rung = max(self.swap_rungs)
            host_buf = np.zeros((rung, ecfg.page_size), np.float32)
            spill_stage = np.empty((ecfg.lora_rank,), np.float32)
            # host-buffer CONTENTS from live data are fine — buffers
            # are data; only geometry is constrained
            host_buf[:len(self.priv)] = self.priv
            # non-host-named arrays may size from data (other rules)
            buf = np.zeros((len(self.queue),), np.int32)
            return host_buf, spill_stage, buf
    ''', "pkg/__init__.py": ""})
    assert "HOST-TIER-STATIC" not in _rules_of(res), \
        "\n".join(f.render() for f in res.findings)


# --------------------------------------------------------------------------
# WARMUP-COVERAGE
# --------------------------------------------------------------------------


_WARMUP_BAD = '''
    import jax

    class Eng:
        def __init__(self):
            self._step = jax.jit(lambda c: c)
            self._extra = jax.jit(lambda c: c)    # never warmed/tracked

        def warmup(self):
            self._step(0)

        def compiled_cache_sizes(self):
            return {"step": self._step._cache_size()}
'''


def test_warmup_coverage_fires_on_forgotten_variant(tmp_path):
    res = _synth(tmp_path, {"pkg/mod.py": _WARMUP_BAD,
                            "pkg/__init__.py": ""})
    hits = [f for f in res.findings if f.rule == "WARMUP-COVERAGE"]
    assert len(hits) == 2, "\n".join(f.render() for f in hits)
    assert all("_extra" in f.message for f in hits)


_KNOB_ENGINE = '''
    import jax

    class Eng:
        def __init__(self):
            self._step_variants = {}
            for c in (1, 2):
                self._step_variants[c] = jax.jit(lambda x: x)
            self._retire = jax.jit(lambda s: s)

        def warmup(self):
            for c, fn in sorted(self._step_variants.items()):
                fn(0)
            self._retire(0)

        def compiled_cache_sizes(self):
            out = {"retire": self._retire._cache_size()}
            for c, fn in sorted(self._step_variants.items()):
                out[f"step_c{c}"] = fn._cache_size()
            return out
'''


def test_warmup_coverage_knob_ladder_link(tmp_path):
    """The serving.tuner half: VARIANT_KNOBS entries must name a
    compiled-program dict family on a warmup-defining class — a knob
    pointing at nothing could ladder candidates warmup never compiles."""
    # positive: the declared family exists, is warmed, is tracked
    res = _synth(tmp_path, {
        "pkg/eng.py": _KNOB_ENGINE,
        "pkg/tuner.py":
            'VARIANT_KNOBS = {"decode_chunk": "_step_variants"}\n',
        "pkg/__init__.py": ""})
    assert "WARMUP-COVERAGE" not in _rules_of(res), \
        "\n".join(f.render() for f in res.findings)
    # negative: the knob maps to a family nobody builds
    (tmp_path / "bad").mkdir()
    res = _synth(tmp_path / "bad", {
        "pkg/eng.py": _KNOB_ENGINE,
        "pkg/tuner.py":
            'VARIANT_KNOBS = {"spec_k": "_missing_variants"}\n',
        "pkg/__init__.py": ""})
    hits = [f for f in res.findings if f.rule == "WARMUP-COVERAGE"]
    assert len(hits) == 1, "\n".join(f.render() for f in res.findings)
    assert "_missing_variants" in hits[0].message \
        and "'spec_k'" in hits[0].message
    assert hits[0].path == "pkg/tuner.py"
    # negative: the family exists but warmup never touches it — the
    # BASE checks fire on the engine side (the ladder link holds)
    (tmp_path / "unwarmed").mkdir()
    res = _synth(tmp_path / "unwarmed", {
        "pkg/eng.py": _KNOB_ENGINE.replace(
            """            for c, fn in sorted(self._step_variants.items()):
                fn(0)
""", ""),
        "pkg/tuner.py":
            'VARIANT_KNOBS = {"decode_chunk": "_step_variants"}\n',
        "pkg/__init__.py": ""})
    hits = [f for f in res.findings if f.rule == "WARMUP-COVERAGE"]
    assert any("_step_variants" in f.message
               and "warmup()" in f.message for f in hits), \
        "\n".join(f.render() for f in res.findings)


def test_warmup_coverage_clean_via_direct_and_getattr_refs(tmp_path):
    res = _synth(tmp_path, {"pkg/mod.py": '''
        import jax

        class Eng:
            def __init__(self):
                self._step = jax.jit(lambda c: c)
                self._admits = {}
                self._admits[(8, 1)] = jax.jit(lambda c: c)

            def warmup(self):
                self._helper()
                for k, fn in sorted(self._admits.items()):
                    fn(0)

            def _helper(self):
                self._step(0)

            def compiled_cache_sizes(self):
                out = {n: getattr(self, f"_{n}")._cache_size()
                       for n in ("step",)}
                out["admit"] = len(self._admits)
                return out
    ''', "pkg/__init__.py": ""})
    assert "WARMUP-COVERAGE" not in _rules_of(res), \
        "\n".join(f.render() for f in res.findings)


# --------------------------------------------------------------------------
# ABI-LOCKSTEP
# --------------------------------------------------------------------------


def _abi_tree(version_py):
    return {
        "csrc/host_runtime.cpp":
            "static const int32_t kAbiVersion = 3;\n",
        "apex_tpu/__init__.py": "",
        "apex_tpu/_native/__init__.py":
            f"_ABI_VERSION = {version_py}\n",
    }


def test_abi_lockstep_fires_on_drift(tmp_path):
    res = _synth(tmp_path, _abi_tree(2), targets=["apex_tpu"])
    hits = [f for f in res.findings if f.rule == "ABI-LOCKSTEP"]
    assert len(hits) == 1 and "kAbiVersion=3" in hits[0].message \
        and "_ABI_VERSION=2" in hits[0].message


def test_abi_lockstep_clean_in_lockstep(tmp_path):
    res = _synth(tmp_path, _abi_tree(3), targets=["apex_tpu"])
    assert "ABI-LOCKSTEP" not in _rules_of(res)


# --------------------------------------------------------------------------
# METRIC-DRIFT
# --------------------------------------------------------------------------


_METRIC_SRC = '''
    def wire(registry):
        registry.counter("serving_good_total", "documented")
        registry.gauge("serving_orphan_total", "not in the doc")
'''


def test_metric_drift_both_directions(tmp_path):
    res = _synth(tmp_path, {
        "apex_tpu/__init__.py": "",
        "apex_tpu/serving/__init__.py": "",
        "apex_tpu/serving/sched.py": _METRIC_SRC,
        "docs/API.md":
            "`serving_good_total` and `serving_ghost_total` exist.\n",
    }, targets=["apex_tpu"])
    hits = [f for f in res.findings if f.rule == "METRIC-DRIFT"]
    msgs = "\n".join(f.render() for f in hits)
    assert len(hits) == 2, msgs
    assert any("serving_ghost_total" in f.message
               and f.path == "docs/API.md" for f in hits), msgs
    assert any("serving_orphan_total" in f.message
               and f.path == "apex_tpu/serving/sched.py"
               for f in hits), msgs


def test_metric_drift_span_colliding_with_engine_api(tmp_path):
    # `fetch` is both an Engine method and a span-section name; a BARE
    # doc mention (`engine.fetch`) is a span claim and must be backed
    # by a registration — only the call spelling (`engine.fetch()`) is
    # excused as an API reference
    res = _synth(tmp_path, {
        "apex_tpu/__init__.py": "",
        "apex_tpu/serving/__init__.py": "",
        "apex_tpu/serving/engine.py": '''
            class Engine:
                def fetch(self):
                    pass
        ''',
        "apex_tpu/serving/sched.py": '''
            def wire(registry, spans):
                registry.counter("serving_ok_total", "")
                spans.section("engine.dispatch", 0.0, 0.0)
        ''',
        "docs/API.md": "`serving_ok_total`; `engine.dispatch` and "
                       "`engine.fetch` spans; call `engine.fetch()` "
                       "to sync.\n",
    }, targets=["apex_tpu"])
    hits = [f for f in res.findings if f.rule == "METRIC-DRIFT"]
    assert len(hits) == 1 and "engine.fetch" in hits[0].message, \
        "\n".join(f.render() for f in res.findings)


def test_metric_drift_sched_sections(tmp_path):
    # a section is registered by whatever call names it (the
    # scheduler's own wrappers here); `sched.<x>` alone in backticks is
    # a span claim even where <x> is also a method (`sched.step`) — the
    # call spelling and a code sample's attribute access are not
    res = _synth(tmp_path, {
        "apex_tpu/__init__.py": "",
        "apex_tpu/serving/__init__.py": "",
        "apex_tpu/serving/scheduler.py": '''
            class Scheduler:
                def step(self):
                    with self._phase("sched.collect"):
                        with self._timed("engine.fetch"):
                            self.completions = {}
        ''',
        "docs/API.md": "`sched.collect` and `engine.fetch` sections "
                       "inside `sched.step`; call `sched.step()`, "
                       "read `sched.completions[rid]`.\n",
        "bench.py": "done = sched.completions\n",
    }, targets=["apex_tpu"])
    hits = [f for f in res.findings if f.rule == "METRIC-DRIFT"]
    assert len(hits) == 1 and "sched.step" in hits[0].message \
        and hits[0].path == "docs/API.md", \
        "\n".join(f.render() for f in res.findings)


def test_metric_drift_label_and_alternation_tokens(tmp_path):
    res = _synth(tmp_path, {
        "apex_tpu/__init__.py": "",
        "apex_tpu/serving/__init__.py": "",
        "apex_tpu/serving/sched.py": '''
            def wire(registry):
                registry.counter("serving_spec_drafted_total", "")
                registry.counter("serving_spec_accepted_total", "")
                registry.counter("serving_shed_total", "", labels=("r",))
        ''',
        "docs/API.md": "`serving_spec_{drafted,accepted}_total` and "
                       '`serving_shed_total{r="x"}` are exported.\n',
    }, targets=["apex_tpu"])
    assert "METRIC-DRIFT" not in _rules_of(res), \
        "\n".join(f.render() for f in res.findings)


def test_metric_drift_slo_family_pos_and_neg(tmp_path):
    """The SLO observatory's gauge families follow the labelled-family
    shape (`serving_slo_quantile_seconds{metric="ttft",quantile="p99"}`
    in the doc) — pin that the rule accepts the documented spelling
    AND still fires on an slo-prefixed orphan/ghost pair."""
    res = _synth(tmp_path, {
        "apex_tpu/__init__.py": "",
        "apex_tpu/serving/__init__.py": "",
        "apex_tpu/serving/sched.py": '''
            def wire(registry):
                registry.gauge("serving_slo_quantile_seconds", "",
                               labels=("metric", "quantile"))
                registry.counter("serving_slo_alerts_total", "",
                                 labels=("objective", "state"))
                registry.gauge("serving_slo_orphan", "undocumented")
        ''',
        "docs/API.md":
            '`serving_slo_quantile_seconds{metric="ttft",quantile="p99"}`'
            ' and `serving_slo_alerts_total{objective="o",state="s"}` '
            'are exported, as is `serving_slo_ghost_total`.\n',
    }, targets=["apex_tpu"])
    hits = [f for f in res.findings if f.rule == "METRIC-DRIFT"]
    msgs = "\n".join(f.render() for f in hits)
    assert len(hits) == 2, msgs
    assert any("serving_slo_ghost_total" in f.message
               and f.path == "docs/API.md" for f in hits), msgs
    assert any("serving_slo_orphan" in f.message
               and f.path == "apex_tpu/serving/sched.py"
               for f in hits), msgs


# --------------------------------------------------------------------------
# EVENT-DRIFT
# --------------------------------------------------------------------------


_EVENT_VOCAB = '''
    EVENT_FIELDS = {
        "good": ("request_id",),
        "undocumented": ("n",),
        "never_recorded": ("x",),
    }
'''

_EVENT_DOC = ("#### Flight-recorder event names\n"
              "| event | fields | meaning |\n"
              "|---|---|---|\n"
              "| `good` | request_id | fine |\n"
              "| `never_recorded` | x | vocabulary orphan |\n"
              "| `phantom` | y | doc orphan |\n")


def _event_tree(tmp_path, sched_src, doc=_EVENT_DOC):
    return _synth(tmp_path, {
        "apex_tpu/__init__.py": "",
        "apex_tpu/telemetry/__init__.py": "",
        "apex_tpu/telemetry/flightrec.py": _EVENT_VOCAB,
        "apex_tpu/serving/__init__.py": "",
        "apex_tpu/serving/sched.py": sched_src,
        "docs/API.md": doc,
    }, targets=["apex_tpu"], rules=["EVENT-DRIFT"])


def test_event_drift_all_directions(tmp_path):
    res = _event_tree(tmp_path, '''
        def wire(recorder):
            recorder.record("good", "r0")
            recorder.record("undocumented", 3)
            recorder.record("ghost", 1)
            db.record("not_an_event")     # non-recorder receiver
    ''')
    hits = [f for f in res.findings if f.rule == "EVENT-DRIFT"]
    msgs = "\n".join(f.render() for f in hits)
    # ghost: recorded, not in vocabulary (anchored at the call site)
    assert any("'ghost'" in f.message
               and f.path == "apex_tpu/serving/sched.py"
               for f in hits), msgs
    # undocumented: in vocabulary + recorded, missing from the doc table
    assert any("'undocumented'" in f.message and "API.md" in f.message
               and f.path == "apex_tpu/telemetry/flightrec.py"
               for f in hits), msgs
    # never_recorded: dead vocabulary (documented but no call site)
    assert any("'never_recorded'" in f.message
               and "no record() call" in f.message for f in hits), msgs
    # phantom: documented, not in the vocabulary (anchored in the doc)
    assert any("'phantom'" in f.message and f.path == "docs/API.md"
               for f in hits), msgs
    # the non-recorder receiver stays out of scope
    assert not any("not_an_event" in f.message for f in hits), msgs
    assert len(hits) == 4, msgs


def test_event_drift_clean_tree(tmp_path):
    res = _event_tree(tmp_path, '''
        def wire(rec):
            rec.record("good", "r0")
            rec.record("undocumented", 3)
            rec.record("never_recorded", 1)
    ''', doc=("#### Flight-recorder event names\n"
              "| event | fields | meaning |\n"
              "|---|---|---|\n"
              "| `good` | request_id | fine |\n"
              "| `undocumented` | n | now documented |\n"
              "| `never_recorded` | x | recorded after all |\n"))
    assert "EVENT-DRIFT" not in _rules_of(res), \
        "\n".join(f.render() for f in res.findings)


def test_event_drift_sees_annotated_vocabulary(tmp_path):
    """The REAL flightrec module binds the vocabulary with a type
    annotation (`EVENT_FIELDS: Dict[...] = {...}` — ast.AnnAssign);
    the rule must parse that spelling too, or it is silently inert
    against the actual repo (the regression this pins: the rule
    shipped matching plain Assign only and never fired on the tree)."""
    res = _synth(tmp_path, {
        "apex_tpu/__init__.py": "",
        "apex_tpu/telemetry/__init__.py": "",
        "apex_tpu/telemetry/flightrec.py": '''
            from typing import Dict, Tuple

            EVENT_FIELDS: Dict[str, Tuple[str, ...]] = {
                "good": ("request_id",),
                "dead_entry": ("x",),
            }
        ''',
        "apex_tpu/serving/__init__.py": "",
        "apex_tpu/serving/sched.py": 'def f(recorder):\n'
                                     '    recorder.record("good", 1)\n',
        "docs/API.md": ("#### Flight-recorder event names\n"
                        "| event | fields | meaning |\n"
                        "|---|---|---|\n"
                        "| `good` | request_id | fine |\n"),
    }, targets=["apex_tpu"], rules=["EVENT-DRIFT"])
    hits = [f for f in res.findings if f.rule == "EVENT-DRIFT"]
    msgs = "\n".join(f.render() for f in hits)
    assert any("'dead_entry'" in f.message
               and "no record() call" in f.message for f in hits), msgs
    assert any("'dead_entry'" in f.message and "API.md" in f.message
               for f in hits), msgs
    assert len(hits) == 2, msgs


def test_event_drift_absent_on_foreign_trees(tmp_path):
    # no flightrec.py (or one without the vocabulary) = not this repo
    # shape; the rule must stay silent instead of flagging everything
    res = _synth(tmp_path, {
        "apex_tpu/__init__.py": "",
        "apex_tpu/serving/__init__.py": "",
        "apex_tpu/serving/sched.py": 'def f(rec):\n'
                                     '    rec.record("anything", 1)\n',
        "docs/API.md": _EVENT_DOC,
    }, targets=["apex_tpu"], rules=["EVENT-DRIFT"])
    assert "EVENT-DRIFT" not in _rules_of(res), \
        "\n".join(f.render() for f in res.findings)


def test_event_drift_slo_vocabulary_pos_and_neg(tmp_path):
    """SLO burn/alert events ride the same vocabulary contract: a
    documented + recorded `slo_state` stays clean, a recorded-but-
    unregistered `slo_ghost` fires at the call site, and a vocabulary
    entry `slo_dead` with no record() call fires as dead vocabulary."""
    res = _synth(tmp_path, {
        "apex_tpu/__init__.py": "",
        "apex_tpu/telemetry/__init__.py": "",
        "apex_tpu/telemetry/flightrec.py": '''
            EVENT_FIELDS = {
                "slo_state": ("objective", "from", "to",
                              "fast_burn", "slow_burn"),
                "slo_dead": ("x",),
            }
        ''',
        "apex_tpu/serving/__init__.py": "",
        "apex_tpu/serving/sched.py": '''
            def wire(recorder):
                recorder.record("slo_state", "o", "ok", "warning",
                                1.0, 1.0)
                recorder.record("slo_ghost", 1)
        ''',
        "docs/API.md": ("#### Flight-recorder event names\n"
                        "| event | fields | meaning |\n"
                        "|---|---|---|\n"
                        "| `slo_state` | objective, from, to, "
                        "fast_burn, slow_burn | transition |\n"
                        "| `slo_dead` | x | never recorded |\n"),
    }, targets=["apex_tpu"], rules=["EVENT-DRIFT"])
    hits = [f for f in res.findings if f.rule == "EVENT-DRIFT"]
    msgs = "\n".join(f.render() for f in hits)
    assert any("'slo_ghost'" in f.message
               and f.path == "apex_tpu/serving/sched.py"
               for f in hits), msgs
    assert any("'slo_dead'" in f.message
               and "no record() call" in f.message for f in hits), msgs
    assert not any("slo_state" in f.message for f in hits), msgs
    assert len(hits) == 2, msgs


# --------------------------------------------------------------------------
# DURABLE-WRITE
# --------------------------------------------------------------------------


def test_durable_write_fires_on_bare_artifact_writes(tmp_path):
    res = _synth(tmp_path, {"pkg/mod.py": '''
        import json
        import os

        def save(state, ckpt_dir, step):
            # the torn-artifact class this rule exists for: bare
            # open(w) at the real destination
            with open(os.path.join(ckpt_dir, f"step{step}.json"),
                      "w") as f:
                json.dump(state, f)

        def dump(report, out):
            with open(out + "/bundle.json", mode="wb") as f:
                f.write(report)

        def seal(journal_path, rows):
            f = open(journal_path, "x")
            f.write(rows)
    ''', "pkg/__init__.py": ""})
    hits = [f for f in res.findings if f.rule == "DURABLE-WRITE"]
    msgs = "\n".join(f.render() for f in hits)
    assert len(hits) == 3, msgs
    assert any("ckpt" in f.message and "'w'" in f.message
               for f in hits), msgs
    assert any("bundle" in f.message and "'wb'" in f.message
               for f in hits), msgs
    assert any("journal" in f.message and "'x'" in f.message
               for f in hits), msgs
    assert all("_atomic" in f.message for f in hits), msgs


def test_durable_write_clean_on_blessed_spellings(tmp_path):
    res = _synth(tmp_path, {"pkg/mod.py": '''
        import json
        import os

        def save(state, ckpt_dir, tmp, name):
            # writes into an atomic temp target spell the temp name,
            # not the artifact — that is the point of the idiom
            with open(os.path.join(tmp, name), "w") as f:
                json.dump(state, f)

        def read(ckpt_dir, step):
            # reads are out of scope
            with open(os.path.join(ckpt_dir, f"step{step}.json")) as f:
                return json.load(f)

        def extend(journal_path, rows):
            # appending IS the journal contract — exempt mode
            with open(journal_path, "ab") as f:
                f.write(rows)

        def scratch(workdir, payload):
            # non-durable names may write bare (other files' turf)
            with open(os.path.join(workdir, "scratch.bin"), "wb") as f:
                f.write(payload)
    ''', "pkg/__init__.py": ""})
    assert "DURABLE-WRITE" not in _rules_of(res), \
        "\n".join(f.render() for f in res.findings)


def test_durable_write_exempts_the_blessed_implementations(tmp_path):
    # _atomic.py and serving/journal.py ARE the safe paths being
    # policed — their own destination writes must not fire
    body = '''
        def write(checkpoint_path, data):
            with open(checkpoint_path, "w") as f:
                f.write(data)
    '''
    res = _synth(tmp_path, {
        "apex_tpu/__init__.py": "",
        "apex_tpu/_atomic.py": body,
        "apex_tpu/serving/__init__.py": "",
        "apex_tpu/serving/journal.py": body,
        "apex_tpu/other.py": body,
    }, targets=["apex_tpu"], rules=["DURABLE-WRITE"])
    hits = [f for f in res.findings if f.rule == "DURABLE-WRITE"]
    msgs = "\n".join(f.render() for f in hits)
    assert len(hits) == 1, msgs
    assert hits[0].path == "apex_tpu/other.py", msgs


# --------------------------------------------------------------------------
# CITATION
# --------------------------------------------------------------------------


_CITE_SRC = '''
    """Module header.

    Good: apex/amp/scaler.py (U). Wrapped but tagged:
    apex/fp16_utils/{fp16util,
    loss_scaler}.py (U). Bad, untagged: apex/contrib/foo/bar.py is
    the reference.
    """
'''


def test_citation_rule_requires_marker(tmp_path):
    res = _synth(tmp_path, {"pkg/mod.py": _CITE_SRC,
                            "pkg/__init__.py": ""})
    hits = [f for f in res.findings if f.rule == "CITATION"]
    assert len(hits) == 1, "\n".join(f.render() for f in hits)
    assert "apex/contrib/foo/bar.py" in hits[0].message


# --------------------------------------------------------------------------
# TIER1-COST
# --------------------------------------------------------------------------


_TIER1_SRC = '''
    import pytest

    def test_unmarked(engine):
        engine.warmup()          # should fire

    @pytest.mark.slow
    def test_marked(engine):
        engine.warmup()          # slow-marked: exempt

    def helper(engine):          # apex: noqa on the def line covers it
        engine.warmup()
'''


def test_tier1_cost_rule(tmp_path):
    src = _TIER1_SRC.replace(
        "def helper(engine):          # apex: noqa on the def line",
        "def helper(engine):  # apex: noqa[TIER1-COST]: shared helper")
    res = _synth(tmp_path, {"tests/test_x.py": src},
                 targets=["tests"], rules=["TIER1-COST"])
    hits = [f for f in res.findings if f.rule == "TIER1-COST"]
    assert len(hits) == 1 and "test_unmarked" in hits[0].message, \
        "\n".join(f.render() for f in res.findings)
    assert len(res.suppressions_used) == 1  # the def-line noqa


def test_tier1_cost_sees_through_lambdas(tmp_path):
    # a lambda is never scanned as a function of its own, so a warmup
    # tucked into one is charged to the enclosing def — otherwise the
    # `mk = lambda: engine.warmup()` spelling escapes the allowlist
    res = _synth(tmp_path, {"tests/test_x.py": '''
        def test_lam(engine):
            mk = lambda: engine.warmup()
            mk()
    '''}, targets=["tests"], rules=["TIER1-COST"])
    hits = [f for f in res.findings if f.rule == "TIER1-COST"]
    assert len(hits) == 1 and "test_lam" in hits[0].message, \
        "\n".join(f.render() for f in res.findings)


def test_tier1_cost_only_sees_test_files(tmp_path):
    res = _synth(tmp_path, {"pkg/mod.py": '''
        def run(engine):
            engine.warmup()
    ''', "pkg/__init__.py": ""}, rules=["TIER1-COST"])
    assert not res.findings


# --------------------------------------------------------------------------
# the suppression mechanism itself
# --------------------------------------------------------------------------


def test_justified_suppression_silences_and_counts(tmp_path):
    res = _synth(tmp_path, {"pkg/mod.py": '''
        import jax

        def f(x):
            return int(x)  # apex: noqa[TRACER-LEAK]: synthetic pin

        j = jax.jit(f)
    ''', "pkg/__init__.py": ""})
    assert not res.findings, "\n".join(f.render() for f in res.findings)
    assert len(res.suppressions_used) == 1
    s = summary_dict(res)
    assert s["suppressions"]["active"] == 1
    assert s["suppressions"]["by_rule"] == {"TRACER-LEAK": 1}


def test_bare_suppression_is_a_finding(tmp_path):
    res = _synth(tmp_path, {"pkg/mod.py": '''
        import jax

        def f(x):
            return int(x)  # apex: noqa[TRACER-LEAK]

        j = jax.jit(f)
    ''', "pkg/__init__.py": ""})
    assert _rules_of(res) == ["NOQA-BARE"], \
        "\n".join(f.render() for f in res.findings)
    assert res.exit_code == 1


def test_unused_suppression_is_a_finding(tmp_path):
    res = _synth(tmp_path, {"pkg/mod.py": '''
        def f(x):
            return x + 1  # apex: noqa[TRACER-LEAK]: nothing fires here
    ''', "pkg/__init__.py": ""})
    assert _rules_of(res) == ["NOQA-UNUSED"], \
        "\n".join(f.render() for f in res.findings)


def test_suppression_outside_targets_still_matches(tmp_path):
    # a global rule (METRIC-DRIFT) anchors findings at package files a
    # partial/--changed run never targeted; a justified suppression at
    # the registration site must silence them there too, or the
    # documented pre-commit hook exits 1 spuriously
    files = {
        "apex_tpu/__init__.py": "",
        "apex_tpu/other.py": "X = 1\n",
        "apex_tpu/serving/__init__.py": "",
        "apex_tpu/serving/sched.py": '''
            def wire(registry):
                registry.gauge("serving_internal_state", "")  # apex: noqa[METRIC-DRIFT]: internal-only, deliberately undocumented
        ''',
        "docs/API.md": "no metrics documented\n",
    }
    res = _synth(tmp_path, files, targets=["apex_tpu/other.py"])
    assert not res.findings, "\n".join(f.render() for f in res.findings)
    # the same run WITH the registration file targeted counts it active
    res2 = _synth(tmp_path, files,
                  targets=["apex_tpu/serving/sched.py"])
    assert not res2.findings, \
        "\n".join(f.render() for f in res2.findings)
    assert len(res2.suppressions_used) == 1


def test_disabled_rules_suppressions_out_of_scope(tmp_path):
    # a TIER1-COST noqa in a test file is not "unused" to a run that
    # never enabled TIER1-COST — each battery polices its own rules
    res = _synth(tmp_path, {"tests/test_x.py": '''
        def helper(engine):  # apex: noqa[TIER1-COST]: other battery
            engine.warmup()
    '''}, targets=["tests"], rules=["CITATION"])
    assert not res.findings, "\n".join(f.render() for f in res.findings)


def test_unknown_rule_suppression_is_a_finding(tmp_path):
    # a typo'd (or renamed-rule) id must not become a permanently dead
    # annotation no run ever flags — the full battery reports it; a
    # partial --rules run stays silent (it cannot tell another
    # battery's id from no such id)
    files = {"pkg/mod.py":
             "X = 1  # apex: noqa[TRACERLEAK]: typo'd id\n",
             "pkg/__init__.py": ""}
    res = _synth(tmp_path, files)
    assert _rules_of(res) == ["NOQA-UNKNOWN"], \
        "\n".join(f.render() for f in res.findings)
    assert "TRACERLEAK" in res.findings[0].message
    res2 = _synth(tmp_path, files, rules=["CITATION"])
    assert not res2.findings, \
        "\n".join(f.render() for f in res2.findings)


def test_tier1_cost_respects_pytestmark(tmp_path):
    # `pytestmark = pytest.mark.slow` at module or class level is the
    # standard whole-scope slow spelling — it must exempt exactly like
    # the per-function decorator, or authors get restyled by the linter
    res = _synth(tmp_path, {
        "tests/test_mod.py": '''
            import pytest

            pytestmark = pytest.mark.slow

            def test_soak(engine):
                engine.warmup()
        ''',
        "tests/test_cls.py": '''
            import pytest

            class TestSoak:
                pytestmark = [pytest.mark.slow]

                def test_inner(self, engine):
                    engine.warmup()

            def test_outside(engine):
                engine.warmup()   # not under the marked class: fires
        ''',
    }, targets=["tests"], rules=["TIER1-COST"])
    hits = [f for f in res.findings if f.rule == "TIER1-COST"]
    assert len(hits) == 1 and "test_outside" in hits[0].message, \
        "\n".join(f.render() for f in res.findings)


def test_docstring_noqa_examples_are_not_suppressions(tmp_path):
    res = _synth(tmp_path, {"pkg/mod.py": '''
        """Docs may show `# apex: noqa[TRACER-LEAK]: why` verbatim."""
    ''', "pkg/__init__.py": ""})
    assert not res.findings, "\n".join(f.render() for f in res.findings)


# --------------------------------------------------------------------------
# dependency hygiene
# --------------------------------------------------------------------------


def test_analysis_imports_stdlib_only(tmp_path):
    """The linter must stay importable and runnable with jax/numpy
    purged and blocked (it lints the tree BEFORE a broken change could
    even import) — same harness as serving.api's purged-import test."""
    script = tmp_path / "probe.py"
    script.write_text(textwrap.dedent(f'''
        import sys

        BLOCKED = ("jax", "jaxlib", "numpy", "scipy", "torch")

        class _Blocker:
            def find_spec(self, name, path=None, target=None):
                if name.split(".")[0] in BLOCKED:
                    raise ImportError(f"blocked import: {{name}}")

        # blocked BEFORE apex_tpu itself loads: the claim is that the
        # linter runs on a machine where jax cannot import at all (the
        # parent package degrades to its stdlib-only corners)
        sys.meta_path.insert(0, _Blocker())

        import apex_tpu
        # degradation shape: a jax-backed subpackage must surface the
        # REAL missing module, not a fake "no attribute" error...
        try:
            apex_tpu.mesh
        except ImportError as e:
            assert "jax" in str(e), e
        else:
            raise AssertionError("apex_tpu.mesh imported without jax?")
        # ...while a genuinely absent attribute stays an AttributeError
        try:
            apex_tpu.not_a_subpackage
        except AttributeError:
            pass
        from apex_tpu.analysis.core import run_analysis
        res = run_analysis(
            [{os.path.join(REPO, "apex_tpu", "analysis")!r}],
            root={REPO!r})
        print("FINDINGS", len(res.findings))
    '''))
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    r = subprocess.run([sys.executable, str(script)],
                       capture_output=True, text=True, timeout=120,
                       cwd=REPO, env=env)
    assert r.returncode == 0, r.stderr
    assert "FINDINGS 0" in r.stdout, r.stdout
