"""The region vocabulary: scopes and kernel names inside the programs,
the tick's phases and counts on the host (docs/API.md "Regions").

Device names are checked where they end up — the ``op_name`` of the
compiled program's instructions, which is what a profiler trace
carries — on tiny programs compiled for the CPU mesh. Host phases are
checked on a fake clock.
"""

import ast
import contextlib
import pathlib
import re
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu import mesh as mx
from apex_tpu.amp import ScalerConfig
from apex_tpu.models import gpt, training
from apex_tpu.optimizers import fused_adam
from apex_tpu.serving import (
    Engine, EngineConfig, Request, SamplingParams, Scheduler, StepHandle)
from apex_tpu.telemetry import SpanRecorder
from apex_tpu.transformer.testing import standalone_gpt_config

KERNELS = pathlib.Path(__file__).resolve().parents[1] / "apex_tpu" / "kernels"
_OP_NAME = re.compile(r'op_name="([^"]+)"')
_REGION = re.compile(r"apex\.[a-z_.]+")


def _paths(compiled):
    return _OP_NAME.findall(compiled.as_text())


@contextlib.contextmanager
def _fresh_compiles():
    """The persistent compile cache keys a program without its
    metadata, so a hit would hand back an older build's ``op_name``s:
    compile anew here."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


def _regions(paths, *, backward):
    """Regions named by the paths of one direction: a backward
    instruction's path passes through ``transpose(``."""
    return {r for p in paths if ("transpose(" in p) == backward
            for r in _REGION.findall(p)}


# --- device: scopes in the compiled programs ------------------------------

@pytest.fixture(scope="module")
def train_paths(devices8):
    cfg = gpt.GPTConfig(vocab_size=96, hidden_size=64, num_layers=2,
                        num_heads=4, seq_len=32, ce_chunk=16, remat=True,
                        compute_dtype=jnp.float32)
    mesh = mx.build_mesh(tp=2, devices=devices8[:4])     # dp=2 x tp=2
    init_fn, step_fn = training.make_train_step(
        cfg, mesh, fused_adam(1e-3), ScalerConfig(enabled=False),
        clip_grad_norm=1.0)
    state = jax.eval_shape(init_fn, jax.random.PRNGKey(0))
    tok = jax.ShapeDtypeStruct((8, 32), jnp.int32)
    with _fresh_compiles():
        return _paths(step_fn.lower(state, tok, tok).compile())


@pytest.mark.parametrize("region", ["apex.embed", "apex.attn", "apex.mlp",
                                    "apex.ce_head"])
def test_train_step_model_regions_forward_and_backward(train_paths, region):
    assert region in _regions(train_paths, backward=False)
    assert region in _regions(train_paths, backward=True)


@pytest.mark.parametrize("region", ["apex.grad_sync", "apex.clip",
                                    "apex.optimizer", "apex.layers"])
def test_train_step_step_regions(train_paths, region):
    assert region in (_regions(train_paths, backward=False)
                      | _regions(train_paths, backward=True))


def test_train_step_is_the_program_the_readers_look_for(train_paths):
    assert all(p.startswith("jit(_local_step)/") for p in train_paths
               if p.startswith("jit("))


def _engine_paths(devices8, **model):
    cfg = standalone_gpt_config(vocab_size=96, seq_len=64, **model)
    mesh = mx.build_mesh(tp=1, devices=devices8[:1])
    ecfg = EngineConfig(slots=2, max_prompt_len=8, max_seq_len=24,
                        decode_chunk=2)
    with Engine(cfg, gpt.init(cfg, jax.random.PRNGKey(0)), mesh,
                ecfg) as eng, _fresh_compiles():
        arr = jax.ShapeDtypeStruct
        step = eng._step_variants[ecfg.decode_chunk].lower(
            eng._params, eng.cache, eng.state,
            arr((ecfg.slots, cfg.vocab_size), jnp.bool_)).compile()
        (bucket, k), admit = sorted(eng._admits.items())[0]
        i32, f32 = np.int32, np.float32
        admit = admit.lower(
            eng._params, eng.cache, eng.state, arr((k,), i32),
            arr((k, bucket), i32), arr((k,), i32), arr((k,), i32),
            arr((k,), f32), arr((k,), i32), arr((k,), f32),
            arr((k, 2), np.uint32), arr((k,), i32), arr((k,), i32),
            arr((k,), jnp.bool_), arr((k, cfg.vocab_size), jnp.bool_)
        ).compile()
        return {"step": _paths(step), "admit": _paths(admit)}


@pytest.fixture(scope="module")
def engine_paths(devices8):
    """op_name paths of a tiny engine's decode-step program and of one
    of its admission programs (off the TPU: the XLA decode path)."""
    return _engine_paths(devices8)


@pytest.fixture(scope="module")
def kernel_engine_paths(devices8):
    """The same with the decode kernels (interpreted here), the path
    every serving cell of the benchmark runs."""
    return _engine_paths(devices8, decode_attn_impl="kernel")


@pytest.mark.parametrize("program,region", [
    ("step", "apex.embed"), ("step", "apex.attn"), ("step", "apex.mlp"),
    ("step", "apex.lm_head"), ("step", "apex.sample"),
    ("step", "apex.decode.layers"), ("step", "apex.decode.cache_slice"),
    ("step", "apex.decode.attn"), ("step", "apex.decode.cache_stack"),
    ("admit", "apex.embed"), ("admit", "apex.attn"), ("admit", "apex.mlp"),
    ("admit", "apex.lm_head"), ("admit", "apex.sample"),
    ("admit", "apex.prefill.cache_insert"),
])
def test_engine_program_regions(engine_paths, program, region):
    assert region in _regions(engine_paths[program], backward=False)


@pytest.mark.parametrize("region,there", [
    ("apex.decode.layers", True), ("apex.decode.attn", True),
    ("apex.mlp", True), ("apex.sample", True),
    ("apex.decode.cache_slice", False),
    ("apex.decode.cache_stack", False)])
def test_kernel_step_program_regions(kernel_engine_paths, region, there):
    """On the kernel path the layer scan carries the cache and the
    kernels address it by layer: `apex.decode.layers` is the scan's
    slicing of the stacked weights, and the two scopes of the XLA
    fallback's slice-out / put-back name nothing."""
    regions = _regions(kernel_engine_paths["step"], backward=False)
    assert (region in regions) == there


def test_engine_programs_are_the_ones_the_readers_look_for(engine_paths):
    for program, jitted in (("step", "jit(step_local)/"),
                            ("admit", "jit(admit_local)/")):
        assert any(p.startswith(jitted) for p in engine_paths[program])


# --- device: kernel names -------------------------------------------------

def _pallas_call_names():
    """``(file, line, name= of the call or None)`` of every
    ``pallas_call(`` under apex_tpu/kernels/."""
    out = []
    for path in sorted(KERNELS.glob("*.py")):
        for node in ast.walk(ast.parse(path.read_text())):
            if isinstance(node, ast.Call) and getattr(
                    node.func, "attr", None) == "pallas_call":
                name = next((kw.value.value for kw in node.keywords
                             if kw.arg == "name"
                             and isinstance(kw.value, ast.Constant)), None)
                out.append((path.name, node.lineno, name))
    return out


def test_every_pallas_call_is_named_and_names_are_unique():
    calls = _pallas_call_names()
    assert len(calls) >= 20
    unnamed = [c for c in calls if not isinstance(c[2], str) or not c[2]]
    assert not unnamed, f"pallas_call without a string name=: {unnamed}"
    names = [c[2] for c in calls]
    assert len(set(names)) == len(names), sorted(names)


@pytest.mark.parametrize("marker,owner", [
    ("flash_att", "flash_attention.py"),
    ("decode_att", "decode_attention.py")])
def test_attention_kernel_names_stay_with_their_file(marker, owner):
    """The benchmark's readers find attention kernels by these two
    substrings: every kernel of the owning file carries its marker and
    no other file's kernel does."""
    for fname, line, name in _pallas_call_names():
        assert (marker in name) == (fname == owner), (fname, line, name)


# --- host: the tick as phases --------------------------------------------

class _Clock:
    """Advances a millisecond on every read, so every section has a
    length and an order."""

    def __init__(self):
        self.t = 0.0

    def __call__(self):
        self.t += 1e-3
        return self.t


@pytest.fixture(scope="module")
def tiny_engine(devices8):
    cfg = standalone_gpt_config(vocab_size=96, seq_len=64)
    mesh = mx.build_mesh(tp=1, devices=devices8[:1])
    with Engine(cfg, gpt.init(cfg, jax.random.PRNGKey(0)), mesh,
                EngineConfig(slots=2, max_prompt_len=8, max_seq_len=24,
                             decode_chunk=2)) as eng:
        yield eng


def _served(eng, **kw):
    sched = Scheduler(eng, clock=_Clock(), **kw)
    prompts = {"a": [1, 2, 3], "b": [4, 5, 6, 7, 8], "c": [9, 10]}
    for rid, prompt in prompts.items():
        sched.submit(Request(rid, prompt, max_tokens=4))
    sched.run_until_idle()
    assert set(sched.completions) == set(prompts)
    return sched, prompts


PHASES = ("sched.housekeeping", "sched.admit", "sched.dispatch",
          "sched.collect", "sched.publish")


@pytest.fixture(scope="module")
def span_rows(tiny_engine):
    annotated = []

    class Annotation:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            annotated.append(self.name)

        def __exit__(self, *exc):
            return False

    from apex_tpu import profiler

    spans = SpanRecorder()
    with pytest.MonkeyPatch.context() as mp:
        # the scheduler hands the recorder the profiler's annotation
        mp.setattr(profiler, "annotate", Annotation)
        _, prompts = _served(tiny_engine, spans=spans)
    return spans.events(), annotated, prompts


def test_sched_step_encloses_its_five_phases(span_rows):
    rows = [e for e in span_rows[0] if e[0] == 1]
    steps = [e for e in rows if e[2] == "sched.step"]
    assert steps and all(e[4] is None for e in steps)
    for _, t0, _, t1, _ in steps:
        inside = [e for e in rows if e[4] == "sched.step"
                  and t0 <= e[1] and e[3] <= t1]
        assert tuple(e[2] for e in sorted(inside, key=lambda e: e[1])
                     ) == PHASES
    assert {e[2] for e in rows if e[4] == "sched.step"} == set(PHASES)
    # a submit is a section beside the tick, one a request
    submits = [e for e in rows if e[2] == "sched.submit"]
    assert len(submits) == len(span_rows[2])
    assert all(e[4] is None for e in submits)


@pytest.mark.parametrize("section,parent", [
    ("engine.admit", "sched.admit"), ("engine.dispatch", "sched.dispatch"),
    ("engine.fetch", "sched.collect")])
def test_engine_sections_name_their_phase(span_rows, section, parent):
    found = [e for e in span_rows[0] if e[0] == 1 and e[2] == section]
    assert found and all(e[4] == parent for e in found)
    phases = [e for e in span_rows[0] if e[0] == 1 and e[2] == parent]
    for _, t0, _, t1, _ in found:
        assert any(p[1] <= t0 and t1 <= p[3] for p in phases)


def test_fetch_splits_into_the_wait_and_the_copies(span_rows):
    """Every ``engine.fetch`` holds one ``engine.fetch.wait`` (the copy
    of the tokens, which waits for the chunk) followed by one
    ``engine.fetch.copy`` (the copies after it), both naming the fetch
    as their parent."""
    rows = [e for e in span_rows[0] if e[0] == 1]
    fetches = [e for e in rows if e[2] == "engine.fetch"]
    assert fetches
    for f in fetches:
        inside = sorted((e for e in rows if e[4] == "engine.fetch"
                         and f[1] <= e[1] and e[3] <= f[3]),
                        key=lambda e: e[1])
        assert [e[2] for e in inside] == ["engine.fetch.wait",
                                          "engine.fetch.copy"]
        assert inside[0][3] <= inside[1][1]
    assert len([e for e in rows if e[4] == "engine.fetch"]) == \
        2 * len(fetches)


def test_untraced_fetch_takes_no_section(tiny_engine, monkeypatch):
    """Without a recorder the scheduler fetches a chunk as it always
    did: no section factory reaches the handle, so nothing is timed
    or recorded."""
    calls = []
    fetch = StepHandle.fetch

    def spy(self, *args, **kwargs):
        calls.append((args, kwargs))
        return fetch(self, *args, **kwargs)

    monkeypatch.setattr(StepHandle, "fetch", spy)
    _served(tiny_engine)
    assert calls and all(c == ((), {}) for c in calls)


def test_a_clock_row_opens_every_tick(span_rows):
    """One clock row when the scheduler takes the recorder, then one at
    the entry of every tick, ahead of its ``sched.step`` section: the
    recorder's clock beside the wall clock the profiler stamps with."""
    rows = span_rows[0]
    clocks = [e for e in rows if e[0] == 3]
    steps = [e for e in rows if e[0] == 1 and e[2] == "sched.step"]
    assert len(clocks) == len(steps) + 1
    assert all(e[2] == "clock" and e[4] is None for e in clocks)
    assert all(a[1] < s[1] for a, s in zip(clocks[1:], steps))
    wall = [e[3] for e in clocks]
    assert wall == sorted(wall) and abs(wall[-1] - time.time()) < 600


def test_sections_are_annotated_under_the_apex_prefix(span_rows):
    rows, annotated, _ = span_rows
    sections = [e[2] for e in rows if e[0] == 1]
    assert sorted(annotated) == sorted("apex." + n for n in sections)


def test_prefill_counts_sum_to_what_was_admitted(span_rows, tiny_engine):
    rows, _, prompts = span_rows
    total = {}
    for e in rows:
        if e[0] == 2:
            total[e[2]] = total.get(e[2], 0) + e[3]
    assert total["prefill.rows"] == len(prompts)
    assert total["prefill.tokens_real"] == sum(map(len, prompts.values()))
    buckets = tiny_engine.prompt_buckets
    assert total["prefill.tokens_padded"] >= total["prefill.tokens_real"]
    assert total["prefill.tokens_padded"] % buckets[0] == 0
    admits = [e for e in rows if e[0] == 1 and e[2] == "engine.admit"]
    assert len(admits) <= total["prefill.dispatches"] <= len(prompts)


def test_the_count_vocabulary_is_what_a_served_script_records(span_rows):
    """Every count a run records is one of the eleven names docs/API.md
    lists, and a run that admits and decodes records all eleven."""
    assert {e[2] for e in span_rows[0] if e[0] == 2} == {
        "prefill.tokens_real", "prefill.tokens_padded", "prefill.rows",
        "prefill.dispatches", "decode.chunks_needed", "decode.chunks_grid",
        "decode.row_steps_live", "decode.row_steps_grid",
        "sample.dispatches", "sample.dispatches_drawn",
        "sample.dispatches_sorted"}


def test_decode_chunk_counts_of_the_served_script(span_rows, tiny_engine):
    """One pair of counts a decode dispatch; the grid's count is slots
    x chunks of the horizon every time, and what the fills need never
    passes it. The tiny engine's horizon is one chunk, so a dispatch
    needs as many chunks as it has live slots: at most both."""
    rows = span_rows[0]
    needed = [e[3] for e in rows if e[2] == "decode.chunks_needed"]
    grid = [e[3] for e in rows if e[2] == "decode.chunks_grid"]
    dispatches = [e for e in rows if e[0] == 1 and e[2] == "engine.dispatch"]
    assert len(needed) == len(grid) == len(dispatches) > 0
    ecfg = tiny_engine.engine_cfg
    assert tiny_engine.read_chunk == ecfg.max_seq_len
    assert set(grid) == {ecfg.slots}
    assert all(1 <= n <= g for n, g in zip(needed, grid))


def test_decode_chunks_needed_equals_the_hand_count(devices8):
    """Two requests on a paged engine whose read chunk is a page of 8:
    prompts of 3 and 7 tokens, four tokens each, two a chunk. At the
    first dispatch each slot holds its prompt and one token (positions
    3 and 7: one chunk each); at the second two more (5 and 9: one and
    two). The grid is 2 slots x 3 pages both times."""
    cfg = standalone_gpt_config(vocab_size=96, seq_len=64)
    mesh = mx.build_mesh(tp=1, devices=devices8[:1])
    spans = SpanRecorder()
    with Engine(cfg, gpt.init(cfg, jax.random.PRNGKey(0)), mesh,
                EngineConfig(slots=2, max_prompt_len=8, max_seq_len=24,
                             decode_chunk=2, page_size=8)) as eng:
        assert eng.read_chunk == 8
        sched = Scheduler(eng, clock=_Clock(), spans=spans)
        sched.submit(Request("a", [1, 2, 3], max_tokens=4))
        sched.submit(Request("b", [4, 5, 6, 7, 8, 9, 10], max_tokens=4))
        sched.run_until_idle()
        assert set(sched.completions) == {"a", "b"}
    counts = [(e[2], e[3]) for e in spans.events()
              if e[0] == 2 and e[2].startswith("decode.chunks_")]
    assert counts == [("decode.chunks_needed", 2), ("decode.chunks_grid", 6),
                      ("decode.chunks_needed", 3), ("decode.chunks_grid", 6)]


def test_decode_row_steps_equal_the_hand_count(devices8):
    """Three requests on two slots, two steps a chunk, the first token
    drawn at admission, each chunk fetched before the next dispatch.
    Dispatch 1: ``a`` (4 tokens) has 3 of budget left and is live both
    steps, ``b`` (2) one. Dispatch 2: ``b`` has ended and ``c`` (5)
    taken its slot: ``a`` 1, ``c`` 2. Dispatch 3: ``a`` has ended, ``c``
    2. A chunk's grid over every slot is 2 x 2 = 4."""
    cfg = standalone_gpt_config(vocab_size=96, seq_len=64)
    mesh = mx.build_mesh(tp=1, devices=devices8[:1])
    spans = SpanRecorder()
    with Engine(cfg, gpt.init(cfg, jax.random.PRNGKey(0)), mesh,
                EngineConfig(slots=2, max_prompt_len=8, max_seq_len=24,
                             decode_chunk=2)) as eng:
        sched = Scheduler(eng, clock=_Clock(), spans=spans)
        for rid, n in (("a", 4), ("b", 2), ("c", 5)):
            sched.submit(Request(rid, [1, 2, 3], max_tokens=n))
        sched.run_until_idle()
        assert set(sched.completions) == {"a", "b", "c"}
    rows = [(e[2], e[3]) for e in spans.events()
            if e[0] == 2 and e[2].startswith("decode.row_steps_")]
    live = [n for name, n in rows if name == "decode.row_steps_live"]
    grid = [n for name, n in rows if name == "decode.row_steps_grid"]
    assert grid == [4] * len(live)
    assert live == [3, 3, 2], live


def test_sampler_counts_of_the_served_script(span_rows):
    """One triple of counts a decode dispatch; the served script is
    greedy, so no dispatch draws and none sorts — counted as 0, not
    left out, so that the share exists and reads 0."""
    rows = span_rows[0]
    by = {n: [e[3] for e in rows if e[2] == "sample." + n]
          for n in ("dispatches", "dispatches_drawn", "dispatches_sorted")}
    dispatches = [e for e in rows if e[0] == 1 and e[2] == "engine.dispatch"]
    assert by["dispatches"] == [1] * len(dispatches) and dispatches
    assert by["dispatches_drawn"] == [0] * len(dispatches)
    assert by["dispatches_sorted"] == [0] * len(dispatches)


def test_sampler_counts_equal_the_hand_count(devices8):
    """Three requests, two tokens a chunk, the first token drawn at
    admission. Greedy ``g`` (9 tokens) decodes in dispatches 1-4.
    Sampled, unfiltered ``s`` (3 tokens; ``top_k`` at the vocabulary's
    width filters nothing) joins for dispatch 2 alone: drawn, not
    sorted. Nucleus ``n`` (5 tokens) joins for dispatches 3 and 4:
    drawn and sorted. Greedy ``h`` (3 tokens) decodes alone in
    dispatch 5: 0 and 0 again."""
    cfg = standalone_gpt_config(vocab_size=96, seq_len=64)
    mesh = mx.build_mesh(tp=1, devices=devices8[:1])
    spans = SpanRecorder()
    with Engine(cfg, gpt.init(cfg, jax.random.PRNGKey(0)), mesh,
                EngineConfig(slots=2, max_prompt_len=8, max_seq_len=24,
                             decode_chunk=2)) as eng:
        sched = Scheduler(eng, clock=_Clock(), spans=spans)

        def dispatches():
            return sum(1 for e in spans.events()
                       if e[2] == "sample.dispatches")

        def step_until(n):
            while dispatches() < n:
                sched.step()
            assert dispatches() == n

        sched.submit(Request("g", [1, 2, 3], max_tokens=9))
        step_until(1)
        sched.submit(Request("s", [4, 5], max_tokens=3,
                             sampling=SamplingParams(
                                 temperature=0.7, top_k=96, seed=1)))
        step_until(2)
        sched.submit(Request("n", [6, 7, 8], max_tokens=5,
                             sampling=SamplingParams(
                                 temperature=0.8, top_p=0.9, seed=2)))
        step_until(4)
        sched.submit(Request("h", [9], max_tokens=3))
        sched.run_until_idle()
        assert set(sched.completions) == {"g", "s", "n", "h"}
    by = {n: [e[3] for e in spans.events() if e[2] == "sample." + n]
          for n in ("dispatches", "dispatches_drawn", "dispatches_sorted")}
    assert by["dispatches"] == [1, 1, 1, 1, 1]
    assert by["dispatches_drawn"] == [0, 1, 1, 1, 0]
    assert by["dispatches_sorted"] == [0, 0, 1, 1, 0]


def test_without_a_recorder_nothing_is_annotated(tiny_engine, monkeypatch):
    from apex_tpu import profiler

    called = []
    monkeypatch.setattr(profiler, "annotate",
                        lambda name: called.append(name))
    monkeypatch.setattr(jax.profiler, "TraceAnnotation",
                        lambda *a, **k: called.append(a))
    sched, _ = _served(tiny_engine)
    assert sched.spans is None and not called
