"""Compile-only checks for a described v5e — no chip, nothing runs.

What the CPU backbone cannot see: whether Mosaic accepts the decode
kernels' blocks at the model's real widths, and what the TPU compiler
does with the resident cache at a program's entry and exit. The TPU
compiler is installed beside the CPU backend and compiles for a
``v5e:2x2`` topology that is described and not attached; a machine
where it cannot be described skips the file. A compile that passes is
not a chip run.

The topology is described inside a fixture, never at import (one
process holds the TPU library at a time), and every such test lives in
this one file.
"""

import contextlib
import importlib
import json
import math
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, SingleDeviceSharding

from apex_tpu import mesh as mx
from apex_tpu.kernels import _utils as kernel_utils
from apex_tpu.models import gpt
from apex_tpu.serving.engine import Engine, EngineConfig
from apex_tpu.transformer.testing import standalone_gpt_config

# the module, not the function the package re-exports under its name
da = importlib.import_module("apex_tpu.kernels.decode_attention")


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    with pytest.MonkeyPatch.context() as env:
        # what the TPU library asks of a host that has no TPU
        env.setenv("TPU_LOG_DIR", "disabled")
        env.setenv("TPU_ACCELERATOR_TYPE", "v5litepod-4")
        env.setenv("TPU_WORKER_HOSTNAMES", "localhost")
        try:
            yield topologies.get_topology_desc(platform="tpu",
                                               topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@contextlib.contextmanager
def _for_mosaic():
    """Kernels lower for Mosaic instead of the interpreter (this
    process's default backend is the CPU), and no described-device
    program enters the persistent compile cache."""
    from jax.experimental.compilation_cache import compilation_cache

    not_interpreted = lambda: False
    with pytest.MonkeyPatch.context() as m:
        m.setattr(da, "use_interpret", not_interpreted)
        m.setattr(kernel_utils, "use_interpret", not_interpreted)
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield
        finally:
            jax.config.update("jax_enable_compilation_cache", was)
            compilation_cache.reset_cache()


@pytest.fixture
def mosaic():
    with _for_mosaic():
        yield


def _grids(jaxpr, kernel="decode_attn_read"):
    """The grid of every ``kernel`` in ``jaxpr``, loops and calls
    included; a dynamic bound (the decode kernels' count of live rows)
    reads None."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call" and \
                kernel in str(eqn.params.get("name")):
            found.append(tuple(d if isinstance(d, int) else None
                               for d in eqn.params["grid_mapping"].grid))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += _grids(sub, kernel)
    return found


def _nbytes(shape, dtype):
    return math.prod(shape) * jnp.dtype(dtype).itemsize


def _yields(text, shape):
    """The instructions of the compiled ``text`` whose result is an
    array of ``shape`` — either way round its last two dims — and that
    are not a parameter, an element of a tuple or a bitcast."""
    turned = tuple(shape[:-2]) + (shape[-1], shape[-2])
    dims = "|".join(",".join(map(str, sh)) for sh in (shape, turned))
    return [ln.strip()[:160] for ln in text.splitlines()
            if re.search(rf"^\s*(ROOT )?%\S+ = \w+\[({dims})\]", ln)
            and not re.search(r" (parameter|get-tuple-element|bitcast)\(",
                              ln)]


@pytest.mark.parametrize("kind", [None, "int8", "fp8"])
@pytest.mark.parametrize("layout", ["contiguous", "paged", "paged16"])
def test_stacked_decode_kernels_compile_for_v5e(topo, mosaic, layout, kind):
    """GPT-2's head shapes (16 heads of 64) at a horizon of 1024 and
    the serving cells' 40 slots: the layer-indexed write and read
    kernels, and the T-column write, on a stacked cache / page pool in
    each storage; the read's grid is live rows x head groups x chunks,
    all 16 heads in one group, and the step's write grid is the live
    rows (a dynamic bound) where the T-column write's is every row. A
    horizon of 1024 and pages of 128 lie with their positions on the
    lanes in every storage, the kernels take them so, and the donated
    cache is never copied; pages of 16 lie with the PAGES on the lanes,
    the kernels take them row-major, and the compiler relays the pool
    on the way in and out."""
    layers, b, h, s_max, d = 2, 40, 16, 1024, 64
    page = {"contiguous": None, "paged": 128, "paged16": 16}[layout]
    one = SingleDeviceSharding(topo.devices[0])
    arr = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one)
    rows, horizon = (b * s_max // page, page) if page else (b, s_max)
    shape = (layers, 2, rows, h, horizon, d)
    storage = da.kv_storage_dtype(kind) if kind else jnp.bfloat16
    cache = arr(shape, storage)
    if kind:
        cache = {"kv": cache, "scale": arr(shape[:-1], jnp.float32)}
    table = arr((b, s_max // page), jnp.int32) if page else None
    row = arr((b, h, d), jnp.bfloat16)
    lanes = da._positions_on_lanes(d, horizon, storage)
    assert lanes == (layout != "paged16")

    def step(cache, layer, q, k_new, v_new, cols, pos, live, table):
        out, cache = da.stacked_decode_attention(
            q, k_new, v_new, cache, layer, pos, table=table,
            live=da.live_rows(live), kind=kind)
        return out, da.stacked_write_columns(
            cols, cols, cache, layer, pos, table=table, kind=kind)

    traced = jax.jit(step, donate_argnums=0).trace(
        cache, arr((), jnp.int32), row, row, row,
        arr((b, h, 3, d), jnp.bfloat16), arr((b,), jnp.int32),
        arr((b,), jnp.bool_), table)
    bk = page or da.decode_block_k(s_max, storage, quantized=bool(kind))
    assert _grids(traced.jaxpr.jaxpr) == [(None, 1, s_max // bk)]
    assert _grids(traced.jaxpr.jaxpr, "decode_attn_write") == [
        (None,)] + [(b,)] * 3
    compiled = traced.lower().compile()
    text = compiled.as_text()
    calls = lambda name: re.findall(
        rf"^\s*%{name}[.\d]* = .* custom-call\(", text, re.M)
    assert len(calls("decode_attn_write")) == 4
    assert len(calls("decode_attn_read")) == 1
    made = _yields(text, shape)
    copies = [ln for ln in made if "decode_attn_write" not in ln]
    if lanes:
        # the kernels' operand is a bitcast of the cache as it lies
        assert not copies, copies[:3]
        assert compiled.memory_analysis().temp_size_in_bytes < _nbytes(
            shape, storage) // 4
    else:
        assert any(" copy(" in ln for ln in copies), made[:3]


class PlanEngine(Engine):
    """Programs built and never run: nothing can be placed on a
    described device."""

    def _build(self):
        super()._build()
        self.init_program = self._init
        self._init = lambda params: (None, None)


def _plan_engine(topo, vocab_size):
    """``(engine, params, cache, state)`` of a small GPT-2-shaped
    engine on one described chip, everything but the engine as shapes."""
    # a cache of 48 MiB: one small enough for the chip's fast memory is
    # moved there and back, which is no relayout and no model of a
    # deployment's
    cfg = standalone_gpt_config(vocab_size=vocab_size, seq_len=1024,
                                hidden_size=256, num_heads=4,
                                num_layers=3, compute_dtype=jnp.bfloat16)
    assert cfg.head_dim == 64
    ecfg = EngineConfig(slots=16, max_prompt_len=64, max_seq_len=1024,
                        decode_chunk=2)
    mesh = mx.build_mesh(tp=1, devices=list(topo.devices)[:1])
    params = jax.tree.map(
        lambda s, sp: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=NamedSharding(mesh, sp)),
        jax.eval_shape(lambda: gpt.init(cfg, jax.random.PRNGKey(0))),
        gpt.param_specs(cfg))
    eng = PlanEngine(cfg, params, mesh, ecfg)
    cache, state = jax.eval_shape(eng.init_program, params)
    return eng, params, cache, state


def test_engine_step_program_has_no_cache_copy_in_its_loops(topo, mosaic):
    """The engine's step program, compiled for the chip, holds the
    cache in place from its parameter to its result: no instruction
    anywhere yields an array of one layer's cache, none but the aliased
    write kernel (once per layer-loop body) yields the whole cache —
    no ``copy``, no ``transpose``: at head size 64 the resident cache
    lies with its positions on the lanes and the kernels take it so —
    and the program's temporaries are a fraction of the cache. The
    cache's parameter and result keep the device's default layout."""
    eng, params, cache, state = _plan_engine(topo, vocab_size=96)
    cfg, ecfg = eng.cfg, eng.engine_cfg
    assert da._positions_on_lanes(cfg.head_dim, ecfg.max_seq_len,
                                  cache.dtype)
    traced = eng._step_variants[ecfg.decode_chunk].trace(
        params, cache, state,
        jax.ShapeDtypeStruct((ecfg.slots, cfg.vocab_size), jnp.bool_))
    # one read kernel in the program: the live slots x one group of all
    # four heads x the chunks of the horizon
    assert _grids(traced.jaxpr.jaxpr) == [
        (None, 1, -(-ecfg.max_seq_len // eng.read_chunk))]
    compiled = traced.lower().compile()
    text = compiled.as_text()
    assert not _yields(text, cache.shape[1:]), _yields(
        text, cache.shape[1:])[:3]
    made = _yields(text, cache.shape)
    assert made and all("decode_attn_write" in ln for ln in made), made[:3]
    assert compiled.memory_analysis().temp_size_in_bytes < _nbytes(
        cache.shape, cache.dtype) // 4
    # positions on the lanes, as the device lays a minor dim of 64 out
    # by itself: nothing asked for it
    whole = ",".join(map(str, cache.shape))
    lies = rf"bf16\[{whole}\]\{{4,5,3,2,1,0[:}}]"
    assert re.search(lies + r".* parameter\(", text)
    assert re.search(
        rf"entry_computation_layout=.*{lies}.*->.*{lies}", text)


def _scan_bodies(jaxpr, length):
    """The body of every ``scan`` of ``length`` steps in ``jaxpr``,
    nested ones included."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scan" and eqn.params["length"] == length:
            found.append(eqn.params["jaxpr"].jaxpr)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += _scan_bodies(sub, length)
    return found


def _ops_over(jaxpr, shape):
    """Operations anywhere in ``jaxpr`` that take an operand of
    ``shape``."""
    found = [eqn.primitive.name for eqn in jaxpr.eqns
             if any(getattr(v.aval, "shape", None) == shape
                    for v in eqn.invars)]
    for eqn in jaxpr.eqns:
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += _ops_over(sub, shape)
    return found


@pytest.fixture(scope="module")
def chat_step(topo):
    """The chat cell's deployment at its real size (GPT-2 medium, fp32
    parameters, bf16 compute, 40 slots, chunks of 8 steps, a horizon of
    1024): ``(engine, the caller's parameters as shapes, the step
    program traced with the weights the engine holds, compiled, (cache,
    state) as shapes)`` for the described v5e, compiled once for the
    tests that read it."""
    from benchmark.harness import recipe
    from benchmark.jobs import serve_base

    cfg, ecfg = serve_base.engine_setup(recipe.load_cell("gpt2m_chat"))
    assert (ecfg.slots, ecfg.decode_chunk, ecfg.max_seq_len) == (
        40, 8, 1024)
    assert jnp.dtype(cfg.param_dtype) == jnp.float32
    with _for_mosaic():
        mesh = mx.build_mesh(tp=1, devices=list(topo.devices)[:1])
        params = jax.tree.map(
            lambda s, sp: jax.ShapeDtypeStruct(
                s.shape, s.dtype, sharding=NamedSharding(mesh, sp)),
            jax.eval_shape(lambda: gpt.init(cfg, jax.random.PRNGKey(0))),
            gpt.param_specs(cfg))
        eng = PlanEngine(cfg, params, mesh, ecfg)
        cache, state = jax.eval_shape(eng.init_program, eng._params)
        traced = eng._step_variants[ecfg.decode_chunk].trace(
            eng._params, cache, state,
            jax.ShapeDtypeStruct((ecfg.slots, cfg.vocab_size), jnp.bool_))
        return eng, params, traced, traced.lower().compile(), (cache, state)


def _plan_gib(compiled):
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes + m.generated_code_size_in_bytes
            - m.alias_size_in_bytes) / 2 ** 30


def test_chat_step_program_walks_the_live_rows(chat_step):
    """The chat cell's step program as the engine runs it: both decode
    kernels' row axes are the live count, a dynamic bound; the row list
    is built once a decode step, outside the layer scan, whose body
    holds no operation over a ``[slots, slots]`` operand; and the plan
    is 4.62 GiB to 1 %. The program that takes the fp32 parameters and
    casts them plans 5.75 GiB: 0.56 GiB more of arguments, and the 0.56
    GiB bf16 copy of the layer stacks that is the bulk of its
    temporaries (the word table's 0.1 GiB copy is in both)."""
    eng, _, traced, compiled, _ = chat_step
    cfg, ecfg = eng.cfg, eng.engine_cfg
    jaxpr = traced.jaxpr.jaxpr
    assert _grids(jaxpr) == [(None, 1, ecfg.max_seq_len // eng.read_chunk)]
    assert _grids(jaxpr, "decode_attn_write") == [(None,)]
    square = (ecfg.slots, ecfg.slots)
    (steps,) = _scan_bodies(jaxpr, ecfg.decode_chunk)
    (layers,) = _scan_bodies(jaxpr, cfg.num_layers)
    assert _ops_over(steps, square)
    assert not _ops_over(layers, square), _ops_over(layers, square)
    plan = _plan_gib(compiled)
    assert abs(plan - 4.62) < 0.0462, plan


def test_chat_step_program_casts_no_weight(chat_step):
    """The step program the engine runs takes every layer weight a
    forward casts (matmul kernels and biases) as a bf16 parameter and
    converts none of them: no instruction of the entry that reads such
    a parameter yields another element type. The LayerNorm affine and
    the word and position tables stay fp32 parameters."""
    eng, params, _, compiled, _ = chat_step
    caller = jax.tree_util.tree_flatten_with_path(params)[0]
    held = jax.tree.leaves(eng._params)
    comps, entry = _computations(compiled.as_text())
    # an entry parameter's number is its leaf's place in the flattened
    # arguments, the weights first
    found = {int(m.group(3)): (m.group(1), m.group(2)) for m in (
        re.match(r"\s*%(\S+) = (\w+)\[.* parameter\((\d+)\)", ln)
        for ln in comps[entry]) if m}
    cast = 0
    for i, ((path, x), mine) in enumerate(zip(caller, held)):
        name, dtype = found[i]
        if x.dtype == mine.dtype:
            assert dtype == "f32", (jax.tree_util.keystr(path), dtype)
            continue
        cast += 1
        assert dtype == "bf16", (jax.tree_util.keystr(path), dtype)
        for ln in comps[entry]:
            if not re.search(rf"%{re.escape(name)}[,)]",
                             ln.split(" = ", 1)[-1]):
                continue
            out = re.match(r"\s*(ROOT )?%\S+ = (\w+)\[", ln)
            assert " convert(" not in ln and (
                out is None or out.group(2) == "bf16"), ln[:160]
    # attn's and mlp's two kernels and two biases each
    assert cast == 8


def _matmuls(text):
    """``(tilings, bodies)`` of a compiled program: the window of every
    matmul fusion in program order — how the compiler splits its
    operands and contraction, and so the order of its sums — and the
    computations that hold a matmul, names dropped, sorted."""
    tilings = []
    for ln in text.splitlines():
        if '"window_config"' in ln and (
                "kind=kOutput" in ln or " convolution(" in ln):
            w = json.loads(re.search(r"backend_config=(\{.*\})", ln)
                           .group(1))["window_config"]
            tilings.append(tuple(tuple(w[k]) for k in (
                "input_window_bounds", "kernel_window_bounds",
                "output_window_bounds", "iteration_bounds")))
    bodies = []
    for m in re.finditer(r"^%\S+ \([^\n]*\) -> [^\n]* \{\n(.*?)\n\}",
                         text, re.S | re.M):
        if " convolution(" in m.group(1):
            body = re.sub(r", (metadata|backend_config)=.*", "",
                          m.group(1))
            body = re.sub(r"%[\w.\-]+|S\(\d\)", "", body)
            bodies.append(body)
    return tilings, sorted(bodies)


@pytest.mark.parametrize("program", ["step", "widest_admission"])
def test_chat_programs_tile_their_matmuls_as_the_casting_ones(
        chat_step, mosaic, program):
    """The chat cell's step program and its widest admission, compiled
    with the weights the engine holds and with the caller's fp32 tree
    (the programs that cast in every call): every matmul fusion has the
    same body and the same tiling, so it sums the same bf16 products in
    the same order. Holding the word table in bf16 broke this (the
    table became the admission's cross-program prefetch and its
    matmuls were tiled again)."""
    from benchmark.harness import plan

    eng, params, _, held, (cache, state) = chat_step
    name = plan.largest_engine_programs(eng)[program != "step"]
    texts = []
    for tree in (params, eng._params):
        if program == "step" and tree is eng._params:
            texts.append(held.as_text())
            continue
        fn, args = plan.engine_programs(eng, tree, cache, state)[name]
        texts.append(fn.lower(*args).compile().as_text())
    casting, holding = map(_matmuls, texts)
    assert casting[0] and casting[1] and casting == holding


def _computations(text):
    """``({name: lines}, entry)`` of a compiled program's text."""
    comps, entry, cur = {}, None, None
    for ln in text.splitlines():
        head = re.match(r"^(ENTRY )?%(\S+) \(.*\{\s*$", ln)
        if head:
            cur = head.group(2)
            comps[cur] = []
            entry = cur if head.group(1) else entry
        elif ln.startswith("}"):
            cur = None
        elif cur is not None:
            comps[cur].append(ln)
    return comps, entry


def _always_run(comps, entry):
    """The computations the entry reaches — loop bodies, fusions,
    calls — without passing through a ``conditional``."""
    seen, todo = set(), [entry]
    while todo:
        name = todo.pop()
        if name in seen:
            continue
        seen.add(name)
        for ln in comps[name]:
            if " conditional(" not in ln:
                todo += [n for n in re.findall(r"%([\w.\-]+)",
                                               ln.split(" = ", 1)[-1])
                         if n in comps]
    return seen


def test_engine_programs_sort_only_under_a_conditional(topo, mosaic):
    """The sampler's vocabulary sort, in the step program and in an
    admission program compiled for the chip, lies in a branch of a
    ``conditional`` (``sampling.draw_slots``' level 2) and nowhere
    the program always runs: not in the scan's ``while`` body, not in
    the entry. The vocabulary is wide enough that a branch is no
    candidate for a ``select`` of both sides."""
    eng, params, cache, state = _plan_engine(topo, vocab_size=2048)
    cfg, ecfg = eng.cfg, eng.engine_cfg
    arr = jax.ShapeDtypeStruct
    i32, f32 = jnp.int32, jnp.float32
    bucket, k = sorted(eng._admits)[0]
    programs = {
        "step": eng._step_variants[ecfg.decode_chunk].lower(
            params, cache, state,
            arr((ecfg.slots, cfg.vocab_size), jnp.bool_)),
        "admit": eng._admits[(bucket, k)].lower(
            params, cache, state, arr((k,), i32), arr((k, bucket), i32),
            arr((k,), i32), arr((k,), i32), arr((k,), f32),
            arr((k,), i32), arr((k,), f32), arr((k, 2), jnp.uint32),
            arr((k,), i32), arr((k,), i32), arr((k,), jnp.bool_),
            arr((k, cfg.vocab_size), jnp.bool_))}
    for name, lowered in programs.items():
        comps, entry = _computations(lowered.compile().as_text())
        sorting = {c for c, lines in comps.items()
                   if any(" sort(" in ln for ln in lines)}
        assert sorting, name
        always = _always_run(comps, entry)
        assert any(" conditional(" in ln for c in always
                   for ln in comps[c]), name
        assert not sorting & always, (name, sorted(sorting & always))
        if name == "step":
            assert any(" while(" in ln for ln in comps[entry])


def test_latent_step_and_admission_compile_for_v5e(topo, mosaic):
    """The latent mixer's decode step and a batched admission over
    block tables, at the published head, latent, rope and index widths
    (128 heads of 128 + 64 over a 512 + 64 latent row, 64 index heads of
    128) with everything that only scales cut (hidden 256, two layers,
    8 slots, a horizon of 2048 in pages of 128, top-256): the TPU
    compiler takes the two-plane cache's scatter write, the page
    gathers of the indexer, the exact top-k, the row gather through
    the table and the grouped expert products, and the donated cache
    comes out of both programs aliased, not copied."""
    import dataclasses

    from apex_tpu.models import latent
    from apex_tpu.transformer.moe import RoutedConfig

    lc = latent.LatentConfig(
        routed=RoutedConfig(num_experts=32, experts_held=(0, 4), top_k=8,
                            n_group=8, topk_group=4, routed_scale=2.5),
        q_lora_rank=128, index_topk=256, dense_ffn=512, expert_ffn=256)
    cfg = gpt.GPTConfig(vocab_size=1024, hidden_size=256, num_layers=2,
                        num_heads=128, seq_len=2048,
                        compute_dtype=jnp.bfloat16,
                        param_dtype=jnp.bfloat16, latent=lc)
    ecfg = EngineConfig(slots=8, max_prompt_len=1024, max_seq_len=2048,
                        decode_chunk=2, page_size=128,
                        prompt_buckets=(128,), admit_batch_sizes=(1, 2),
                        prefix_pool_slots=2)
    mesh = mx.build_mesh(tp=1, devices=[topo.devices[0]])
    params = jax.tree.map(
        lambda s, sp: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=NamedSharding(mesh, sp)),
        jax.eval_shape(lambda: gpt.init(cfg, jax.random.PRNGKey(0))),
        gpt.param_specs(cfg))

    eng = PlanEngine(cfg, params, mesh, ecfg)
    cache, state = jax.eval_shape(eng.init_program, params)
    arr = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt)
    b, k, mp, v = 8, 2, eng.max_pages, cfg.vocab_size
    i32, f32 = jnp.int32, jnp.float32
    step = eng._step_variants[2].lower(
        params, cache, state, arr((b, v), jnp.bool_), arr((b, mp), i32)
    ).compile()
    admit = eng._admits[(128, k)].lower(
        params, cache, state, arr((k,), i32), arr((k, 128), i32),
        arr((k,), i32), arr((k,), i32), arr((k,), i32), arr((k,), f32),
        arr((k,), i32), arr((k,), f32), arr((k, 2), jnp.uint32),
        arr((k,), i32), arr((k,), i32), arr((k,), jnp.bool_),
        arr((k, v), jnp.bool_), arr((k, mp), i32)).compile()
    held = sum(_nbytes(x.shape, x.dtype) for x in jax.tree.leaves(cache))
    planes = [",".join(map(str, cache[k].shape)) for k in ("ckv", "ki")]
    for compiled in (step, admit):
        assert compiled.memory_analysis().alias_size_in_bytes >= held
        copies = [ln.strip()[:120] for ln in compiled.as_text().splitlines()
                  if " copy(" in ln and any(f"[{p}]" in ln for p in planes)]
        assert not copies, copies
