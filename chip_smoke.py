#!/usr/bin/env python3
"""Chip smoke: the quickest proof that apex_tpu still starts on the chip.

One process, one command, no arguments: ``python chip_smoke.py`` from the
root of a checkout on a machine with a TPU. It drives the main path once
at GPT-2 355M full width through the entry points a user calls —
``training.make_train_step`` and ``serving.Engine`` + ``Scheduler`` —
checks what comes out, runs every public decode-attention kernel and the
standalone apex kernels against their references, and prints as its last
line ``{"ok": true, "device": {...}}``. Any failed check raises, so the
exit code is non-zero and no result line is printed. Without a TPU it
exits non-zero before doing any work.

Timings it prints are smoke timings on the named device, not a
benchmark.

``--tiny-cpu`` is for the sandbox only: it relaxes the device gate and
shrinks every size so the same code runs end to end on the CPU with the
Pallas kernels interpreted — to spend no chip time on typos.
"""

import argparse
import functools
import gc
import importlib.metadata
import json
import math
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
NATIVE_SO = os.path.join(ROOT, "apex_tpu", "_native", "libapex_tpu_host.so")

#: the ``355m`` preset of examples/gpt_train.py
FULL = dict(vocab_size=50304, hidden_size=1024, num_layers=24, num_heads=16,
            seq_len=1024)
TINY = dict(vocab_size=512, hidden_size=128, num_layers=2, num_heads=2,
            seq_len=128)

#: kernel-vs-reference tolerances of tests/test_decode_attention.py
BF16_TOL = dict(rtol=3e-2, atol=3e-2)
QUANT_TOL = {"int8": dict(rtol=3e-2, atol=3e-2),
             "fp8": dict(rtol=6e-2, atol=6e-2)}


def log(msg):
    print(msg, flush=True)


def version(dist):
    try:
        return importlib.metadata.version(dist)
    except importlib.metadata.PackageNotFoundError:
        return "not installed"


def device_gate(tiny):
    """Name the device before anything else; refuse anything but a TPU
    running compiled (not interpreted) kernels."""
    import jax

    import apex_tpu
    from apex_tpu.kernels._utils import use_interpret

    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    log(f"device: platform={dev.platform} kind={dev.device_kind} "
        f"count={device['count']}")
    log(f"versions: python={sys.version.split()[0]} jax={jax.__version__} "
        f"jaxlib={version('jaxlib')} libtpu={version('libtpu')}")
    caps = apex_tpu.capabilities()
    log(f"capabilities: {json.dumps(caps, sort_keys=True)}")
    if not tiny:
        if dev.platform != "tpu":
            raise SystemExit(
                f"chip_smoke: no TPU — jax.devices()[0].platform is "
                f"{dev.platform!r}; refusing to smoke-test a fallback")
        if os.environ.get("APEX_TPU_FORCE_INTERPRET") is not None:
            raise SystemExit(
                "chip_smoke: APEX_TPU_FORCE_INTERPRET is set — kernels "
                "would not be compiled by Mosaic")
        if use_interpret():
            raise SystemExit(
                "chip_smoke: use_interpret() is True on a TPU platform")
    if not caps["native_host_runtime"]:
        raise SystemExit(
            "chip_smoke: csrc/host_runtime.cpp did not build or load")
    return device


def check_close(name, got, want, *, rtol, atol):
    import numpy as np

    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    if not np.all(np.isfinite(got)):
        raise AssertionError(f"{name}: non-finite values")
    np.testing.assert_allclose(got, want, rtol=rtol, atol=atol,
                               err_msg=name)
    return float(np.max(np.abs(got - want))) if got.size else 0.0


# ---------------------------------------------------------------------------
# trainer
# ---------------------------------------------------------------------------

def train_phase(label, model, mesh, batches, steps):
    """A few ``make_train_step`` steps with the bench knobs on one
    repeated synthetic batch; every loss finite and the last below the
    first. ``batches`` is tried in order until one fits device memory —
    the widths are never lowered. Returns the final state and metrics
    (for the placement check)."""
    import jax
    import jax.numpy as jnp
    from jax.errors import JaxRuntimeError

    from apex_tpu.amp import ScalerConfig
    from apex_tpu.models import gpt, training
    from apex_tpu.optimizers import fused_adam

    tp = mesh.shape["tp"]
    seq = model["seq_len"]
    cfg = gpt.GPTConfig(
        remat=True, ce_chunk=min(512, seq // 2),
        compute_dtype=jnp.bfloat16, attn_impl="flash", ln_impl="xla",
        remat_policy="qkv_fc1_attn", sequence_parallel=tp > 1, **model)
    init_fn, step_fn = training.make_train_step(
        cfg, mesh, fused_adam(1e-4, layout="tree"),
        ScalerConfig(enabled=False))
    state = init_fn(jax.random.PRNGKey(0))
    for batch in batches:
        tok = jax.random.randint(jax.random.PRNGKey(1), (batch, seq), 0,
                                 cfg.vocab_size)
        tgt = jnp.roll(tok, -1, axis=1)
        t0 = time.perf_counter()
        try:
            compiled = step_fn.lower(state, tok, tgt).compile()
        except JaxRuntimeError as e:
            if "RESOURCE_EXHAUSTED" not in str(e):
                raise
            log(f"{label}: batch {batch} does not fit device memory: "
                f"{str(e).splitlines()[0][:300]}")
            continue
        compile_s = time.perf_counter() - t0
        break
    else:
        raise AssertionError(
            f"{label}: no batch in {batches} fits device memory")
    log(f"{label}: mesh={dict(mesh.shape)} batch={batch} seq={seq} fit; "
        f"compile {compile_s:.1f} s (smoke timing)")
    losses, step_s = [], []
    for i in range(steps):
        t0 = time.perf_counter()
        state, metrics = compiled(state, tok, tgt)
        jax.block_until_ready((state, metrics))
        step_s.append(time.perf_counter() - t0)
        losses.append(float(metrics["loss"]))
        log(f"{label}: step {i} loss {losses[-1]:.4f} "
            f"{step_s[-1]:.3f} s (smoke timing)")
    if not all(math.isfinite(loss) for loss in losses):
        raise AssertionError(f"{label}: non-finite loss in {losses}")
    if not losses[-1] < losses[0]:
        raise AssertionError(
            f"{label}: loss did not fall: {losses[0]} -> {losses[-1]}")
    peak = jax.local_devices()[0].memory_stats() or {}
    log(f"{label}: PASS losses {losses[0]:.4f} -> {losses[-1]:.4f}; "
        f"peak_bytes_in_use={peak.get('peak_bytes_in_use', 'not reported')}")
    return state, metrics


def check_four_devices(label, state, metrics):
    """The tp=2 x dp=2 state and the step's output really live on four
    distinct devices."""
    import jax

    params = jax.tree.leaves(state.params)
    on = set().union(*(leaf.sharding.device_set for leaf in params))
    out = metrics["loss"].sharding.device_set
    if len(on) != 4 or len(out) != 4:
        raise AssertionError(
            f"{label}: parameters on {len(on)} devices, step output on "
            f"{len(out)} — expected 4 and 4")
    log(f"{label}: parameters and step output on 4 distinct devices: "
        f"{sorted(d.id for d in on)}")


# ---------------------------------------------------------------------------
# server
# ---------------------------------------------------------------------------

def serve_phase(model, tiny):
    """``Engine.warmup()`` at full width and a horizon where "auto"
    takes the decode kernel, then a ``Scheduler`` answers mixed greedy
    and sampled requests to completion with the recompile guard armed."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from apex_tpu import mesh as mx
    from apex_tpu.models import gpt
    from apex_tpu.serving import (Engine, EngineConfig, Request,
                                  SamplingParams, Scheduler)

    cfg = gpt.GPTConfig(
        remat=False, compute_dtype=jnp.bfloat16,
        # off-TPU "auto" keeps the XLA path; the sandbox run forces the
        # (interpreted) kernel so the same code is exercised
        decode_attn_impl="kernel" if tiny else "auto", **model)
    seq = model["seq_len"]
    if tiny:
        ecfg = EngineConfig(slots=4, max_prompt_len=32, max_seq_len=seq,
                            decode_chunk=4, prompt_buckets=(8, 32),
                            admit_batch_sizes=(1, 2))
        shapes = [(3, 6), (20, 9), (32, 5), (11, 12), (7, 8)]
    else:
        # a short (bucket, k) ladder keeps warmup to minutes; 256 puts
        # the admission prefill on the flash kernel
        ecfg = EngineConfig(slots=8, max_prompt_len=256, max_seq_len=seq,
                            decode_chunk=8, prompt_buckets=(32, 256),
                            admit_batch_sizes=(1, 2, 4))
        shapes = [(5, 48), (30, 64), (256, 40), (100, 96), (17, 33),
                  (200, 72), (64, 128), (9, 24), (150, 56), (31, 80)]
    impl = gpt._decode_attn_impl(cfg, ecfg.max_seq_len)
    if impl != "kernel":
        raise AssertionError(
            f"serve: decode attention resolved to {impl!r}, not the kernel")
    rng = np.random.default_rng(0)
    requests = []
    for i, (p_len, n_new) in enumerate(shapes):
        sampled = i % 2 == 1
        requests.append(Request(
            request_id=f"r{i}",
            prompt=rng.integers(0, cfg.vocab_size, p_len).tolist(),
            max_tokens=n_new,
            sampling=(SamplingParams(temperature=0.8, top_k=40, top_p=0.95,
                                     seed=100 + i)
                      if sampled else SamplingParams())))
    mesh = mx.build_mesh(tp=1, devices=jax.devices()[:1])
    params = gpt.init(cfg, jax.random.PRNGKey(0))
    with Engine(cfg, params, mesh, ecfg) as eng:
        sentinel = eng.recompile_sentinel()
        t0 = time.perf_counter()
        eng.warmup()
        warm_s = time.perf_counter() - t0
        warm = sentinel.compiles_total()
        log(f"serve: warmup {warm_s:.1f} s (smoke timing), "
            f"{warm['backend_compiles']} executables materialised on the "
            f"live jax.monitoring stream, programs "
            f"{sorted(warm['tracked'])}")
        # every program but init (compiled at construction, before the
        # sentinel existed) materialised inside warmup
        if warm["backend_compiles"] < len(warm["tracked"]) - 1:
            raise AssertionError(
                "serve: the monitoring stream delivered fewer compile "
                "events than warmup has programs")
        t0 = time.perf_counter()
        with eng.recompile_guard() as guard:
            sched = Scheduler(eng)
            for r in requests:
                sched.submit(r)
            sched.run_until_idle()
            delta = guard.check()
        serve_s = time.perf_counter() - t0
        after = sentinel.compiles_total()
        summary = sched.summary()
    compiles = after["backend_compiles"] - warm["backend_compiles"]
    log(f"serve: compiles after warmup {compiles} (guard delta {delta}, "
        f"alarms {guard.alarms})")
    if compiles or delta or guard.alarms:
        raise AssertionError("serve: compilation after warmup")
    for r in requests:
        c = sched.completions.get(r.request_id)
        if c is None:
            raise AssertionError(f"serve: {r.request_id} never completed")
        ok = (c.finish_reason == "length" and len(c.tokens) == r.max_tokens
              and all(0 <= t < cfg.vocab_size for t in c.tokens))
        log(f"serve: {r.request_id} prompt {len(r.prompt)} "
            f"{'sampled' if r.sampling.temperature else 'greedy'} -> "
            f"{len(c.tokens)}/{r.max_tokens} tokens, {c.finish_reason}")
        if not ok:
            raise AssertionError(f"serve: {r.request_id} did not finish "
                                 f"normally: {c}")
    faults = {k: summary[k] for k in ("retries", "retry_exhausted",
                                      "rebuilds", "watchdog_trips")}
    log(f"serve: {faults} health={sched.health.state} "
        f"tokens={int(summary['tokens_emitted'])} in {serve_s:.2f} s "
        f"(smoke timing)")
    if any(faults.values()) or sched.health.state != "ok":
        raise AssertionError("serve: the resilience layer absorbed a fault")
    log("serve: PASS")


def decode_logits_phase(model):
    """The kernel is right, not merely running: one ``decode_step``'s
    logits under ``decode_attn_impl="kernel"`` against ``"xla"`` on the
    same prefilled cache, at full width and horizon. Depth is cut to
    the two layers of the unit test whose tolerance this is: the two
    paths round differently (fp32 scores and a late normalisation in
    the kernel, bf16 scores in XLA) and that compounds per layer — at
    24 layers 6 logits in 201216 land just outside it (PERF.md)."""
    import dataclasses

    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from apex_tpu import mesh as mx
    from apex_tpu.models import gpt

    seq = model["seq_len"]
    p_len = seq // 4
    cfg = gpt.GPTConfig(remat=False, compute_dtype=jnp.bfloat16,
                        **{**model, "num_layers": 2})
    mesh = mx.build_mesh(tp=1, devices=jax.devices()[:1])
    params = gpt.init(cfg, jax.random.PRNGKey(0))
    pspecs = gpt.param_specs(cfg)
    prompt = jax.random.randint(jax.random.PRNGKey(1), (4, p_len), 0,
                                cfg.vocab_size)
    tok = jax.random.randint(jax.random.PRNGKey(2), (4,), 0, cfg.vocab_size)
    pos = jnp.asarray([p_len, p_len // 2, 1, p_len - 1], jnp.int32)
    cache_spec = gpt.cache_specs(cfg)
    smap = functools.partial(jax.shard_map, mesh=mesh, check_vma=False)
    cache, _ = jax.jit(smap(
        lambda p, t: gpt.prefill(cfg, p, t, max_len=seq),
        in_specs=(pspecs, P(None, None)),
        out_specs=(cache_spec, P(None, None))))(params, prompt)
    logits = {}
    for impl in ("kernel", "xla"):
        icfg = dataclasses.replace(cfg, decode_attn_impl=impl)
        logits[impl], _ = jax.jit(smap(
            lambda p, c, tk, ps, icfg=icfg: gpt.decode_step(
                icfg, p, c, tk, ps),
            in_specs=(pspecs, cache_spec, P(None), P(None)),
            out_specs=(P(None, None), cache_spec)))(params, cache, tok, pos)
    diff = check_close("decode_step logits kernel vs xla", logits["kernel"],
                       logits["xla"], **BF16_TOL)
    log(f"decode logits: kernel vs xla at horizon {seq}, "
        f"{cfg.num_layers} layers: max|diff| {diff:.2e} within "
        f"{BF16_TOL}: PASS")


# ---------------------------------------------------------------------------
# every decode-attention kernel variant
# ---------------------------------------------------------------------------

def _attend_reference(q, k_cache, v_cache, pos):
    """fp32 materialised-scores attention of ``q [b, h, d]`` over
    ``[b, h, S, d]`` caches, columns ``0..pos[b]``."""
    import jax
    import jax.numpy as jnp

    q, k_cache, v_cache = (t.astype(jnp.float32)
                           for t in (q, k_cache, v_cache))
    s = jnp.einsum("bhd,bhsd->bhs", q, k_cache) / q.shape[-1] ** 0.5
    valid = jnp.arange(k_cache.shape[2])[None, None] <= pos[:, None, None]
    p = jax.nn.softmax(jnp.where(valid, s, -1e30), axis=-1)
    return jnp.einsum("bhs,bhsd->bhd", p, jnp.where(
        valid[..., None], v_cache, 0.0))


def decode_kernel_sweep(model, tiny):
    """Compile and run once each public entry of
    ``kernels/decode_attention.py`` at the model's head shapes, against
    the ``*_xla`` functions beside it. One line per variant; a variant
    the compiler refuses raises."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from apex_tpu import kernels as K

    h = model["num_heads"]
    d = model["hidden_size"] // h
    S = model["seq_len"]
    b, T = (4, 3) if tiny else (8, 4)
    page = 32        # a whole int8/fp8 tile of positions per page
    mp = S // page
    n_pages = b * mp + 1
    keys = iter(jax.random.split(jax.random.PRNGKey(7), 16))
    rnd = lambda shape: jax.random.normal(next(keys), shape) * 0.5
    q, k1, v1 = (rnd((b, h, d)).astype(jnp.bfloat16) for _ in range(3))
    kT, vT = (rnd((b, h, T, d)).astype(jnp.bfloat16) for _ in range(2))
    raw = dict(kc=rnd((b, h, S, d)), vc=rnd((b, h, S, d)),
               kp=rnd((n_pages, h, page, d)), vp=rnd((n_pages, h, page, d)))
    # rows own disjoint pages in scrambled order; page 0 is nobody's
    table = jnp.asarray(np.random.default_rng(0).permutation(
        np.arange(1, n_pages)).reshape(b, mp), jnp.int32)
    # first/last cells, tile and page boundaries; T columns fit the horizon
    pos = jnp.asarray([0, page - 1, page, S - T, S // 2 + 5, 17,
                       S - 2 * page, S // 3][:b], jnp.int32)

    def values(cache):
        # a cache — (data,) or (storage, scales) — as fp32 values
        data = cache[0].astype(jnp.float32)
        return data * cache[1][..., None] if len(cache) == 2 else data

    def run(name, kernel_fn, reference_fn, operands, tol):
        """Both sides map the operand dict to (attention out or None,
        [cache, ...])."""
        out, caches = jax.jit(kernel_fn)(operands)
        jax.block_until_ready((out, caches))
        ref_out, ref_caches = jax.jit(reference_fn)(operands)
        worst = 0.0
        if out is not None:
            worst = check_close(f"{name} out", out, ref_out, **tol)
        for got, want in zip(caches, ref_caches):
            worst = max(worst, check_close(
                f"{name} cache", values(got), values(want), **tol))
        log(f"decode-kernel variant {name}: compiled, matches the XLA "
            f"reference (max|diff| {worst:.2e})")

    for kind in ("bf16", "int8", "fp8"):
        quant = kind != "bf16"
        tol = QUANT_TOL[kind] if quant else BF16_TOL
        # the plain writes are copies: bit-exact
        wtol = QUANT_TOL[kind] if quant else dict(rtol=0.0, atol=0.0)
        if quant:
            store = lambda x: tuple(K.quantize_kv_rows(x, kind))
        else:
            store = lambda x: (x.astype(jnp.bfloat16),)
        ops = dict(q=q, k1=k1, v1=v1, kT=kT, vT=vT, table=table, pos=pos,
                   **{name: store(x) for name, x in raw.items()})
        n = len(ops["kc"])
        pair = lambda flat: [tuple(flat[:n]), tuple(flat[n:])]
        col = lambda x: x[:, :, None]

        def xla_write(cache, new, o):
            return tuple(K.cache_write_columns_xla(plane, x, o["pos"])
                         for plane, x in zip(cache, store(new)))

        def xla_pwrite(pool, new, o):
            return tuple(
                K.paged_write_columns_xla(plane, x, o["table"], o["pos"])
                for plane, x in zip(pool, store(new)))

        def attend(kcache, vcache, o):
            return _attend_reference(o["q"], values(kcache),
                                     values(vcache), o["pos"])

        def k_decode(o):
            if quant:
                out, *flat = K.decode_attention_quantized(
                    o["q"], o["k1"], o["v1"], *o["kc"], *o["vc"],
                    o["pos"], kind=kind)
            else:
                out, *flat = K.decode_attention(
                    o["q"], o["k1"], o["v1"], *o["kc"], *o["vc"], o["pos"])
            return out, pair(flat)

        def r_decode(o):
            kw = xla_write(o["kc"], col(o["k1"]), o)
            vw = xla_write(o["vc"], col(o["v1"]), o)
            return attend(kw, vw, o), [kw, vw]

        def k_cols(o):
            args = (o["kT"], o["vT"], *o["kc"], *o["vc"], o["pos"])
            flat = (K.cache_write_columns_quant(*args, kind) if quant
                    else K.cache_write_columns(*args))
            return None, pair(list(flat))

        def r_cols(o):
            return None, [xla_write(o["kc"], o["kT"], o),
                          xla_write(o["vc"], o["vT"], o)]

        def k_pcol(o):
            args = (o["k1"], o["v1"], *o["kp"], *o["vp"], o["table"],
                    o["pos"])
            flat = (K.paged_write_column_quant(*args, kind) if quant
                    else K.paged_write_column(*args))
            return None, pair(list(flat))

        def r_pcol(o):
            return None, [xla_pwrite(o["kp"], col(o["k1"]), o),
                          xla_pwrite(o["vp"], col(o["v1"]), o)]

        def k_pcols(o):
            args = (o["kT"], o["vT"], *o["kp"], *o["vp"], o["table"],
                    o["pos"])
            flat = (K.paged_write_columns_quant(*args, kind) if quant
                    else K.paged_write_columns(*args))
            return None, pair(list(flat))

        def r_pcols(o):
            return None, [xla_pwrite(o["kp"], o["kT"], o),
                          xla_pwrite(o["vp"], o["vT"], o)]

        def k_pattn(o):
            args = (o["q"], *o["kp"], *o["vp"], o["table"], o["pos"])
            if quant:
                return K.paged_attention_quantized(*args, kind=kind), []
            return K.paged_attention(*args), []

        def r_pattn(o):
            gather = lambda pool: tuple(
                K.paged_gather_xla(plane, o["table"]) for plane in pool)
            return attend(gather(o["kp"]), gather(o["vp"]), o), []

        run(f"decode_attention[{kind}]", k_decode, r_decode, ops, tol)
        run(f"cache_write_columns[{kind}]", k_cols, r_cols, ops, wtol)
        run(f"paged_write_column[{kind}]", k_pcol, r_pcol, ops, wtol)
        run(f"paged_write_columns[{kind}]", k_pcols, r_pcols, ops, wtol)
        run(f"paged_attention[{kind}]", k_pattn, r_pattn, ops, tol)
    log("decode-kernel sweep: PASS (15 variants, none skipped)")


# ---------------------------------------------------------------------------
# the standalone kernels that are apex's public surface
# ---------------------------------------------------------------------------

def standalone_kernel_sweep(model, tiny):
    """flat_ops (Adam, l2norm, scale, axpby), layer_norm forward and
    backward, softmax, xentropy — once each at the model's shapes,
    against ``jax.numpy``."""
    import jax
    import jax.numpy as jnp

    from apex_tpu import kernels as K

    hid, heads, vocab = (model["hidden_size"], model["num_heads"],
                         model["vocab_size"])
    seq = model["seq_len"]
    keys = iter(jax.random.split(jax.random.PRNGKey(11), 16))
    rnd = lambda shape: jax.random.normal(next(keys), shape)

    # one layer's matmul weights (12 h^2) as a flat fp32 buffer
    n = 12 * hid * hid
    p, g = rnd((n,)) * 0.02, rnd((n,)) * 1e-3
    m, v = rnd((n,)) * 1e-3, jnp.abs(rnd((n,))) * 1e-6
    hp = dict(lr=1e-3, b1=0.9, b2=0.999, eps=1e-8, weight_decay=0.01,
              bias_correction1=0.1, bias_correction2=0.001)
    (np_,), (nm,), (nv,) = jax.jit(lambda: K.adam_flat(
        [p], [g], [m], [v], **hp))()
    rm = hp["b1"] * m + (1 - hp["b1"]) * g
    rv = hp["b2"] * v + (1 - hp["b2"]) * g * g
    upd = (rm / hp["bias_correction1"]) / (
        jnp.sqrt(rv / hp["bias_correction2"]) + hp["eps"])
    rp = p - hp["lr"] * (upd + hp["weight_decay"] * p)
    check_close("adam_flat m", nm, rm, rtol=1e-5, atol=1e-8)
    check_close("adam_flat v", nv, rv, rtol=1e-5, atol=1e-12)
    check_close("adam_flat p", np_, rp, rtol=1e-5, atol=2e-6)
    log(f"standalone kernel adam_flat [{n}]: matches jax.numpy")

    norm = jax.jit(lambda: K.l2norm_flat([p, g]))()
    check_close("l2norm_flat", norm,
                jnp.sqrt(jnp.sum(p * p) + jnp.sum(g * g)),
                rtol=1e-4, atol=0.0)
    (sc,), inf = jax.jit(lambda: K.scale_flat([g], 1 / 1024.0))()
    check_close("scale_flat", sc, g / 1024.0, rtol=1e-6, atol=0.0)
    (ax,), inf2 = jax.jit(lambda: K.axpby_flat(0.5, [p], 2.0, [g]))()
    check_close("axpby_flat", ax, 0.5 * p + 2.0 * g, rtol=1e-6, atol=1e-9)
    if bool(inf) or bool(inf2):
        raise AssertionError("flat_ops: overflow flag set on finite input")
    log(f"standalone kernels l2norm_flat, scale_flat, axpby_flat [{n}]: "
        f"match jax.numpy")

    rows = (4 if tiny else 16) * seq
    x = rnd((rows, hid)).astype(jnp.bfloat16)
    w, bias = 1.0 + 0.1 * rnd((hid,)), 0.1 * rnd((hid,))
    dy = rnd((rows, hid)).astype(jnp.bfloat16)

    def ln_ref(x, w, bias):
        xf = x.astype(jnp.float32)
        mu = xf.mean(-1, keepdims=True)
        var = ((xf - mu) ** 2).mean(-1, keepdims=True)
        return (xf - mu) * jax.lax.rsqrt(var + 1e-5) * w + bias

    def fwd_bwd(fn):
        y, vjp = jax.vjp(fn, x, w, bias)
        return (y,) + vjp(dy.astype(y.dtype))

    got = jax.jit(lambda: fwd_bwd(K.layer_norm))()
    want = jax.jit(lambda: fwd_bwd(ln_ref))()
    for name, a, r, tol in zip(
            ("y", "dx", "dw", "db"), got, want,
            (BF16_TOL, BF16_TOL, dict(rtol=5e-3, atol=0.5),
             dict(rtol=5e-3, atol=0.5))):
        check_close(f"layer_norm {name}", a, r, **tol)
    log(f"standalone kernel layer_norm fwd+bwd [{rows}, {hid}] bf16: "
        f"matches jax.numpy")

    s = seq
    scores = rnd((1, heads, s, s)).astype(jnp.bfloat16)
    sm = jax.jit(lambda: K.scaled_upper_triang_masked_softmax(
        scores, scale=0.125))()
    tri = jnp.tril(jnp.ones((s, s), bool))
    sm_ref = jax.nn.softmax(jnp.where(
        tri, scores.astype(jnp.float32) * 0.125, -1e30), axis=-1)
    check_close("scaled_upper_triang_masked_softmax", sm, sm_ref,
                rtol=2e-2, atol=1e-3)
    log(f"standalone kernel softmax [1, {heads}, {s}, {s}] bf16: matches "
        f"jax.numpy")

    t_rows = 2 * seq
    logits = rnd((t_rows, vocab))
    target = jax.random.randint(next(keys), (t_rows,), 0, vocab)

    def ce_ref(lg):
        lse = jax.nn.logsumexp(lg, axis=-1)
        return lse - jnp.take_along_axis(lg, target[:, None], axis=-1)[:, 0]

    loss, dlogits = jax.jit(lambda: jax.value_and_grad(
        lambda lg: K.softmax_cross_entropy(lg, target).mean())(logits))()
    rloss, rdl = jax.jit(lambda: jax.value_and_grad(
        lambda lg: ce_ref(lg).mean())(logits))()
    check_close("softmax_cross_entropy loss", loss, rloss, rtol=1e-5,
                atol=1e-5)
    check_close("softmax_cross_entropy grad", dlogits, rdl, rtol=1e-3,
                atol=1e-11)
    log(f"standalone kernel xentropy fwd+bwd [{t_rows}, {vocab}] fp32: "
        f"matches jax.numpy")
    log("standalone kernel sweep: PASS")


# ---------------------------------------------------------------------------

def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument(
        "--tiny-cpu", action="store_true",
        help="sandbox only: relax the device gate and run every phase at "
        "a tiny size on the CPU with the kernels interpreted")
    args = ap.parse_args()
    tiny = args.tiny_cpu
    model = TINY if tiny else FULL

    started = time.time()
    # the native host runtime must be one built in THIS run from the
    # tracked source: drop whatever artifact the tree was copied with
    # (the gate then requires that it built and loaded)
    if os.path.exists(NATIVE_SO):
        os.remove(NATIVE_SO)
    device = device_gate(tiny)

    import jax

    from apex_tpu import mesh as mx
    from apex_tpu._capabilities import enable_compilation_cache
    from apex_tpu.telemetry import RecompileSentinel

    log(f"compile cache: {enable_compilation_cache() or 'disabled'}")
    process = RecompileSentinel().install()   # whole-run cache hit/miss

    devices = jax.devices()
    steps = 3 if tiny else 5
    train_phase("train[1 chip]", model,
                mx.build_mesh(tp=1, devices=devices[:1]),
                (2,) if tiny else (16, 12, 8, 4), steps)
    gc.collect()
    serve_phase(model, tiny)
    gc.collect()
    decode_logits_phase(model)
    gc.collect()
    decode_kernel_sweep(model, tiny)
    standalone_kernel_sweep(model, tiny)
    gc.collect()
    if len(devices) >= 4:
        label = "train[4 chips, tp=2 dp=2]"
        state, metrics = train_phase(
            label, model, mx.build_mesh(tp=2, devices=devices[:4]),
            (4,) if tiny else (16, 8), steps)
        check_four_devices(label, state, metrics)
    else:
        log(f"train[4 chips]: not run — {len(devices)} device(s) "
            f"visible, four needed")

    totals = process.compiles_total()
    process.uninstall()
    log(f"compile cache: {totals['cache_hits']} hits, "
        f"{totals['cache_misses']} misses, {totals['backend_compiles']} "
        f"executables, {totals['compile_seconds']:.1f} s compiling; "
        f"total {time.time() - started:.0f} s")
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
