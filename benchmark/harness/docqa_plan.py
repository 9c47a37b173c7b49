"""The programs of an engine under the latent mixer, with the abstract
arguments each compiles for: the sibling of ``harness/plan.py``, whose
table is written for the GPT-2 engines' argument lists (an admission
here also takes each row's start position and block table, and there
are fill programs). ``tools/aot_plan_docqa.py`` plans a described chip
with it, a traced ``serve_docqa`` run the live engine.
"""

from __future__ import annotations


def programs(eng, params, cache, state):
    """``name -> (jitted program, abstract arguments)`` of every step,
    admission and fill program of ``eng``; ``params`` / ``cache`` /
    ``state`` are arrays or ``ShapeDtypeStruct`` trees."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    ecfg, vocab = eng.engine_cfg, eng.cfg.vocab_size
    arr = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt)
    abstract = lambda tree: jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=getattr(x, "sharding", None)), tree)
    p, c, s = abstract(params), abstract(cache), abstract(state)
    i32, f32, mp = np.int32, np.float32, eng.max_pages
    out = {}
    for chunk, fn in sorted(eng._step_variants.items()):
        out[f"step_c{chunk}"] = (fn, (
            p, c, s, arr((ecfg.slots, vocab), jnp.bool_),
            arr((ecfg.slots, mp), i32)))
    for (bucket, k), fn in sorted(eng._admits.items()):
        out[f"admit_p{bucket}_k{k}"] = (fn, (
            p, c, s, arr((k,), i32), arr((k, bucket), i32), arr((k,), i32),
            arr((k,), i32), arr((k,), i32), arr((k,), f32), arr((k,), i32),
            arr((k,), f32), arr((k, 2), np.uint32), arr((k,), i32),
            arr((k,), i32), arr((k,), jnp.bool_),
            arr((k, vocab), jnp.bool_), arr((k, mp), i32)))
    for width, fn in sorted(eng._fills.items()):
        out[f"fill_t{width}"] = (fn, (
            p, c, arr((1, width), i32), arr((1,), i32), arr((1,), i32),
            arr((1, mp), i32)))
    return out
