"""The compiler's memory plan of the engine's programs.

``Engine`` builds its programs in ``_build`` and keeps them in private
tables; there is no public way to ask one for its plan. This module is
the one place that knows those names. The AOT planning tool uses it on
described devices (where slots are chosen before any chip time), and a
traced serving run uses it on the live engine for ``hbm_plan_gib``. If
the program's layout changes, :func:`engine_plans` raises
``AttributeError`` and the metric is left out rather than guessed.
"""

from __future__ import annotations

from typing import Any, Dict, Iterable, Optional, Tuple

from benchmark.harness import device as device_mod


def _abstract(tree):
    import jax

    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                       sharding=getattr(x, "sharding", None)),
        tree)


def engine_programs(eng, params, cache, state
                    ) -> Dict[str, Tuple[Any, tuple]]:
    """``name -> (jitted program, abstract arguments)`` for every step
    and admission program of ``eng``. ``params``/``cache``/``state`` are
    arrays or ``ShapeDtypeStruct`` trees (with shardings)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    ecfg, vocab = eng.engine_cfg, eng.cfg.vocab_size
    p, c, s = _abstract(params), _abstract(cache), _abstract(state)
    arr = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt)
    out = {}
    for chunk, fn in sorted(eng._step_variants.items()):
        out[f"step_c{chunk}"] = (
            fn, (p, c, s, arr((ecfg.slots, vocab), jnp.bool_)))
    for (bucket, k), fn in sorted(eng._admits.items()):
        i32, f32 = np.int32, np.float32
        out[f"admit_p{bucket}_k{k}"] = (fn, (
            p, c, s, arr((k,), i32), arr((k, bucket), i32), arr((k,), i32),
            arr((k,), i32), arr((k,), f32), arr((k,), i32), arr((k,), f32),
            arr((k, 2), np.uint32), arr((k,), i32), arr((k,), i32),
            arr((k,), jnp.bool_), arr((k, vocab), jnp.bool_)))
    return out


def engine_plans(eng, params, cache, state, *,
                 only: Optional[Iterable[str]] = None) -> Dict[str, int]:
    """HBM bytes each program needs (see ``device.plan_bytes``)."""
    progs = engine_programs(eng, params, cache, state)
    names = list(progs) if only is None else [n for n in only if n in progs]
    return {n: device_mod.plan_bytes(progs[n][0].lower(*progs[n][1]).compile())
            for n in names}


def largest_engine_programs(eng) -> Tuple[str, str]:
    """The decode step and the widest admission: the two programs one
    of which sets the engine's peak."""
    ecfg = eng.engine_cfg
    return (f"step_c{ecfg.decode_chunk}",
            f"admit_p{eng.prompt_buckets[-1]}_k{eng.admit_batch_sizes[-1]}")
