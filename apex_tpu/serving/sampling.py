"""Token sampling — the ONE temperature/top-k/top-p implementation.

Extracted from ``gpt.generate`` so the batch-of-one decode path and the
continuous-batching engine can never diverge: :func:`draw` is the
scalar-parameter form ``generate``/the examples use, and
:func:`draw_slots` is the per-slot vectorised form the serving engine
threads through its compiled step — each slot's token is bit-identical
to what a solo ``generate`` call with that slot's parameters would draw
(the engine's continuous-batching oracle pins this token-for-token).

Filters compose in the mainstream (HF/Megatron warper) order — the
caller applies temperature first, then top-k, then nucleus mass measured
on the renormalized top-k distribution — with static shapes throughout
(the form ``lax.scan`` and jit need). :func:`filter_logits` takes
Python-int/float parameters (free when disabled); the traced variant
inside :func:`draw_slots` takes them as device scalars so per-request
values never trigger a recompile, and is value-equal to the static form
for enabled and disabled settings alike.

:func:`draw_slots` does only what some live row asks for: one
``lax.switch`` inside the compiled program, on a level reduced from the
per-slot parameters, so a step whose rows are all greedy runs no sort,
softmax, cumsum, ``fold_in`` or gumbel draw (its docstring has the
three levels). The level is data: one compiled program serves every
mix of requests.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def filter_logits(logits, top_k: int, top_p: float, mask=None):
    """Nucleus/top-k logit filtering: positions outside the top-k (by
    value), or outside the smallest set whose softmax mass reaches
    top_p, are masked to -inf. ``top_k``/``top_p`` are static Python
    values; 0 / outside (0, 1) disable. One sort; static shapes.

    ``mask`` (optional, bool ``[..., vocab]``) is the constrained-
    decoding vocab mask: False positions are removed from the candidate
    set BEFORE the top-k/top-p filters, so the filters act on the
    allowed distribution (an all-True mask is value-identical to no
    mask). The serving engine threads a per-slot mask through the
    traced variant; the host-side schema DFA
    (:mod:`apex_tpu.serving.api.constrain`) advances it per emitted
    token."""
    vocab = logits.shape[-1]
    if mask is not None:
        logits = jnp.where(mask, logits, jnp.finfo(logits.dtype).min)
    kk = top_k if 0 < top_k < vocab else 0
    pp = top_p if 0.0 < top_p < 1.0 else 0.0
    if not kk and not pp:
        return logits
    neg = jnp.finfo(logits.dtype).min
    sorted_desc = jnp.sort(logits, axis=-1)[..., ::-1]
    if kk:
        # masking the sorted tail IS the top-k filter (no second sort)
        sorted_desc = jnp.where(
            jnp.arange(vocab) < kk, sorted_desc, neg)
        thresh = sorted_desc[..., kk - 1][..., None]
    else:
        thresh = None
    if pp:
        probs = jax.nn.softmax(sorted_desc, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        # keep every position whose *preceding* cumulative mass is below
        # top_p (the first token is always kept)
        keep = jnp.concatenate(
            [jnp.ones_like(cum[..., :1], bool), cum[..., :-1] < pp],
            axis=-1)
        # threshold value = smallest kept logit
        pthresh = jnp.min(
            jnp.where(keep, sorted_desc, jnp.inf), axis=-1, keepdims=True)
        thresh = pthresh if thresh is None else jnp.maximum(thresh, pthresh)
    return jnp.where(logits < thresh, neg, logits)


def _filter_logits_traced(logits, top_k, top_p):
    """:func:`filter_logits` with *traced* scalar parameters (per-slot
    values under vmap). Value-identical to the static form: disabled
    settings map to sentinels that keep every position — ``top_k`` off →
    k = vocab (the k-threshold becomes the minimum logit, which masks
    nothing), ``top_p`` off → mass bound +inf (every position kept, the
    p-threshold likewise the minimum)."""
    vocab = logits.shape[-1]
    neg = jnp.finfo(logits.dtype).min
    kk = jnp.where((top_k > 0) & (top_k < vocab), top_k,
                   jnp.int32(vocab)).astype(jnp.int32)
    pp = jnp.where((top_p > 0.0) & (top_p < 1.0), top_p,
                   jnp.float32(jnp.inf))
    sorted_desc = jnp.sort(logits, axis=-1)[..., ::-1]
    sorted_desc = jnp.where(jnp.arange(vocab) < kk, sorted_desc, neg)
    kthresh = jnp.take(sorted_desc, kk - 1, axis=-1)[..., None]
    probs = jax.nn.softmax(sorted_desc, axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    keep = jnp.concatenate(
        [jnp.ones_like(cum[..., :1], bool), cum[..., :-1] < pp], axis=-1)
    pthresh = jnp.min(
        jnp.where(keep, sorted_desc, jnp.inf), axis=-1, keepdims=True)
    return jnp.where(logits < jnp.maximum(kthresh, pthresh), neg, logits)


def draw(logits, t, *, temperature: float = 0.0, top_k: int = 0,
         top_p: float = 1.0, key=None, mask=None):
    """One token per row of ``logits [..., vocab]`` — ``gpt.generate``'s
    draw, verbatim: greedy argmax at ``temperature <= 0``, else a
    categorical sample from the temperature-scaled, top-k/top-p-filtered
    distribution under ``fold_in(key, t)`` (``t`` is the position of the
    token the logits were computed from, so every decode step draws from
    a distinct, reproducible stream). ``mask`` (bool ``[..., vocab]``)
    restricts the draw to True positions — constrained decoding; both
    the greedy argmax and the sampled branch honour it."""
    if temperature > 0.0:
        # temperature first: top_p must see the distribution actually
        # being sampled (standard warper order)
        scaled = filter_logits(logits / temperature, top_k, top_p,
                               mask=mask)
        return jax.random.categorical(
            jax.random.fold_in(key, t), scaled, axis=-1
        ).astype(jnp.int32)
    if mask is not None:
        logits = jnp.where(mask, logits, jnp.finfo(logits.dtype).min)
    return jnp.argmax(logits, axis=-1).astype(jnp.int32)


def asks(temperature, top_k, top_p, vocab: int):
    """What rows ask of the draw, as ``(drawn, filtered)``: sampled at
    all (``temperature > 0``), and sampled through a filter that can
    drop a position (``0 < top_k < vocab`` or ``0 < top_p < 1``).
    Elementwise over device vectors (:func:`draw_slots`' levels) or
    over one request's Python scalars (the scheduler's
    ``sample.dispatches_*`` counts): one definition for both."""
    drawn = temperature > 0
    return drawn, drawn & (((top_k > 0) & (top_k < vocab))
                           | ((top_p > 0.0) & (top_p < 1.0)))


@jax.named_scope("apex.sample")
def draw_slots(logits, keys, t, temperature, top_k, top_p, masks=None,
               live=None):
    """Per-slot batched draw: ``logits [B, vocab]``; ``keys [B, 2]``
    (raw PRNG key data); ``t``/``temperature``/``top_k``/``top_p`` all
    ``[B]`` device vectors. ``masks`` (optional bool ``[B, vocab]``) is
    the per-slot constrained-decoding vocab mask — False positions are
    dropped to the dtype minimum before any draw, so an all-True row is
    bit-identical to the maskless path (the engine always passes masks;
    unconstrained slots ride all-True rows). ``live`` (optional bool
    ``[B]``, default every row) says whose token the caller will use:
    a row that is not live still gets a token, of no meaning. Returns
    ``[B] int32``.

    Live slot ``b``'s token is bit-identical to
    ``draw(logits[b:b+1], t[b], temperature=.., key=keys[b])[0]`` — the
    vmapped inner function sees a ``[1, vocab]`` row, so even the
    categorical's gumbel noise has the solo-generate shape.

    How much of the draw runs is chosen inside the compiled program,
    by ``lax.switch`` on what the live rows' parameters ask for:

    0. no live row has ``temperature > 0``: argmax of the masked
       logits — no sort, softmax, cumsum, ``fold_in`` or gumbel draw;
    1. some live row is sampled, none with a filter on (``0 < top_k <
       vocab`` or ``0 < top_p < 1``): the categorical draw over
       ``logits / temperature`` without :func:`_filter_logits_traced`,
       which is the identity for such rows (its threshold is the row's
       minimum);
    2. otherwise the filter and the draw for every row. Greedy rows of
       levels 1 and 2 take their argmax by ``where`` (their sampled
       lane divides by a safe 1.0 and is discarded): one filtered row
       makes the whole batch pay the sort.

    Each level returns for every live row what level 2 would, so the
    choice never shows in a stream. The level is reduced from vectors
    that are replicated over ``tp`` (the engine's per-slot state), so
    every device of a mesh takes the same branch."""
    vocab = logits.shape[-1]
    if masks is not None:
        logits = jnp.where(masks, logits, jnp.finfo(logits.dtype).min)
    drawn, filtered = asks(temperature, top_k, top_p, vocab)
    if live is not None:
        drawn, filtered = drawn & live, filtered & live
    level = (jnp.any(drawn).astype(jnp.int32)
             + jnp.any(filtered).astype(jnp.int32))

    def greedy(lg, *_):
        return jnp.argmax(lg, axis=-1).astype(jnp.int32)

    def sampled(filtering):
        def one(lg, key, tt, temp, kk, pp):
            safe = jnp.where(temp > 0, temp, jnp.float32(1.0))
            scaled = lg / safe
            if filtering:
                scaled = _filter_logits_traced(scaled, kk, pp)
            token = jax.random.categorical(
                jax.random.fold_in(key, tt), scaled, axis=-1)
            return jnp.where(temp > 0, token,
                             jnp.argmax(lg, axis=-1)).astype(jnp.int32)

        return lambda lg, *rest: jax.vmap(one)(lg[:, None], *rest)[:, 0]

    return jax.lax.switch(
        level, (greedy, sampled(False), sampled(True)),
        logits, keys, t, temperature, top_k, top_p)
