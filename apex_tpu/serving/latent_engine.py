"""The engine's programs and host paths under the latent mixer
(``GPTConfig.latent``): what :class:`~apex_tpu.serving.engine.Engine`
delegates to when its model attends a two-plane latent cache.

The entry points are the engine's own — ``admit_many``, ``step_async``,
``register_prefix``, ``match_prefix``, ``warmup`` — and the decode step
programs are built by the code every model shares
(``gpt.decode_steps``). What differs is admission: every prefill runs
THROUGH the paged cache (``gpt.prefill_paged``), with the number of
positions a row already holds as DATA. So one compiled program per
(tail bucket, batch size) serves

- a cold short prompt (``start = 0``),
- a question over a shared document (the document's pages mapped
  read-only into the row's block table, ``start`` its length), several
  documents in one batch,
- and, a chunk at a time through the ``fill`` programs (no first token
  drawn), a prompt longer than the largest bucket and the registration
  of a shared prefix.

A registered prefix lives in cache pages pinned by the registration;
both planes of a page (the attended rows and the index keys) share
copy-on-write under the one block table, and a hit moves no bytes. The
prefix's length is cut to whole pages: the tail's first write must not
land in a shared page.
"""

from __future__ import annotations

from typing import Any, Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from apex_tpu.models import gpt, latent
from apex_tpu.serving.pages import SINK, PagesExhausted


def check(cfg, ecfg, mesh, spec_ladder) -> None:
    """What this mixer is not served with, loudly (as experts are)."""
    refuse = lambda what, why: ValueError(
        f"{what} does not compose with the latent mixer "
        f"(GPTConfig.latent): {why}")
    latent.check(cfg)     # the model's own: layer counts, cache dtype
    if spec_ladder:
        raise refuse("speculation (spec_k / spec_ks)",
                     "the verify forward routes a batch of draft tokens "
                     "whose selections were never pinned against "
                     "sequential steps")
    if ecfg.adapter_slots:
        raise refuse("adapter_slots > 0",
                     "the mixer's projections and the routed experts "
                     "have no per-row low-rank seam")
    if ecfg.host_swap:
        raise refuse("host_swap", "the swap tier moves per-head K/V "
                     "pages; two-plane pages were never parked")
    if ecfg.page_size <= 0:
        raise refuse("page_size == 0", "admission prefills through the "
                     "block table; there is no contiguous layout")
    if mesh.shape.get("tp", 1) != 1:
        raise refuse(f"tp={mesh.shape['tp']}", "attention is whole on "
                     "every chip; the experts' exchange over chips is "
                     "ROADMAP R2")
    if ecfg.prefill_chunk and ecfg.prefill_chunk < max(
            ecfg.prompt_buckets or (ecfg.max_prompt_len,)):
        raise refuse(f"prefill_chunk={ecfg.prefill_chunk}",
                     "a chunk narrower than the widest tail bucket")


def build(eng) -> None:
    """The admission and fill programs (the step programs, ``init`` and
    ``retire`` are built by ``Engine._build`` for every model)."""
    from apex_tpu.serving.engine import _admitted_state, _draw_first

    cfg, ecfg, mesh = eng.cfg, eng.engine_cfg, eng._mesh
    pspecs, cache_spec = gpt.param_specs(cfg), gpt.cache_specs(cfg)
    state_spec = {k: P() for k in ("tok", "pos", "remaining", "done",
                                   "temp", "top_k", "top_p", "key", "eos")}
    sm = lambda f, in_specs, out_specs, donate=(): jax.jit(
        jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                      out_specs=out_specs, check_vma=False),
        donate_argnums=donate)
    scalar = P()

    def admit_local(params, cache, state, slots, tails, starts, t_lens,
                    max_tokens, temp, top_k, top_p, keys, eos, req_idx,
                    seeded, masks, tables):
        # ONE forward admits the whole [k, bucket] batch of tails, each
        # row over whatever its block table already holds
        cache, logits0 = gpt.prefill_paged(
            cfg, params, cache, tails, starts, t_lens - 1, tables)
        p_lens = starts + t_lens
        with jax.named_scope("apex.sample"):
            keys, first, first_lp = _draw_first(
                logits0, keys, seeded, req_idx, p_lens, temp, top_k, top_p,
                masks)
        new_state, hit_eos, done0 = _admitted_state(
            state, slots, first, p_lens, max_tokens, temp, top_k, top_p,
            keys, eos)
        return cache, new_state, first, first_lp, hit_eos, done0

    def fill_local(params, cache, tail, start, last, table):
        return gpt.prefill_paged(cfg, params, cache, tail, start, last,
                                 table, head=False)[0]

    eng._admits = {
        (bucket, k): sm(admit_local,
                        (pspecs, cache_spec, state_spec) + (scalar,) * 14,
                        (cache_spec, state_spec, scalar, scalar, scalar,
                         scalar), donate=(1, 2))
        for bucket in eng._buckets for k in eng._batch_sizes}
    eng._fills = {
        width: sm(fill_local, (pspecs, cache_spec) + (scalar,) * 4,
                  cache_spec, donate=(1,))
        for width in sorted({eng._buckets[-1], eng._fill_chunk})}


def program_items(eng) -> List[Tuple[str, Any]]:
    return [(f"fill_t{w}", fn) for w, fn in sorted(eng._fills.items())]


def _fill(eng, tokens: np.ndarray, start: int, row: np.ndarray,
          leave: bool) -> int:
    """Run ``tokens`` (positions ``start ..``) into the cache pages of
    the table ``row``, a chunk a dispatch; returns the position
    reached. ``leave``: stop with between one token and one widest
    bucket of them left for the caller's admission program, which draws
    the first token from them; otherwise take them all."""
    chunk, widest = eng._fill_chunk, eng._buckets[-1]
    done, n = 0, tokens.size
    while n - done > (widest if leave else 0):
        left = n - done
        width = chunk if left >= chunk or left > widest else widest
        take = min(width, left - int(leave))
        tail = np.full((1, width), eng.engine_cfg.pad_token_id, np.int32)
        tail[0, :take] = tokens[done:done + take]
        eng.cache = eng._fills[width](
            eng._params, eng.cache, tail,
            np.asarray([start + done], np.int32),
            np.asarray([take - 1], np.int32), row[None])
        done += take
    return start + done


def register_prefix(eng, tokens) -> int:
    """Prefill a shared prefix ONCE into cache pages the registration
    pins; returns its id. The prefix is cut to whole pages. Call after
    ``warmup`` (which resets the pool)."""
    ecfg, page = eng.engine_cfg, eng.engine_cfg.page_size
    if not ecfg.prefix_pool_slots:
        raise ValueError("prefix pool disabled "
                         "(EngineConfig.prefix_pool_slots == 0)")
    tokens = np.asarray(tokens, np.int32)
    if tokens.ndim != 1 or tokens.size < page:
        raise ValueError(f"a prefix needs at least one whole page of "
                         f"{page} tokens")
    if tokens.min() < 0 or tokens.max() >= eng.cfg.vocab_size:
        raise ValueError(f"prefix tokens outside vocab "
                         f"[0, {eng.cfg.vocab_size})")
    n = min(tokens.size // page, eng._max_pages - 1) * page
    toks = tokens[:n].tolist()
    for pid, held in eng._prefix_tokens.items():
        if held == toks:
            return pid
    if eng._prefix_used >= ecfg.prefix_pool_slots:
        raise ValueError(f"prefix pool full ({ecfg.prefix_pool_slots})")
    pages = eng._page_alloc.alloc(n // page)
    row = np.full((eng._max_pages,), SINK, np.int32)
    row[:len(pages)] = pages
    try:
        _fill(eng, tokens[:n], 0, row, leave=False)
    except Exception:
        eng._page_alloc.free(pages)
        eng._poisoned = True     # the fill DONATES the cache
        raise
    pid = eng._prefix_used
    eng._prefix_used += 1
    eng._prefix_pages[pid] = pages
    eng._prefix_tokens[pid] = toks
    eng._prefix_index.setdefault(tuple(toks[:page]), []).append(pid)
    eng._page_alloc.used_tokens += n
    return pid


def refill_prefixes(eng) -> None:
    """After ``rebuild_slots``: the registered prefixes' pages are
    pinned but the fresh cache is empty — compute them again."""
    for pid, pages in sorted(eng._prefix_pages.items()):
        row = np.full((eng._max_pages,), SINK, np.int32)
        row[:len(pages)] = pages
        _fill(eng, np.asarray(eng._prefix_tokens[pid], np.int32), 0, row,
              leave=False)


def match_prefix(eng, prompt):
    """The longest registered prefix ``prompt`` starts with and
    outgrows: ``(id, length)`` or None. Candidates come from a hash of
    the first page; the whole prefix is then compared."""
    page = eng.engine_cfg.page_size
    if len(prompt) <= page:
        return None
    best = None
    for pid in eng._prefix_index.get(tuple(prompt[:page]), ()):
        held = eng._prefix_tokens[pid]
        n = len(held)
        if n < len(prompt) and (best is None or n > best[1]) \
                and prompt[:n] == held:
            best = (pid, n)
    return best


def _validate(eng, a) -> Tuple[np.ndarray, int, int]:
    """``(tail tokens, prompt length, start)`` of one admission."""
    ecfg = eng.engine_cfg
    if not 0 <= a.slot < ecfg.slots:
        raise ValueError(f"slot {a.slot} outside [0, {ecfg.slots})")
    gpt._check_stop_tokens(eng.cfg, a.eos_token_id, None)
    n = len(a.prompt)
    if not 1 <= n <= ecfg.max_prompt_len:
        raise ValueError(f"prompt must hold 1..{ecfg.max_prompt_len} "
                         f"tokens, got {n}")
    if not 1 <= a.max_tokens <= ecfg.max_seq_len - n:
        raise ValueError(
            f"max_tokens {a.max_tokens} outside [1, "
            f"{ecfg.max_seq_len - n}] for a {n}-token prompt at "
            f"max_seq_len {ecfg.max_seq_len}")
    if a.adapter:
        raise ValueError("the latent mixer serves the base weights only")
    if a.allowed_tokens is not None:
        eng._check_allowed_tokens(a.allowed_tokens)
    start = 0
    if a.prefix_page is not None:
        start = a.prefix_len
        held = eng._prefix_tokens.get(a.prefix_page)
        if held is None or start != len(held) or not start < n:
            raise ValueError(
                f"prefix {a.prefix_page} of length {a.prefix_len} is "
                f"not a registered prefix this prompt outgrows")
        if list(a.prompt[:start]) != held:
            raise ValueError(
                f"prompt[:{start}] does not match the tokens of "
                f"registered prefix {a.prefix_page}")
    elif a.prefix_len:
        raise ValueError("prefix_len without prefix_page")
    tail = np.asarray(a.prompt[start:], np.int32)
    if tail.min() < 0 or tail.max() >= eng.cfg.vocab_size:
        raise ValueError(f"prompt tokens outside vocab "
                         f"[0, {eng.cfg.vocab_size})")
    return tail, n, start


def admit_many(eng, items: Sequence[Any], result_cls) -> List[Any]:
    """``Engine.admit_many`` under this mixer: prefix hits and cold
    prompts ride the same (tail bucket, k) programs, together."""
    from apex_tpu.serving.engine import _NO_EOS, _threefry_key_data

    ecfg = eng.engine_cfg
    valid = [_validate(eng, a) for a in items]
    slots_used = [a.slot for a in items]
    if len(set(slots_used)) != len(slots_used):
        raise ValueError(f"admit_many slots must be distinct, got "
                         f"{slots_used}")
    total = sum(eng.pages_needed(n, a.max_tokens, st)
                for a, (_, n, st) in zip(items, valid))
    if not eng._page_alloc.can_alloc(total):
        raise PagesExhausted(total, eng._page_alloc.free_pages)
    widest = eng._buckets[-1]
    pending, i, group = [], 0, 0
    while i < len(items):
        k = max(s for s in eng._batch_sizes if s <= len(items) - i)
        batch, rows, tails, starts = items[i:i + k], [], [], []
        for a, (tail, n, st) in zip(batch, valid[i:i + k]):
            row = eng._alloc_slot_pages(
                a.slot, n, a.max_tokens, prefix_page=a.prefix_page,
                prefix_len=st)
            if tail.size > widest:
                # the long part of a prompt goes in by chunks, alone
                at = _fill(eng, tail, st, row, leave=True)
                tail, st = tail[at - st:], at
            rows.append(row)
            tails.append(tail)
            starts.append(st)
            eng.set_slot_mask(a.slot, a.allowed_tokens)
        bucket = eng.bucket_for(max(t.size for t in tails))
        padded = np.full((k, bucket), ecfg.pad_token_id, np.int32)
        for j, t in enumerate(tails):
            padded[j, :t.size] = t
        keys = np.stack([
            _threefry_key_data(a.seed) if a.seed is not None
            else np.zeros((2,), np.uint32) for a in batch])
        req_idx = np.arange(eng._req_counter, eng._req_counter + k,
                            dtype=np.int32)
        eng._req_counter += k
        arr = lambda vals, dt: np.asarray(vals, dt)
        eng.cache, eng.state, first, first_lp, hit_eos, done = \
            eng._admits[(bucket, k)](
                eng._params, eng.cache, eng.state,
                arr([a.slot for a in batch], np.int32), padded,
                arr(starts, np.int32), arr([t.size for t in tails],
                                           np.int32),
                arr([a.max_tokens for a in batch], np.int32),
                arr([a.temperature for a in batch], np.float32),
                arr([a.top_k for a in batch], np.int32),
                arr([a.top_p for a in batch], np.float32), keys,
                arr([_NO_EOS if a.eos_token_id is None
                     else int(a.eos_token_id) for a in batch], np.int32),
                req_idx, arr([a.seed is not None for a in batch], bool),
                np.stack([eng._masks[a.slot] for a in batch]),
                np.stack(rows))
        pending.append(((first, first_lp, hit_eos, done), bucket, k,
                        group))
        i += k
        group += 1
    results = []
    for (first, first_lp, hit_eos, done), bucket, k, group in pending:
        first, first_lp = np.asarray(first), np.asarray(first_lp)
        hit_eos, done = np.asarray(hit_eos), np.asarray(done)
        for j in range(k):
            results.append(result_cls(
                int(first[j]), bool(hit_eos[j]), bool(done[j]),
                bucket=bucket, batch_size=k, group=group,
                logprob=float(first_lp[j])))
    return results


def warmup(eng) -> None:
    """Compile every admission and fill program against sink pages."""
    from apex_tpu.serving.engine import _NO_EOS

    ecfg = eng.engine_cfg
    sink = lambda k: np.full((k, eng._max_pages), SINK, np.int32)
    for (bucket, k), fn in sorted(eng._admits.items()):
        eng.cache, eng.state, first, _, _, _ = fn(
            eng._params, eng.cache, eng.state,
            np.arange(k, dtype=np.int32),
            np.full((k, bucket), ecfg.pad_token_id, np.int32),
            np.zeros((k,), np.int32), np.ones((k,), np.int32),
            np.ones((k,), np.int32), np.zeros((k,), np.float32),
            np.zeros((k,), np.int32), np.ones((k,), np.float32),
            np.zeros((k, 2), np.uint32),
            np.full((k,), _NO_EOS, np.int32), np.zeros((k,), np.int32),
            np.zeros((k,), bool),
            np.ones((k, eng.cfg.vocab_size), bool), sink(k))
        np.asarray(first)
    for width, fn in sorted(eng._fills.items()):
        eng.cache = fn(eng._params, eng.cache,
                       np.full((1, width), ecfg.pad_token_id, np.int32),
                       np.zeros((1,), np.int32), np.zeros((1,), np.int32),
                       sink(1))


def routing_counts(eng) -> Dict[str, int]:
    """The routed layers' running totals since the cache was built, as
    the programs have added them up on the device: (token, expert)
    pairs routed, pairs whose expert is held here, held experts hit and
    held experts offered (summed over layers and forwards). Reading them waits for the
    newest dispatched program."""
    routed, held, hit, offered = (
        int(x) for x in np.asarray(eng.cache["counts"]))
    return {"pairs_routed": routed, "pairs_held": held,
            "experts_hit": hit, "experts_offered": offered}
