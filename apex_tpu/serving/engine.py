"""Slot-based continuous-batching decode engine — the device loop.

XLA's static-shape world forbids vLLM's dynamic batch: instead a fixed
batch of ``B`` decode *slots* drives ONE compiled per-token program, and
requests flow through slots. All per-request state the device needs —
position, remaining token budget, done flag, eos id, temperature /
top-k / top-p / PRNG key — lives in ``[B]`` device vectors, so the
compiled programs are trace-stable across the whole serving lifetime:

- ``step``:   one ``gpt.decode_steps`` chunk — ``decode_chunk``
  fused per-token steps (each one ``gpt.decode_step`` over all B slots
  at their own positions + one per-slot
  :func:`apex_tpu.serving.sampling.draw_slots`) in ONE compiled
  ``lax.scan``, emitting ``[B, decode_chunk]`` tokens + logprobs +
  finish flags per dispatch so the per-dispatch host cost is
  paid once per chunk instead of once per token. Per-slot vocab masks
  (constrained decoding) ride every dispatch as one static bool
  argument — all-True rows are bit-identical to no mask. :meth:`Engine.step_async` exposes
  the dispatch as an in-flight :class:`StepHandle` so a pipelined
  scheduler can enqueue the NEXT chunk before fetching this one's
  tokens — serial ``device + host`` becomes ``max(device, host)``.
- ``admit``:  one program per static ``(bucket, k)`` pair — prefill a
  ``[k, bucket]`` batch of right-padded prompts in ONE forward
  (``gpt.prefill_many`` — causal attention makes the padded forward
  exact for every row's real tokens), draw k first tokens, insert k
  KV blocks into the shared cache (``gpt.cache_insert_slots``), and
  scatter k state rows at traced slot indices. The admission ladder
  (``admit_batch_sizes``, e.g. 1/2/4) lets a burst of queued requests
  drain in ~1 dispatch instead of k; the prompt-length ladder
  (``prompt_buckets``, powers of two up to ``max_prompt_len``) lets a
  short prompt pay a short padded forward instead of the full one.
- ``retire``: force a slot done (deadline expiry).

A slot's token stream is bit-identical to a solo ``gpt.generate`` run of
the same request (same key, params) — the continuous-batching oracle
test pins this token-for-token, batched admission is pinned equal to k
single admits, bucketed prefill equal to max-length prefill — and
``compiled_cache_sizes`` pins that no program recompiles after
:meth:`Engine.warmup` (which compiles every (bucket, k) variant up
front). Host-side policy (queueing, deadlines, metrics) lives in
:mod:`apex_tpu.serving.scheduler`.
"""

from __future__ import annotations

import contextlib
import dataclasses
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P

from apex_tpu.models import gpt
from apex_tpu.serving import hostswap, sampling
from apex_tpu.serving import latent_engine
from apex_tpu.serving.pages import SINK, PageAllocator, PagesExhausted
from apex_tpu.telemetry.recompile import expected_compiles
from apex_tpu.serving.resilience import (
    KIND_ERROR,
    KIND_HANG,
    KIND_NAN,
    EngineFault,
    FaultPlan,
    FaultSpec,
    InjectedFault,
)


def default_prompt_buckets(max_prompt_len: int) -> Tuple[int, ...]:
    """The static padded-prefill length ladder: powers of two from 8 up
    to (and always including) ``max_prompt_len``. The floor of 8 keeps
    the compiled-program count small — below it the padded forward is
    already tiny and another bucket would buy nothing but a compile."""
    out: List[int] = []
    v = 8
    while v < max_prompt_len:
        out.append(v)
        v *= 2
    out.append(max_prompt_len)
    return tuple(out)


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """Static engine geometry — everything that shapes the compiled
    programs. ``max_prompt_len`` caps prompt length (admission pads to
    the smallest bucket that fits, see ``prompt_buckets``);
    ``max_seq_len`` is the per-slot KV horizon (prompt + generated
    tokens, ``<= cfg.seq_len`` for the position table)."""

    slots: int = 4
    max_prompt_len: int = 64
    max_seq_len: int = 128
    pad_token_id: int = 0
    #: tokens decoded per compiled ``step`` dispatch
    #: (``gpt.decode_steps``): raising it amortises the per-dispatch
    #: host cost over n tokens at the cost of admission latency —
    #: queued requests wait for the in-flight chunk, and a slot that
    #: finishes mid-chunk rides out the rest emitting pad. Token
    #: streams are bit-identical at every setting (the chunk-parity
    #: test pins chunk=8 against chunk=1 against solo generate).
    decode_chunk: int = 1
    #: static ladder of padded prefill lengths; admission picks the
    #: smallest bucket >= the (batch-max) prompt length, so a 4-token
    #: prompt pays an 8-wide padded forward instead of the full
    #: ``max_prompt_len`` one. None = :func:`default_prompt_buckets`
    #: (powers of two up to ``max_prompt_len``). Must be strictly
    #: increasing and end at ``max_prompt_len``. Each (bucket, k) pair
    #: is one compiled admission program — ``Engine.warmup()`` compiles
    #: them all so steady state never traces.
    prompt_buckets: Optional[Tuple[int, ...]] = None
    #: static ladder of admission batch sizes k: ``admit_many`` splits
    #: a burst of queued requests into ladder-sized groups (largest
    #: first), each group ONE prefill forward + ONE dispatch. None =
    #: (1, 2, 4) capped at ``slots``. Must be strictly increasing and
    #: start at 1 (any group count decomposes).
    admit_batch_sizes: Optional[Tuple[int, ...]] = None
    #: speculative decoding: draft tokens per wave (0 disables — no
    #: spec step program, no history buffer; the historical engine).
    #: With ``spec_k > 0`` the engine compiles a SECOND step variant
    #: (``gpt.decode_steps_spec``): each of the chunk's
    #: ``decode_chunk`` scan iterations drafts ``spec_k`` candidates
    #: from the slot's token history (device-side n-gram suffix match
    #: — no second model), verifies all ``spec_k + 1`` positions in
    #: ONE batched target forward, and accept-prefix-selects — a chunk
    #: emits up to ``decode_chunk * (spec_k + 1)`` tokens per slot for
    #: roughly one plain chunk's weight traffic when drafts hit.
    #: Emitted streams are BIT-IDENTICAL to the plain path (greedy and
    #: sampled — verification is token-matching against the target's
    #: own draws at the same key fold points), so the scheduler's
    #: payoff gate flips between the two pre-warmed variants freely.
    spec_k: int = 0
    #: token-history ring width per slot — the n-gram drafter's match
    #: window (newest-last, -1 sentinel padding; seeded from the
    #: prompt tail at admission). Only meaningful with ``spec_k > 0``.
    spec_hist: int = 32
    #: shared-prefix pool pages (0 disables — no extra compiled
    #: programs, no pool buffer). A common prompt prefix
    #: (:meth:`Engine.register_prefix` — a system-prompt template) is
    #: prefilled ONCE into a pool page; a request whose prompt starts
    #: with it (:meth:`Engine.match_prefix`, hash-keyed at
    #: bucket-aligned split points) admits by COPYING the pooled K/V
    #: into its slot via a compiled gather and prefilling only the
    #: tail — admission cost drops from the full prompt bucket to the
    #: tail bucket. One compiled program per (prefix bucket, tail
    #: bucket) pair plus one pool-insert per prefix bucket, all
    #: compiled by :meth:`Engine.warmup`.
    prefix_pool_slots: int = 0
    #: paged KV cache: > 0 switches the slot cache from one contiguous
    #: ``[max_seq_len]``-horizon stripe per slot to a GLOBAL pool of
    #: ``page_size``-token pages plus one ``[max_pages] int32`` block
    #: table row per slot (``max_pages = ceil(max_seq_len /
    #: page_size)`` — config-derived, never request-derived: tables
    #: are DATA in the compiled programs, so one program serves every
    #: table content). A 12-token request then pins
    #: ``ceil((12 + max_tokens) / page_size)`` pages instead of a full
    #: ``max_seq_len`` stripe — the fragmentation-free capacity play —
    #: and prefix-pool hits share the prefix's pages copy-on-write
    #: (refcounted; the prefix region is read-only by construction, so
    #: the "first write" that would allocate is the admission's own
    #: private tail/decode pages). 0 = the historical contiguous
    #: layout. Emitted streams are bit-identical either way (the paged
    #: == contiguous oracle pins it).
    page_size: int = 0
    #: pages in the global pool (paged mode only). 0 = auto-size to
    #: ``slots * max_pages + 1`` — every slot can hold a worst-case
    #: request, plus the reserved sink page 0 (freed slots' table rows
    #: redirect there so their frozen decode lanes write garbage into
    #: garbage). Set lower to oversubscribe HBM against a mixed-length
    #: workload; admission then backpressures through
    #: :class:`~apex_tpu.serving.pages.PagesExhausted` when the pool
    #: runs dry (the scheduler keeps the queue and sheds per policy).
    num_pages: int = 0
    #: chunked prefill: > 0 admits prompts LONGER than this in
    #: ``prefill_chunk``-token slices — chunk 0 through a bucket-sized
    #: cold prefill, later chunks through ``gpt.prefill_extend`` over
    #: the already-ingested prefix — with the scheduler free to run
    #: decode waves between chunk dispatches, so a long-prompt
    #: admission no longer stalls every other stream's TTFT for one
    #: monolithic forward. Must be a prompt bucket dividing
    #: ``max_prompt_len``. One compiled extend variant per chunk index
    #: (``max_prompt_len / prefill_chunk - 1`` of them), all warmed.
    #: Streams are bit-identical to a monolithic admission whenever
    #: cold prefill runs the materialised-scores attention (every
    #: off-TPU config — the ``gpt.prefill_extend`` parity contract).
    #: 0 disables.
    prefill_chunk: int = 0
    #: static ladder of decode-chunk STEP VARIANTS: each value is one
    #: compiled step program (spec variants cross with ``spec_ks``),
    #: all compiled by :meth:`Engine.warmup` and tracked per variant,
    #: so a self-tuning scheduler (``serving.tuner``) switches chunk
    #: size per dispatch with the recompile guard armed. Must be
    #: strictly increasing and contain ``decode_chunk`` (the base
    #: operating point). None = ``(decode_chunk,)`` — the historical
    #: single-variant engine. Token streams are bit-identical at every
    #: rung (the chunk-parity oracle).
    decode_chunks: Optional[Tuple[int, ...]] = None
    #: static ladder of speculative draft widths: each non-zero value
    #: is one compiled spec step variant PER decode-chunk rung (the
    #: tuner's ``spec_k=0`` rung is the plain variant, not a program).
    #: Must be strictly increasing, all >= 1, and contain ``spec_k``
    #: when ``spec_k > 0``. None = ``(spec_k,)`` if ``spec_k > 0``
    #: else no speculation. ``spec_ks`` with ``spec_k == 0`` is valid:
    #: the engine carries the drafter machinery and warm spec variants
    #: but dispatches plain until a tuner asks otherwise.
    spec_ks: Optional[Tuple[int, ...]] = None
    #: batched multi-LoRA adapter pool rows (0 disables — no pool
    #: buffer, no extra program arguments; the historical engine).
    #: With ``adapter_slots > 0`` every dense seam of every forward
    #: (prefill / extend / decode / verify) gains a per-slot low-rank
    #: delta gathered from a static ``[n_adapters, r, ...]`` pool by a
    #: ``[B] int32`` adapter-id table — ids are DATA (the vocab-mask /
    #: block-table pattern), so ONE compiled program serves every
    #: tenant mix and the recompile guard stays flat across adapter
    #: registration and admission churn. Row 0 is the PINNED all-zero
    #: adapter: base traffic decodes numerically exact (the delta is
    #: an exact zero), tenants register into rows 1..n-1 via
    #: :meth:`Engine.register_adapter` (after :meth:`Engine.warmup`,
    #: the prefix-pool lifecycle). The pool is never donated, so it
    #: survives :meth:`Engine.rebuild_slots` and fault replay serves
    #: the same weights.
    adapter_slots: int = 0
    #: low-rank adapter rank r — compile-time static (ADAPTER-STATIC:
    #: every registered adapter shares it; a per-tenant rank would be
    #: a shape ladder and recompile per tenant).
    adapter_rank: int = 8
    #: LoRA scaling numerator: deltas apply as ``(alpha / r) * B A x``.
    adapter_alpha: float = 16.0
    #: host-RAM page tier under the device pool (paged mode only —
    #: requires ``page_size > 0``). True compiles the swap programs
    #: (``pages_out``/``pages_in`` gather/scatter over the page dim,
    #: one variant per power-of-two swap-batch rung, all warmed) and
    #: arms :meth:`Engine.park_slot` / :meth:`Engine.resume_slot`: a
    #: paused conversation's private pages move to host buffers in
    #: storage form (bit-exact round trip, quantized planes included)
    #: so active streams keep every HBM page, and the scheduler can
    #: oversubscribe the pool far past ``num_pages``. Also lifts the
    #: ``register_adapter`` hard cap: cold adapter rows spill to host
    #: under the same LRU and page back in on demand (ids stay DATA —
    #: no recompile). False = the historical hard-capped engine.
    host_swap: bool = False
    #: host-tier capacity in PAGES (0 = unbounded): parking past it
    #: LRU-drops the coldest payloads, whose conversations fall back
    #: to recompute-resume from the emitted-prefix snapshot.
    host_swap_pages: int = 0
    #: how a parked conversation comes back: ``"swap"`` scatters the
    #: host payload into freshly allocated pages and restores the
    #: slot's state row (PRNG key included — bit-identical
    #: continuation); ``"recompute"`` drops the payload and replays
    #: prompt + emitted prefix through the fault-replay machinery
    #: (also bit-identical — same seed, suppressed re-emission);
    #: ``"auto"`` prices the two per resume from measured swap-in cost
    #: vs replay cost and picks the cheaper. Both paths are pinned
    #: equal, so the policy is pure performance.
    resume_policy: str = "auto"


#: eos sentinel in the per-slot eos vector: no stop token for this slot
#: (single-sourced from the decode loop that interprets it)
_NO_EOS = gpt._NO_EOS_SENTINEL


@dataclasses.dataclass(frozen=True)
class Admission:
    """One admission request — the argument row of
    :meth:`Engine.admit_many` (``Engine.admit``'s keyword surface as
    data, so a batch of them can ride one dispatch).

    ``allowed_tokens`` (optional) is the constrained-decoding vocab
    whitelist for the FIRST token — the schema DFA's initial allowed
    set; it also seeds the slot's per-step mask
    (:meth:`Engine.set_slot_mask` advances it between chunks). ``None``
    = unconstrained (and resets any stale mask the slot carried).

    ``adapter`` selects the request's LoRA adapter row (0 = the pinned
    base adapter; rows >= 1 come from
    :meth:`Engine.register_adapter`). It rides the admission prefill
    AND the slot's decode id-table entry, so every token of the
    request — prefill, decode, speculative verify — sees the same
    weights.

    ``prefix_page``/``prefix_len`` (optional) ride a prefix-pool hit
    (:meth:`Engine.match_prefix`): ``prompt`` is still the FULL token
    sequence, but its first ``prefix_len`` tokens (which must equal the
    registered prefix — validated) are copied from pool page
    ``prefix_page`` instead of prefilled, and only the tail runs a
    forward. Streams are bit-identical to a cold admission of the same
    prompt whenever cold prefill runs the materialised-scores
    attention (every off-TPU config; flash prefill differs at the
    reduction-order ulp level — see ``gpt.prefill_extend``)."""

    slot: int
    prompt: Any
    max_tokens: int
    temperature: float = 0.0
    top_k: int = 0
    top_p: float = 1.0
    seed: Optional[int] = None
    eos_token_id: Optional[int] = None
    allowed_tokens: Optional[Sequence[int]] = None
    prefix_page: Optional[int] = None
    prefix_len: int = 0
    adapter: int = 0


@dataclasses.dataclass(frozen=True)
class AdmitResult:
    """Per-request outcome of :meth:`Engine.admit_many`. ``finished``
    is True when the request is already complete after its first token
    (eos, or a budget of 1). ``logprob`` is the model's log-probability
    of the first token (log-softmax of the raw prefill logits).
    ``bucket``/``batch_size``/``group`` record which compiled admission
    variant served it and which dispatch group of the call it rode —
    the scheduler's admission telemetry."""

    first_token: int
    hit_eos: bool
    finished: bool
    bucket: int
    batch_size: int
    group: int
    logprob: float = 0.0


def _pad_span(block, span: int):
    """Zero-pad a cache block pytree (``[l, 2, k, hl, T(, d)]`` leaves)
    to ``span`` columns on the horizon dim (4) — the paged insert's
    page-alignment shim: ``gpt.cache_insert_pages`` writes whole pages,
    and the pad columns land either in the slot's own not-yet-decoded
    cells or in the sink page (masked garbage both ways)."""
    def f(x):
        pad = span - x.shape[4]
        if pad <= 0:
            return x
        w = [(0, 0)] * x.ndim
        w[4] = (0, pad)
        return jnp.pad(x, w)

    return jax.tree.map(f, block)


class ChunkedAdmission:
    """Host progress of one chunked-prefill admission
    (``EngineConfig.prefill_chunk``): created by
    :meth:`Engine.admit_chunked_start` (which dispatches chunk 0),
    advanced one chunk-forward per :meth:`Engine.admit_chunked_step`
    call — the scheduler interleaves decode waves between calls — and
    finished by the same method returning the :class:`AdmitResult`.
    ``chunks_total`` counts the prefill forwards (the admission's
    device dispatches are ``chunks_total + 1`` including the finish)."""

    __slots__ = ("admission", "prompt", "p_len", "chunks_total",
                 "next_chunk", "slot", "_logits")

    def __init__(self, admission: Admission, prompt: np.ndarray,
                 p_len: int, chunks_total: int):
        self.admission = admission
        self.prompt = prompt
        self.p_len = p_len
        self.chunks_total = chunks_total
        self.next_chunk = 1          # chunk 0 dispatched at start
        self.slot = admission.slot
        self._logits = None          # the final chunk's device logits

    @property
    def done_prefilling(self) -> bool:
        """True once every prefill chunk is dispatched (the next
        :meth:`Engine.admit_chunked_step` call runs the finish)."""
        return self.next_chunk >= self.chunks_total


def _threefry_key_data(seed: int) -> np.ndarray:
    """``jax.random.PRNGKey(seed)``'s raw data, computed host-side with
    numpy for the common domain (non-negative int32 seeds — the
    threefry key is just the packed seed, zero hi word, no hashing;
    pinned bit-identical against the real PRNGKey in the tests).
    Avoids dispatching + FETCHING one tiny device program per seeded
    request on the admission hot path — each fetch is a device round
    trip, which would cancel the k→1 dispatch amortization batched
    admission exists for. Seeds outside
    that domain (negative, or > 31 bits — whose truncation depends on
    the runtime's x64 mode) take the real PRNGKey, paying the round
    trip to stay bit-stable."""
    if 0 <= seed < 2**31:
        return np.asarray([0, seed], np.uint32)
    return np.asarray(jax.random.PRNGKey(seed), np.uint32)


_NO_SECTION = contextlib.nullcontext()


class StepHandle:
    """One in-flight decode chunk: the ``[B, n]`` token/logprob/
    finished device futures a :meth:`Engine.step_async` dispatch
    returned. ``fetch()`` copies them to the host, which waits for the
    chunk; it caches, so fetching twice costs one transfer.

    Fault injection (:mod:`apex_tpu.serving.resilience`): a plan's
    ``fetch`` seam is consumed on the FIRST fetch only, and a
    ``dispatch``-seam hang spec rides the handle to be applied where a
    hung dispatch is observed — at the fetch."""

    __slots__ = ("_emit", "_logprobs", "_finished", "_out", "_plan",
                 "_hang", "_on_poison", "_valid_dev", "valid", "spec_k",
                 "ncols")

    def __init__(self, emit, logprobs, finished, *,
                 plan: Optional[FaultPlan] = None,
                 hang: Optional[FaultSpec] = None,
                 on_poison: Optional[Any] = None,
                 valid=None, spec_k: int = 0, ncols: int = 0):
        self._emit = emit
        self._logprobs = logprobs
        self._finished = finished
        self._out: Optional[
            Tuple[np.ndarray, np.ndarray, np.ndarray]] = None
        self._plan = plan
        self._hang = hang
        self._on_poison = on_poison
        #: speculative chunks only: the ``[B, ncols]`` bool plane
        #: marking which columns carry REAL emissions (rejected draft
        #: lanes and done slots emit pad under False). None for plain
        #: chunks (where every live slot's column is real) — and until
        #: :meth:`fetch` lands the device future.
        self._valid_dev = valid
        self.valid: Optional[np.ndarray] = None
        #: draft tokens per wave of the chunk this handle carries (0 =
        #: plain chunk)
        self.spec_k = spec_k
        #: token columns this chunk emits per slot — ``decode_chunk``
        #: for plain chunks, ``decode_chunk * (spec_k + 1)`` for
        #: speculative ones (the scheduler's in-flight budget guard
        #: prices chunks by this)
        self.ncols = ncols

    @property
    def spec(self) -> bool:
        """True when this handle carries a speculative chunk."""
        return self.spec_k > 0

    def fetch(self, section: Optional[Callable[[str], Any]] = None
              ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        """Block until the chunk lands; returns ``(tokens [B, n],
        logprobs [B, n], finished [B, n])`` as host arrays.

        ``section`` (``name -> context manager``, e.g. a span
        recorder's ``section``) times the two parts of a fetch:
        ``engine.fetch.wait``, the copy of the tokens, which waits for
        the device to finish the chunk, and ``engine.fetch.copy``, the
        copies after it (logprobs, finished, and a speculative chunk's
        valid columns)."""
        if self._out is not None:
            return self._out
        spec = self._plan.take("fetch") if self._plan is not None else None
        for s in (self._hang, spec):
            if s is not None and s.kind == KIND_HANG:
                self._plan.hang_fn(s.hang_s)
        if spec is not None and spec.kind == KIND_ERROR:
            if self._on_poison is not None:
                self._on_poison()
            raise InjectedFault(
                f"injected device error at fetch: {spec.describe()}",
                point="fetch", spec=spec)
        wait, copy = ((_NO_SECTION, _NO_SECTION) if section is None else
                      (section("engine.fetch.wait"),
                       section("engine.fetch.copy")))
        with wait:
            tokens = np.asarray(self._emit)
        with copy:
            logprobs = np.asarray(self._logprobs)
            finished = np.asarray(self._finished)
            if self._valid_dev is not None:
                self.valid = np.asarray(self._valid_dev)
        if spec is not None and spec.kind == KIND_NAN:
            # what a NaN logit batch looks like by the time the host
            # sees it: garbage token ids in the poisoned lanes
            tokens = tokens.copy()
            rows = [s for s in spec.slots if 0 <= s < tokens.shape[0]]
            tokens[rows, :] = spec.token
        self._out = (tokens, logprobs, finished)
        return self._out


def _draw_first(logits0, keys, seeded, req_idx, p_lens, temp, top_k,
                top_p, masks):
    """The first token of every admitted row, for every admission
    program: ``(keys, first, first_lp)``. Unseeded rows fold the
    monotonic request counter into the zero base key ON DEVICE (no
    host-side compile to trip a recompile guard); seeded rows keep
    their host key bit-for-bit. The k-row ``draw_slots`` call vmaps per
    row over a ``[1, vocab]`` lane — each row IS the solo-generate
    first draw (same gumbel shape, same fold index ``p_len - 1``)."""
    base = jnp.zeros((2,), jnp.uint32)
    folded = jax.vmap(lambda i: jax.random.fold_in(base, i))(req_idx)
    keys = jnp.where(seeded[:, None], keys, folded)
    first = sampling.draw_slots(logits0, keys, p_lens - 1, temp, top_k,
                                top_p, masks=masks)
    first_lp = jnp.take_along_axis(
        jax.nn.log_softmax(logits0, axis=-1), first[:, None], axis=1)[:, 0]
    return keys, first, first_lp


def _admitted_state(state, slots, first, p_lens, max_tokens, temp, top_k,
                    top_p, keys, eos, hist0=None):
    """The per-slot state after an admission wrote rows ``slots``:
    ``(new_state, hit_eos, done0)``. ``hist0`` (speculation) seeds the
    drafter's ring: the prompt tail (packed host-side — the host knows
    the full prompt) plus the first token just drawn."""
    hit_eos = (eos >= 0) & (first == eos)
    done0 = hit_eos | (max_tokens <= 1)
    new_state = {
        "tok": state["tok"].at[slots].set(first),
        "pos": state["pos"].at[slots].set(p_lens),
        "remaining": state["remaining"].at[slots].set(max_tokens - 1),
        "done": state["done"].at[slots].set(done0),
        "temp": state["temp"].at[slots].set(temp),
        "top_k": state["top_k"].at[slots].set(top_k),
        "top_p": state["top_p"].at[slots].set(top_p),
        "key": state["key"].at[slots].set(keys),
        "eos": state["eos"].at[slots].set(eos),
    }
    if hist0 is not None:
        new_state["hist"] = state["hist"].at[slots].set(
            jnp.concatenate([hist0, first[:, None]], axis=1))
    return new_state, hit_eos, done0


def _held_weights(cfg, params, mesh):
    """``params`` as the engine holds them: every leaf whose dtype
    :func:`gpt.cast_weights` changes is cast once, here, shard by shard
    on ``mesh`` under ``gpt.param_specs`` — the operand every forward
    would otherwise make again from the same bits; every other leaf is
    the caller's own array, uncopied. An abstract leaf
    (``ShapeDtypeStruct``) becomes one of the new dtype with its
    sharding."""
    leaves, tree = jax.tree.flatten(params)
    want = tree.flatten_up_to(
        jax.eval_shape(lambda p: gpt.cast_weights(cfg, p), params))
    specs = tree.flatten_up_to(gpt.param_specs(cfg))
    live = []
    for i, (x, w) in enumerate(zip(leaves, want)):
        if x.dtype == w.dtype:
            continue
        if isinstance(x, jax.ShapeDtypeStruct):
            leaves[i] = jax.ShapeDtypeStruct(x.shape, w.dtype,
                                             sharding=x.sharding)
        else:
            live.append(i)
    if live:
        in_specs = [specs[i] for i in live]
        cast = jax.jit(jax.shard_map(
            lambda xs: [x.astype(cfg.compute_dtype) for x in xs],
            mesh=mesh, in_specs=(in_specs,), out_specs=in_specs))
        weights = [leaves[i] for i in live]
        for i, x in zip(live, cast(weights)):
            leaves[i] = x
    return tree.unflatten(leaves)


class Engine:
    """Compiled slot engine over ``mesh`` (tp sharding like the rest of
    the decode path; dp/pp axes must be 1 — decode state is replicated).

    The class owns the device buffers (cache + slot-state vectors) and
    exposes host-facing ``admit`` / ``admit_many`` / ``step`` /
    ``step_async`` / ``retire``; each call fetches only the tiny
    per-slot outputs (``step_async`` defers even that).

    The layer stacks are held as every forward computes with them: the
    leaves a forward casts to ``cfg.compute_dtype`` (matmul weights and
    biases, :func:`gpt.cast_weights`) are cast once at construction and
    the caller's fp32 leaves are not kept; LayerNorm, embedding tables
    and router leaves, and any leaf already in the compute dtype, are
    the caller's own arrays. The caller's tree is not modified.
    """

    def __init__(self, cfg: "gpt.GPTConfig", params, mesh,
                 engine_cfg: Optional[EngineConfig] = None,
                 fault_plan: Optional[FaultPlan] = None, **overrides):
        ecfg = engine_cfg or EngineConfig(**overrides)
        if engine_cfg is not None and overrides:
            raise ValueError("pass engine_cfg or field overrides, not both")
        if ecfg.slots < 1:
            raise ValueError("need at least one slot")
        if not 1 <= ecfg.max_prompt_len <= ecfg.max_seq_len:
            raise ValueError(
                f"max_prompt_len {ecfg.max_prompt_len} must be in "
                f"[1, max_seq_len={ecfg.max_seq_len}]")
        if ecfg.max_seq_len > cfg.seq_len:
            raise ValueError(
                f"max_seq_len {ecfg.max_seq_len} exceeds the position "
                f"table (cfg.seq_len={cfg.seq_len})")
        if ecfg.decode_chunk < 1:
            raise ValueError(
                f"decode_chunk {ecfg.decode_chunk} must be >= 1")
        if ecfg.spec_k < 0:
            raise ValueError(f"spec_k {ecfg.spec_k} must be >= 0")
        self._chunk_ladder = self._resolve_chunk_ladder(ecfg)
        self._spec_ladder = self._resolve_spec_ladder(ecfg)
        if self._spec_ladder and ecfg.spec_hist < 2:
            raise ValueError(
                f"spec_hist {ecfg.spec_hist} must be >= 2 with "
                f"speculation (the drafter matches a 2-token suffix)")
        if self._spec_ladder and cfg.num_experts:
            raise ValueError(
                "speculation does not compose with num_experts > 0: the "
                "batched verify forward routes a different token count "
                "than sequential steps, so MoE expert capacity breaks "
                "spec == plain bit-parity (see gpt.decode_verify)")
        gpt._check_stop_tokens(cfg, None, ecfg.pad_token_id)
        #: the model's mixer attends a two-plane latent cache: admission
        #: prefills through the block table (serving/latent_engine.py)
        self._latent = cfg.latent is not None
        if self._latent:
            latent_engine.check(cfg, ecfg, mesh, self._spec_ladder)
        for axis in ("dp", "pp", "cp", "ep"):
            if axis in mesh.shape and mesh.shape[axis] != 1:
                raise ValueError(
                    f"serving engine shards over tp only; mesh has "
                    f"{axis}={mesh.shape[axis]}")
        # -- batched multi-LoRA geometry (all compile-time static:
        # pool rows and rank shape the programs, ids are data —
        # ADAPTER-STATIC) ------------------------------------------------
        if ecfg.adapter_slots < 0:
            raise ValueError(
                f"adapter_slots {ecfg.adapter_slots} must be >= 0")
        self._lora = ecfg.adapter_slots > 0
        if self._lora:
            if ecfg.adapter_rank < 1:
                raise ValueError(
                    f"adapter_rank {ecfg.adapter_rank} must be >= 1")
            if cfg.num_experts:
                raise ValueError(
                    "adapter_slots > 0 does not compose with "
                    "num_experts > 0 (the expert FFN has no per-row "
                    "dense seam to delta — see gpt.init_lora_pool)")
        self._lora_scale = (ecfg.adapter_alpha / ecfg.adapter_rank
                            if self._lora else 0.0)
        self._buckets = self._resolve_buckets(ecfg, tails=self._latent)
        self._batch_sizes = self._resolve_batch_sizes(ecfg)
        if ecfg.prefix_pool_slots > 0 and cfg.num_experts:
            raise ValueError(
                "prefix_pool_slots > 0 does not compose with "
                "num_experts > 0: MoE expert capacity depends on the "
                "routed token count, so a tail-only extend forward "
                "drops different tokens than the cold full-prompt "
                "prefill and prefix-hit streams would silently "
                "diverge (see gpt.prefill_extend)")
        # (the dropless routed layer routes row by row: a tail-only or
        # chunked forward computes what the whole prompt's would, so
        # the latent mixer takes both; its prefixes have any whole
        # number of pages, not a bucket's length)
        self._prefix_splits, self._extend_variants = (
            ((), ()) if self._latent else
            self._resolve_prefix_variants(ecfg, self._buckets))
        # -- paged KV cache geometry (all config-derived constants:
        # tables are data, never shapes — PAGE-TABLE-STATIC) ------------
        if ecfg.page_size < 0 or ecfg.num_pages < 0:
            raise ValueError(
                f"page_size {ecfg.page_size} / num_pages "
                f"{ecfg.num_pages} must be >= 0")
        self._paged = ecfg.page_size > 0
        if not self._paged and ecfg.num_pages:
            raise ValueError(
                "num_pages without page_size — the pool geometry only "
                "exists in paged mode")
        self._max_pages = 0
        self._num_pages = 0
        if self._paged:
            self._max_pages = -(-ecfg.max_seq_len // ecfg.page_size)
            self._num_pages = (ecfg.num_pages
                               or ecfg.slots * self._max_pages + 1)
            if self._num_pages < self._max_pages + 1:
                raise ValueError(
                    f"num_pages {self._num_pages} cannot hold one "
                    f"worst-case request ({self._max_pages} pages) "
                    f"plus the sink page")
            if self._prefix_splits:
                # copy-on-write sharing maps whole pages: only
                # page-aligned split points can share (the tail insert
                # starts at the split, and a mid-page split would make
                # a shared page writable)
                splits = tuple(s for s in self._prefix_splits
                               if s % ecfg.page_size == 0)
                if not splits:
                    raise ValueError(
                        f"prefix_pool_slots={ecfg.prefix_pool_slots} "
                        f"with page_size={ecfg.page_size}: no split "
                        f"point in {self._prefix_splits} is "
                        f"page-aligned — pick a page_size dividing a "
                        f"prompt bucket")
                self._extend_variants = tuple(
                    (ps, tb) for ps, tb in self._extend_variants
                    if ps in splits)
                self._prefix_splits = splits
        # -- host-swap tier geometry (rungs config-derived from the
        # worst-case private page count — HOST-TIER-STATIC) -------------
        if ecfg.resume_policy not in ("auto", "swap", "recompute"):
            raise ValueError(
                f"resume_policy {ecfg.resume_policy!r} must be one of "
                f"'auto' | 'swap' | 'recompute'")
        if ecfg.host_swap_pages < 0:
            raise ValueError(
                f"host_swap_pages {ecfg.host_swap_pages} must be >= 0")
        self._host_swap = bool(ecfg.host_swap)
        if self._host_swap and not self._paged:
            raise ValueError(
                "host_swap requires the paged KV cache (page_size > 0) "
                "— the swap tier moves pages, not contiguous stripes")
        if ecfg.host_swap_pages and not self._host_swap:
            raise ValueError(
                "host_swap_pages without host_swap — the host tier "
                "only exists with host_swap=True")
        self._swap_rungs: Tuple[int, ...] = ()
        if self._host_swap:
            self._swap_rungs = hostswap.swap_rungs(self._max_pages)
        # -- chunked prefill geometry -----------------------------------
        if ecfg.prefill_chunk < 0:
            raise ValueError(
                f"prefill_chunk {ecfg.prefill_chunk} must be >= 0")
        self._chunk_size = 0 if self._latent else ecfg.prefill_chunk
        #: latent mixer: tokens a fill dispatch takes of a long prompt
        #: or of a prefix being registered
        self._fill_chunk = ecfg.prefill_chunk or self._buckets[-1]
        if self._chunk_size:
            if cfg.num_experts:
                raise ValueError(
                    "prefill_chunk > 0 does not compose with "
                    "num_experts > 0 (chunked admission rides "
                    "gpt.prefill_extend, which MoE expert capacity "
                    "breaks — see its docstring)")
            if self._chunk_size not in self._buckets:
                raise ValueError(
                    f"prefill_chunk {self._chunk_size} must be one of "
                    f"the prompt buckets {self._buckets} (chunk 0 is a "
                    f"bucket-sized cold prefill)")
            if self._chunk_size >= ecfg.max_prompt_len \
                    or ecfg.max_prompt_len % self._chunk_size:
                raise ValueError(
                    f"prefill_chunk {self._chunk_size} must divide and "
                    f"be smaller than max_prompt_len "
                    f"{ecfg.max_prompt_len} (the chunk ladder is "
                    f"static)")
        self.cfg = cfg
        self.engine_cfg = ecfg
        #: positions in one chunk of the decode read kernel's sweep of
        #: a slot's horizon (a page, under the paged cache)
        self.read_chunk = ecfg.page_size or gpt.decode_read_chunk(
            cfg, ecfg.max_seq_len)
        self._mesh = mesh
        self._sentinel = None  # lazily via recompile_sentinel()
        #: monotonic admission counter — folded into the default PRNG
        #: key of unseeded requests so concurrent sampled requests never
        #: share a stream (they all drew from the zero key before)
        self._req_counter = 0
        self._warmed = False
        #: chaos harness (resilience.FaultPlan): consulted at the
        #: admit/dispatch/fetch seams; None in production
        self.fault_plan = fault_plan
        self._warming = False   # warmup must never consume plan faults
        #: True after a fault invalidated the donated cache/state —
        #: every device call refuses until rebuild_slots()
        self._poisoned = False
        #: per-slot constrained-decoding vocab masks, host mirror —
        #: all-True rows (the unconstrained default) are bit-identical
        #: to no mask in the draw. The device copy is cached and only
        #: re-uploaded when a row changes (set_slot_mask / admission),
        #: so the steady unconstrained path pays one stale-pointer
        #: check per dispatch, not a [B, vocab] transfer.
        self._masks = np.ones((ecfg.slots, cfg.vocab_size), bool)
        self._masks_dev: Optional[Any] = None
        #: prefix-pool host registry: bucket-aligned key (exact token
        #: tuple) → (page, split); pages hold the registered tokens for
        #: admission-time validation. Device pool built in _build.
        self._prefix_index: Dict[Tuple[int, ...], Tuple[int, int]] = {}
        self._prefix_tokens: Dict[int, Tuple[int, ...]] = {}
        self._prefix_used = 0
        self.pool: Optional[Any] = None
        #: paged-mode host state: the page allocator, the [B, max_pages]
        #: block-table host mirror (device copy cached like the masks —
        #: re-uploaded only when a row changes), per-slot page
        #: bookkeeping, and the registered prefixes' pinned cache pages
        self._page_alloc: Optional[PageAllocator] = None
        self._tables: Optional[np.ndarray] = None
        self._tables_dev: Optional[Any] = None
        self._slot_pages: Dict[int, Tuple[List[int], List[int], int]] = {}
        self._prefix_pages: Dict[int, List[int]] = {}
        if self._paged:
            self._page_alloc = PageAllocator(self._num_pages,
                                             ecfg.page_size)
            self._tables = np.full((ecfg.slots, self._max_pages), SINK,
                                   np.int32)
        #: the single in-progress chunked-prefill admission (None
        #: between chunked admissions; the engine serializes them — the
        #: scratch buffer holds one prompt)
        self._chunked: Optional[ChunkedAdmission] = None
        #: multi-LoRA host state: the per-slot adapter-id table mirror
        #: (device copy cached like the masks — re-uploaded only when
        #: a row changes) and the adapter registry (name → row,
        #: row → metadata incl. the registration seed the post-mortem
        #: replay rebuilds adapters from)
        self._adapter_ids = np.zeros((ecfg.slots,), np.int32)
        self._aids_dev: Optional[Any] = None
        self._adapter_names: Dict[str, int] = {}
        self._adapter_meta: Dict[int, Dict[str, Any]] = {}
        self._adapter_used = 1 if self._lora else 0  # row 0 pinned
        self.adapters: Optional[Any] = None
        #: host-swap tier state: the parked-conversation LRU store
        #: (opaque payloads: storage-form page blocks + the slot's
        #: state row + table/mask/adapter mirrors) and the measured
        #: per-page swap-in cost the auto resume policy prices from
        self._host_tier: Optional[hostswap.HostPageTier] = None
        self._swap_in_ewma_s = 0.0
        if self._host_swap:
            self._host_tier = hostswap.HostPageTier(ecfg.host_swap_pages)
        #: adapter paging (host_swap engines): every registration's
        #: host-side weight rows (virtual id → numpy pytree), the
        #: virtual → physical residency maps, and the LRU over resident
        #: physical rows. Without host_swap these stay empty and
        #: virtual == physical (the historical hard-capped registry).
        self._adapter_rows_host: Dict[int, Any] = {}
        self._adapter_phys: Dict[int, int] = {}
        self._adapter_virt: Dict[int, int] = {}
        self._adapter_lru = hostswap.LRUIndex()
        self._adapter_free_rows: List[int] = (
            list(range(ecfg.adapter_slots - 1, 0, -1))
            if self._lora and self._host_swap else [])
        self._adapter_spills = 0
        self._adapter_pageins = 0
        self._build()
        with expected_compiles():
            # construction compiles (the init programs materialise
            # here) are sanctioned: another live engine's armed
            # recompile guard must read them as a replica being built,
            # not as its own trace-stability breach
            #: the weights every program computes with (see the class)
            self._params = _held_weights(cfg, params, mesh)
            self.cache, self.state = self._init(self._params)
            if self._chunk_size:
                self._chunk_scratch = self._chunk_scratch_init(
                    self._params)
            if self._prefix_splits:
                self.pool = self._pool_init(self._params)
            if self._lora:
                # the adapter pool: zeros everywhere — row 0 IS the
                # pinned base adapter; never donated, so it survives
                # rebuild_slots and fault replay
                self.adapters = self._adapter_init(self._params)

    @staticmethod
    def _resolve_buckets(ecfg: EngineConfig,
                         tails: bool = False) -> Tuple[int, ...]:
        """The padded widths admission compiles for. ``tails``: they
        are widths of what a row still has to prefill, a longer prompt
        goes in by chunks, and the ladder need not reach
        ``max_prompt_len``."""
        buckets = ecfg.prompt_buckets
        if buckets is None:
            return default_prompt_buckets(ecfg.max_prompt_len)
        if tails:
            buckets = tuple(int(b) for b in buckets)
            if not buckets or list(buckets) != sorted(set(buckets)) \
                    or buckets[0] < 1 \
                    or buckets[-1] > ecfg.max_prompt_len:
                raise ValueError(
                    f"prompt_buckets must be strictly increasing within "
                    f"[1, max_prompt_len], got {buckets}")
            return buckets
        buckets = tuple(int(b) for b in buckets)
        if not buckets or list(buckets) != sorted(set(buckets)):
            raise ValueError(
                f"prompt_buckets must be strictly increasing, got {buckets}")
        if buckets[0] < 1 or buckets[-1] != ecfg.max_prompt_len:
            raise ValueError(
                f"prompt_buckets must lie in [1, max_prompt_len] and end "
                f"at max_prompt_len={ecfg.max_prompt_len} (every prompt "
                f"needs a bucket), got {buckets}")
        return buckets

    @staticmethod
    def _resolve_batch_sizes(ecfg: EngineConfig) -> Tuple[int, ...]:
        sizes = ecfg.admit_batch_sizes
        if sizes is None:
            return tuple(k for k in (1, 2, 4) if k <= ecfg.slots)
        sizes = tuple(int(k) for k in sizes)
        if not sizes or list(sizes) != sorted(set(sizes)):
            raise ValueError(
                f"admit_batch_sizes must be strictly increasing, got {sizes}")
        if sizes[0] != 1:
            raise ValueError(
                f"admit_batch_sizes must start at 1 (the ladder must "
                f"decompose any group count), got {sizes}")
        if sizes[-1] > ecfg.slots:
            raise ValueError(
                f"admit_batch_sizes max {sizes[-1]} exceeds slots "
                f"{ecfg.slots} — a batch cannot outnumber the slots it "
                f"fills")
        return sizes

    @staticmethod
    def _resolve_chunk_ladder(ecfg: EngineConfig) -> Tuple[int, ...]:
        chunks = ecfg.decode_chunks
        if chunks is None:
            return (ecfg.decode_chunk,)
        chunks = tuple(int(c) for c in chunks)
        if not chunks or list(chunks) != sorted(set(chunks)) \
                or chunks[0] < 1:
            raise ValueError(
                f"decode_chunks must be a strictly increasing ladder of "
                f"values >= 1, got {chunks}")
        if ecfg.decode_chunk not in chunks:
            raise ValueError(
                f"decode_chunks {chunks} must contain decode_chunk "
                f"{ecfg.decode_chunk} — the base operating point must "
                f"be a compiled variant")
        return chunks

    @staticmethod
    def _resolve_spec_ladder(ecfg: EngineConfig) -> Tuple[int, ...]:
        ks = ecfg.spec_ks
        if ks is None:
            return (ecfg.spec_k,) if ecfg.spec_k > 0 else ()
        ks = tuple(int(k) for k in ks)
        if not ks or list(ks) != sorted(set(ks)) or ks[0] < 1:
            raise ValueError(
                f"spec_ks must be a strictly increasing ladder of "
                f"values >= 1 (0 — the plain variant — is a tuner "
                f"rung, not a compiled spec program), got {ks}")
        if ecfg.spec_k > 0 and ecfg.spec_k not in ks:
            raise ValueError(
                f"spec_ks {ks} must contain spec_k {ecfg.spec_k} — the "
                f"base operating point must be a compiled variant")
        return ks

    @staticmethod
    def _resolve_prefix_variants(ecfg: EngineConfig,
                                 buckets: Tuple[int, ...]):
        """The prefix pool's static-shape families: usable SPLIT points
        (bucket values that leave >= 1 tail token) and the compiled
        (split, tail bucket) extend variants — a tail bucket is only
        admitted when the combined block ``split + tail_bucket`` fits
        the slot horizon (the tail block is written at offset
        ``split``, and a clamped ``dynamic_update_slice`` would
        silently corrupt a neighbour's columns)."""
        if ecfg.prefix_pool_slots < 0:
            raise ValueError(
                f"prefix_pool_slots {ecfg.prefix_pool_slots} must be "
                f">= 0")
        if ecfg.prefix_pool_slots == 0:
            return (), ()
        mpl = ecfg.max_prompt_len
        splits: List[int] = []
        variants: List[Tuple[int, int]] = []
        for ps in buckets:
            if ps > mpl - 1:
                continue
            tbs = sorted({min(b for b in buckets if b >= tl)
                          for tl in range(1, mpl - ps + 1)})
            tbs = [tb for tb in tbs if ps + tb <= ecfg.max_seq_len]
            if not tbs:
                continue
            splits.append(ps)
            variants.extend((ps, tb) for tb in tbs)
        if not splits:
            raise ValueError(
                f"prefix_pool_slots={ecfg.prefix_pool_slots} but no "
                f"usable split point: no prompt bucket b satisfies "
                f"b <= max_prompt_len-1 with a tail bucket fitting "
                f"max_seq_len (buckets {buckets}, max_prompt_len "
                f"{mpl}, max_seq_len {ecfg.max_seq_len})")
        return tuple(splits), tuple(variants)

    # -- compiled programs -------------------------------------------------

    def _build(self):
        cfg, ecfg, mesh = self.cfg, self.engine_cfg, self._mesh
        pspecs = gpt.param_specs(cfg)
        B = ecfg.slots
        pad = jnp.int32(ecfg.pad_token_id)
        spec = bool(self._spec_ladder)
        self._spec = spec
        # cache [l, 2, B, heads, S, d]: heads are the tp-sharded dim
        # (under a quantized kv_cache_dtype this is the {"kv", "scale"}
        # spec pytree — same sharding on both planes)
        cache_spec = gpt.cache_specs(cfg)
        state_keys = ["tok", "pos", "remaining", "done", "temp",
                      "top_k", "top_p", "key", "eos"]
        if spec:
            state_keys.append("hist")
        state_spec = {k: P() for k in state_keys}

        paged = self._paged
        p_sz = ecfg.page_size
        lora_on = self._lora
        l_scale = self._lora_scale
        lora_spec = gpt.lora_specs(cfg) if lora_on else None

        def init_local(params):
            if paged:
                # the paged pool: the page dim rides the slot dim of
                # the contiguous layout, the horizon dim is one page
                cache = gpt.init_cache(cfg, params, self._num_pages,
                                       max_len=p_sz)
            else:
                cache = gpt.init_cache(cfg, params, B,
                                       max_len=ecfg.max_seq_len)
            state = {
                "tok": jnp.full((B,), pad, jnp.int32),
                "pos": jnp.zeros((B,), jnp.int32),
                "remaining": jnp.zeros((B,), jnp.int32),
                "done": jnp.ones((B,), bool),   # every slot starts free
                "temp": jnp.zeros((B,), jnp.float32),
                "top_k": jnp.zeros((B,), jnp.int32),
                "top_p": jnp.ones((B,), jnp.float32),
                "key": jnp.zeros((B, 2), jnp.uint32),
                "eos": jnp.full((B,), _NO_EOS, jnp.int32),
            }
            if spec:
                # the drafter's token-history ring, -1 = unfilled
                state["hist"] = jnp.full((B, ecfg.spec_hist), -1,
                                         jnp.int32)
            return cache, state

        def make_step_core(chunk: int):
            def step_core(params, cache, state, masks, table, lora):
                # the whole per-token body (decode + per-slot draw +
                # eos/budget masking) lives in gpt.decode_steps — ONE
                # compiled scan of `chunk` steps per dispatch; masks
                # is the per-slot constrained-decoding vocab whitelist
                # (all-True rows are bit-identical to no mask); table
                # is the paged block table (None = contiguous layout);
                # lora is the (adapter pool, [B] id table, scale)
                # bundle (None = no pool — both pool and ids are DATA,
                # one program per variant serves every tenant mix)
                hist = state["hist"] if spec else None
                pos0 = state["pos"]
                cache, state, toks, lps, fins = gpt.decode_steps(
                    cfg, params, cache, state, chunk,
                    pad_token_id=ecfg.pad_token_id, masks=masks,
                    table=table, lora=lora)
                if spec:
                    # keep the drafter's history fresh across PLAIN
                    # chunks too (a payoff-gated or tuner-driven
                    # scheduler flips between the variants): the
                    # chunk's emitted prefix per row is pos_after -
                    # pos_before columns — shift it into the ring so a
                    # later spec chunk drafts from real context
                    state = {**state, "hist": gpt.shift_hist(
                        hist, toks, state["pos"] - pos0)}
                return cache, state, toks, lps, fins

            return step_core

        def make_step_spec_core(chunk: int, k: int):
            def step_spec_core(params, cache, state, masks, table,
                               lora):
                # the speculative chunk: `chunk` draft-verify-accept
                # waves, emitting up to chunk*(k+1) columns (valid
                # marks the real ones); bit-identical streams to the
                # plain variants by the token-matching verification
                # contract (per adapter mix too — the verify forward
                # gathers the same adapter rows the plain path does)
                return gpt.decode_steps_spec(
                    cfg, params, cache, state, chunk,
                    spec_k=k, pad_token_id=ecfg.pad_token_id,
                    masks=masks, table=table, lora=lora)

            return step_spec_core

        def adapt_step(core):
            # core(params, cache, state, masks, table, lora) → the
            # compiled signature for this engine's (paged, lora)
            # feature mix: disabled features contribute NO arguments,
            # so a featureless engine's programs are byte-for-byte the
            # historical ones
            if paged and lora_on:
                def step_local(params, cache, state, masks, table,
                               adapters, aids):
                    return core(params, cache, state, masks, table,
                                (adapters, aids, l_scale))
            elif paged:
                def step_local(params, cache, state, masks, table):
                    return core(params, cache, state, masks, table,
                                None)
            elif lora_on:
                def step_local(params, cache, state, masks, adapters,
                               aids):
                    return core(params, cache, state, masks, None,
                                (adapters, aids, l_scale))
            else:
                def step_local(params, cache, state, masks):
                    return core(params, cache, state, masks, None,
                                None)

            return step_local

        def _parse_extra(extra):
            """Unpack the optional trailing data args every admission
            program shares — (pages, hist0, lora bundle), absent
            features contributing None — so the paged/spec/lora arg
            order is spelled exactly once."""
            i = 0
            pages = hist0 = lora = None
            if paged:
                pages = extra[i]
                i += 1
            if spec:
                hist0 = extra[i]
                i += 1
            if lora_on:
                lora = (extra[i], extra[i + 1], l_scale)
            return pages, hist0, lora

        def make_admit(bucket: int):
            n_ins = -(-bucket // p_sz) if paged else 0

            def admit_local(params, cache, state, slots, prompts, p_lens,
                            max_tokens, temp, top_k, top_p, keys, eos,
                            req_idx, seeded, masks, *extra):
                # extra rides the optional data args in a fixed order:
                # the paged per-row page indices, the spec history
                # seed, then the adapter pool + per-row adapter ids
                pages, hist0, lora = _parse_extra(extra)
                # ONE padded forward admits the whole [k, bucket] batch;
                # row i's logits/KV are exactly its solo prefill_at's
                blocks, logits0 = gpt.prefill_many(
                    cfg, params, prompts, p_lens - 1, max_len=bucket,
                    lora=lora)
                with jax.named_scope("apex.sample"):
                    keys, first, first_lp = _draw_first(
                        logits0, keys, seeded, req_idx, p_lens, temp,
                        top_k, top_p, masks)
                if paged:
                    # the paged scatter: row i's bucket columns land
                    # in its own allocated pages (pad columns reach
                    # the sink or the row's not-yet-decoded cells —
                    # masked garbage either way)
                    cache = gpt.cache_insert_pages(
                        cache, _pad_span(blocks, n_ins * p_sz), pages,
                        page_size=p_sz)
                else:
                    cache = gpt.cache_insert_slots(cache, blocks, slots)
                new_state, hit_eos, done0 = _admitted_state(
                    state, slots, first, p_lens, max_tokens, temp, top_k,
                    top_p, keys, eos, hist0 if spec else None)
                return cache, new_state, first, first_lp, hit_eos, done0

            return admit_local

        def retire_local(state, slot):
            return {**state, "done": state["done"].at[slot].set(True)}

        # cache + state are donated: the engine rebinds self.cache /
        # self.state from each call's outputs, and without donation
        # every step/admit copies the whole [l, 2, B, hl, S, d] cache
        # just to update one slot's column (CPU-mesh A/B in
        # docs/DESIGN.md "Serving"; re-measure on chip)
        sm = lambda f, in_specs, out_specs, donate=(): jax.jit(
            jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                          out_specs=out_specs, check_vma=False),
            donate_argnums=donate)
        scalar = P()
        n_step_args = 2 if paged else 1  # masks (+ tables)
        # lora args (the adapter pool is tp-sharded — never a scalar
        # spec; the [B]/[k] id tables are) ride LAST on every program
        # that runs a forward
        lora_in = (lora_spec, scalar) if lora_on else ()
        self._init = sm(init_local, (pspecs,), (cache_spec, state_spec))
        if lora_on:
            def adapter_init_local(params):
                return gpt.init_lora_pool(cfg, params,
                                          ecfg.adapter_slots,
                                          ecfg.adapter_rank)

            def adapter_set_local(pool, row, idx):
                return gpt.lora_set_row(pool, row, idx)

            # the pool rides its own init (NOT the slot init): a fault
            # rebuild re-inits slots but leaves registered adapters
            # intact — and the set program is NOT donated, so a failed
            # registration cannot consume the rows already serving
            self._adapter_init = sm(adapter_init_local, (pspecs,),
                                    lora_spec)
            self._adapter_set = sm(
                adapter_set_local,
                (lora_spec, gpt.lora_row_specs(cfg), scalar), lora_spec)
        # one compiled step program per decode-chunk rung, and one
        # spec variant per (chunk, k) cross — a self-tuning scheduler
        # switches among them per dispatch, all pre-warmed, so the
        # armed recompile guard never trips (serving.tuner's contract)
        self._step_variants: Dict[int, Any] = {}
        self._spec_variants: Dict[Tuple[int, int], Any] = {}
        for c in self._chunk_ladder:
            self._step_variants[c] = sm(
                adapt_step(make_step_core(c)),
                (pspecs, cache_spec, state_spec)
                + (scalar,) * n_step_args + lora_in,
                (cache_spec, state_spec, scalar, scalar, scalar),
                donate=(1, 2))
            for k in self._spec_ladder:
                self._spec_variants[(c, k)] = sm(
                    adapt_step(make_step_spec_core(c, k)),
                    (pspecs, cache_spec, state_spec)
                    + (scalar,) * n_step_args + lora_in,
                    (cache_spec, state_spec, scalar, scalar, scalar,
                     scalar),
                    donate=(1, 2))
        # one admission program per (bucket, k) — the k dim and padded
        # width are static shapes, everything request-scoped is data
        # (paged engines thread the per-row page indices, spec engines
        # the host-packed prompt-tail history seed, lora engines the
        # adapter pool + per-row adapter ids)
        n_admit_args = 12 + int(paged) + int(spec)
        self._admits: Dict[Tuple[int, int], Any] = {}
        self._fills: Dict[int, Any] = {}
        if self._latent:
            latent_engine.build(self)
        for bucket in () if self._latent else self._buckets:
            fn = make_admit(bucket)
            for k in self._batch_sizes:
                self._admits[(bucket, k)] = sm(
                    fn, (pspecs, cache_spec, state_spec)
                    + (scalar,) * n_admit_args + lora_in,
                    (cache_spec, state_spec, scalar, scalar, scalar,
                     scalar),
                    donate=(1, 2))
        self._retire = sm(retire_local, (state_spec, scalar), state_spec,
                          donate=(0,))

        # -- host-swap tier programs (host_swap=True) ---------------------
        # pages_out gathers n whole pages (storage form — the quantized
        # planes travel as-is, so the host round trip is bit-exact) and
        # pages_in scatters them back; one compiled variant per
        # power-of-two swap-batch rung (plan_rungs decomposes any
        # count), all warmed, both enumerated by _swap_program_items so
        # the recompile sentinel and the flatness pin cover them. The
        # gather does NOT donate (the cache keeps serving); the scatter
        # donates the cache exactly like every other insert.
        self._swap_outs: Dict[int, Any] = {}
        self._swap_ins: Dict[int, Any] = {}
        if self._host_swap:
            def swap_out_local(cache, pages):
                return gpt.cache_gather_pages(cache, pages)

            def swap_in_local(cache, block, pages):
                return gpt.cache_insert_pages(cache, block,
                                              pages[:, None],
                                              page_size=p_sz)

            for n in self._swap_rungs:
                self._swap_outs[n] = sm(
                    swap_out_local, (cache_spec, scalar), cache_spec)
                self._swap_ins[n] = sm(
                    swap_in_local, (cache_spec, cache_spec, scalar),
                    cache_spec, donate=(0,))

            # the resume scatter's state half: write one parked slot's
            # full state row (PRNG key included — the sampled-parity
            # crux) back at a traced slot index, donating state like
            # retire does
            def state_restore_local(state, row, slot):
                return {k: state[k].at[slot].set(row[k][0])
                        for k in state}

            self._state_restore = sm(
                state_restore_local, (state_spec, state_spec, scalar),
                state_spec, donate=(0,))

        # -- chunked-prefill programs (prefill_chunk > 0) -----------------
        # chunk 0 is a bucket-sized cold prefill into the compute-dtype
        # scratch; chunk i attends the scratch's first i*C columns
        # through gpt.prefill_extend (the prefix-reuse forward — cost
        # scales with the chunk, and its hit == cold parity contract
        # makes chunked streams bit-identical to monolithic admission
        # off-TPU); the finish draws the first token from the final
        # chunk's logits and quantizes/inserts the whole prompt block
        # exactly where a cold admission would
        self._chunk_exts: Dict[int, Any] = {}
        if self._chunk_size:
            chunk_c = self._chunk_size
            mpl = ecfg.max_prompt_len
            # the scratch stores COMPUTE-dtype K/V (the pool's
            # master-copy argument: every later chunk must attend the
            # exact prefix values a cold prefill would see;
            # quantization happens once at the finish insert)
            cfg_ext = dataclasses.replace(cfg, kv_cache_dtype="bf16")
            scratch_spec = gpt.cache_specs(cfg_ext)
            n_fin = -(-mpl // p_sz) if paged else 0

            def scratch_init_local(params):
                return gpt.init_cache(cfg_ext, params, 1, max_len=mpl)

            self._chunk_scratch_init = sm(scratch_init_local, (pspecs,),
                                          scratch_spec)

            def chunk0_local(params, scratch, tokens, *extra):
                lora = ((extra[0], extra[1], l_scale) if lora_on
                        else None)
                blocks, _ = gpt.prefill_many(
                    cfg_ext, params, tokens,
                    jnp.full((1,), chunk_c - 1, jnp.int32),
                    max_len=chunk_c, lora=lora)
                return gpt.cache_insert_slot(scratch, blocks,
                                             jnp.int32(0))

            self._chunk0 = sm(chunk0_local,
                              (pspecs, scratch_spec, scalar) + lora_in,
                              scratch_spec, donate=(1,))

            def make_chunk_ext(i: int):
                pfx = i * chunk_c

                def chunk_ext_local(params, scratch, tail, last,
                                    *extra):
                    lora = ((extra[0], extra[1], l_scale) if lora_on
                            else None)
                    prefix = jax.tree.map(
                        lambda x: lax.slice_in_dim(x, 0, pfx, axis=4),
                        scratch)
                    tail_kv, logits = gpt.prefill_extend(
                        cfg, params, prefix, tail, last,
                        prefix_len=pfx, lora=lora)
                    return (gpt.cache_insert_slot(
                        scratch, tail_kv, jnp.int32(0), pos=pfx),
                        logits)

                return chunk_ext_local

            for i in range(1, mpl // chunk_c):
                self._chunk_exts[i] = sm(
                    make_chunk_ext(i),
                    (pspecs, scratch_spec, scalar, scalar) + lora_in,
                    (scratch_spec, scalar), donate=(1,))

            def chunk_finish_local(params, cache, state, scratch,
                                   logits0, slots, p_lens, max_tokens,
                                   temp, top_k, top_p, keys, eos,
                                   req_idx, seeded, masks, *extra):
                pages = extra[0] if paged else None
                hist0 = extra[-1] if spec else None
                # the fold position is p_len - 1, exactly the cold
                # admission's — same logits (prefill_extend parity),
                # same fold, same first draw
                keys, first, first_lp = _draw_first(
                    logits0, keys, seeded, req_idx, p_lens, temp, top_k,
                    top_p, masks)
                blk = gpt.quantize_cache_block(cfg, scratch)
                if paged:
                    cache = gpt.cache_insert_pages(
                        cache, _pad_span(blk, n_fin * p_sz), pages,
                        page_size=p_sz)
                else:
                    cache = gpt.cache_insert_slot(cache, blk, slots[0])
                new_state, hit_eos, done0 = _admitted_state(
                    state, slots, first, p_lens, max_tokens, temp, top_k,
                    top_p, keys, eos, hist0 if spec else None)
                return (cache, new_state, first, first_lp, hit_eos,
                        done0)

            self._chunk_finish = sm(
                chunk_finish_local,
                (pspecs, cache_spec, state_spec, scratch_spec)
                + (scalar,) * (12 + int(paged) + int(spec)),
                (cache_spec, state_spec, scalar, scalar, scalar,
                 scalar),
                donate=(1, 2))

        # -- shared-prefix pool programs (prefix_pool_slots > 0) ----------
        self._pool_inserts: Dict[int, Any] = {}
        self._pool_pageins: Dict[int, Any] = {}
        self._admit_prefix: Dict[Tuple[int, int], Any] = {}
        if not self._prefix_splits:
            return
        pool_pages = ecfg.prefix_pool_slots
        pool_horizon = max(self._prefix_splits)
        # the pool stores COMPUTE-dtype K/V even under a quantized
        # kv_cache_dtype — the amp master-copy idea: the tail-extend
        # forward attends over the EXACT prefix values (what a cold
        # prefill of the full prompt would see), and quantization
        # happens once at slot insert, exactly where the cold path
        # quantizes. A quantized pool would make hits attend over
        # dequantize(quantize(prefix)) while cold admissions attend
        # over the exact prefix — a quantization-error divergence the
        # bit-parity oracle would only catch when a token lands near a
        # tie. The pool is tiny next to the slot cache; the capacity
        # play is the slots.
        cfg_pool = dataclasses.replace(cfg, kv_cache_dtype="bf16")
        pool_spec = gpt.cache_specs(cfg_pool)

        def pool_init_local(params):
            return gpt.init_cache(cfg_pool, params, pool_pages,
                                  max_len=pool_horizon)

        # the pool rides its own init (NOT the slot init): a fault
        # rebuild re-inits slots but leaves registered prefixes intact
        self._pool_init = sm(pool_init_local, (pspecs,), pool_spec)

        def make_pool_insert(pb: int):
            def pool_insert_local(params, pool, tokens, page):
                # the whole [1, pb] prefix is real — register slices
                # the template AT the bucket — so every stored K/V
                # position is valid for any prompt sharing it
                blocks, _ = gpt.prefill_many(
                    cfg_pool, params, tokens,
                    jnp.full((1,), pb - 1, jnp.int32), max_len=pb)
                return gpt.cache_insert_slot(pool, blocks, page)

            return pool_insert_local

        for pb in self._prefix_splits:
            self._pool_inserts[pb] = sm(
                make_pool_insert(pb),
                (pspecs, pool_spec, scalar, scalar), pool_spec,
                donate=(1,))

        if paged:
            # the copy-on-write page-in: quantize a registered
            # prefix's compute-dtype pool block ONCE into pinned cache
            # pages (the same quantizer, same input values as a cold
            # prefill of those positions — so a page-sharing hit reads
            # bit-identical cache bytes to a PR-7 pooled-slot copy).
            # Hits then map these pages read-only; no prefix K/V bytes
            # move at admission time at all.
            def make_pool_pagein(pb: int):
                def pool_pagein_local(cache, pool, page, pages):
                    block = gpt.cache_gather_page(pool, page, pb)
                    return gpt.cache_insert_pages(
                        cache, gpt.quantize_cache_block(cfg, block),
                        pages, page_size=p_sz)

                return pool_pagein_local

            for pb in self._prefix_splits:
                self._pool_pageins[pb] = sm(
                    make_pool_pagein(pb),
                    (cache_spec, pool_spec, scalar, scalar), cache_spec,
                    donate=(0,))

        def make_admit_prefix(ps: int, tb: int):
            n_tail = -(-tb // p_sz) if paged else 0

            def admit_prefix_local(params, cache, state, pool, slots,
                                   tails, t_lens, max_tokens, temp,
                                   top_k, top_p, keys, eos, req_idx,
                                   seeded, masks, page, *extra):
                pages, hist0, lora = _parse_extra(extra)
                # the compiled gather: page -> [l, 2, 1, hl, ps, d]
                # block of EXACT compute-dtype prefix K/V (the pool's
                # master copy). Prefix hits are validated to ride the
                # BASE adapter (id 0 — the pooled prefix was prefilled
                # with base weights), so the threaded lora bundle is
                # an exact zero delta; it rides anyway so the program
                # signature is uniform across the lora engine's
                # admission family.
                block = gpt.cache_gather_page(pool, page, ps)
                tail_kv, logits0 = gpt.prefill_extend(
                    cfg, params, block, tails, t_lens - 1,
                    prefix_len=ps, lora=lora)
                p_lens = ps + t_lens
                keys, first, first_lp = _draw_first(
                    logits0, keys, seeded, req_idx, p_lens, temp, top_k,
                    top_p, masks)
                if paged:
                    # copy-on-write: the prefix pages are SHARED (the
                    # host mapped them into this slot's table row and
                    # pinned their refcounts) — only the TAIL block
                    # moves, into the slot's private pages at the
                    # page-aligned split offset. The shared pages
                    # already hold quantize(prefix) from registration
                    # page-in, so the slot's gathered cache bytes are
                    # exactly what the contiguous two-insert spelling
                    # below produces.
                    cache = gpt.cache_insert_pages(
                        cache,
                        _pad_span(gpt.quantize_cache_block(cfg, tail_kv),
                                  n_tail * p_sz),
                        pages, page_size=p_sz)
                else:
                    # the prefix block quantizes at INSERT (same
                    # quantizer, same exact input values as a cold
                    # prefill of those positions), the tail block
                    # appends at offset ps — together exactly the
                    # cache bytes a cold admission of the full prompt
                    # would hold
                    cache = gpt.cache_insert_slot(
                        cache, gpt.quantize_cache_block(cfg, block),
                        slots[0])
                    cache = gpt.cache_insert_slot(
                        cache, gpt.quantize_cache_block(cfg, tail_kv),
                        slots[0], pos=ps)
                new_state, hit_eos, done0 = _admitted_state(
                    state, slots, first, p_lens, max_tokens, temp, top_k,
                    top_p, keys, eos, hist0 if spec else None)
                return (cache, new_state, first, first_lp, hit_eos,
                        done0)

            return admit_prefix_local

        for (ps, tb) in self._extend_variants:
            self._admit_prefix[(ps, tb)] = sm(
                make_admit_prefix(ps, tb),
                (pspecs, cache_spec, state_spec, pool_spec)
                + (scalar,) * (13 + int(paged) + int(spec)) + lora_in,
                (cache_spec, state_spec, scalar, scalar, scalar,
                 scalar),
                donate=(1, 2))

    # -- host API ----------------------------------------------------------

    @property
    def slots(self) -> int:
        return self.engine_cfg.slots

    @property
    def prompt_buckets(self) -> Tuple[int, ...]:
        """The resolved padded-prefill length ladder (ascending; ends
        at ``max_prompt_len``)."""
        return self._buckets

    @property
    def admit_batch_sizes(self) -> Tuple[int, ...]:
        """The resolved admission batch-size ladder (ascending; starts
        at 1)."""
        return self._batch_sizes

    @property
    def decode_chunks(self) -> Tuple[int, ...]:
        """The resolved decode-chunk step-variant ladder (ascending;
        always contains the base ``decode_chunk``) — every rung is one
        pre-warmed compiled step program a tuner may dispatch."""
        return self._chunk_ladder

    @property
    def spec_ks(self) -> Tuple[int, ...]:
        """The resolved speculative draft-width ladder (ascending;
        empty = no speculation) — every rung crosses with every
        decode-chunk rung as one pre-warmed spec step program."""
        return self._spec_ladder

    @property
    def prefix_pool_enabled(self) -> bool:
        """True when ``EngineConfig.prefix_pool_slots > 0`` resolved to
        at least one usable split point (under the latent mixer: to
        any pool at all)."""
        return bool(self._prefix_splits) or (
            self._latent and self.engine_cfg.prefix_pool_slots > 0)

    @property
    def prefix_splits(self) -> Tuple[int, ...]:
        """Bucket-aligned split points the prefix pool can reuse at
        (ascending; empty when the pool is disabled)."""
        return self._prefix_splits

    # -- paged KV cache (EngineConfig.page_size > 0) -----------------------

    @property
    def paged(self) -> bool:
        """True when the cache runs the paged layout."""
        return self._paged

    @property
    def page_allocator(self) -> Optional[PageAllocator]:
        """The refcounted page allocator (None in contiguous mode) —
        the scheduler's occupancy/fragmentation gauge source."""
        return self._page_alloc

    @property
    def max_pages(self) -> int:
        """Block-table width per slot (``ceil(max_seq_len /
        page_size)`` — a config-derived constant; 0 in contiguous
        mode)."""
        return self._max_pages

    def pages_needed(self, prompt_len: int, max_tokens: int,
                     prefix_len: int = 0) -> int:
        """Private pages one admission pins: the request's token
        footprint (prompt + budget, minus a shared prefix) in pages.
        0 in contiguous mode — the scheduler's backpressure check is
        layout-agnostic."""
        if not self._paged:
            return 0
        p = self.engine_cfg.page_size
        return -(-(prompt_len + max_tokens) // p) - prefix_len // p

    def can_admit_pages(self, prompt_len: int, max_tokens: int,
                        prefix_len: int = 0) -> bool:
        """Whether the pool currently has the private pages this
        admission needs (always True in contiguous mode)."""
        if not self._paged:
            return True
        return self._page_alloc.can_alloc(
            self.pages_needed(prompt_len, max_tokens, prefix_len))

    def free_slot(self, slot: int) -> None:
        """Release ``slot``'s page mapping: private pages return to
        the free list, shared prefix pages drop one pin, and the
        slot's table row redirects to the sink page (its frozen decode
        lane keeps writing every chunk — the sink absorbs that). The
        scheduler calls this at request release; no-op in contiguous
        mode (slots there are implicitly recycled by the next
        admission's overwrite)."""
        if self._paged:
            self._free_slot_pages(slot)
        if self._host_swap and self._lora:
            # unpin the slot's adapter row so the paging LRU can spill
            # it (done lanes emit pad regardless of the row they read,
            # so rebinding a freed slot to base is stream-invisible)
            self._set_slot_adapter(slot, 0)

    def page_stats(self) -> Optional[Dict[str, float]]:
        """Allocator occupancy snapshot (None in contiguous mode)."""
        if self._page_alloc is None:
            return None
        return self._page_alloc.stats()

    def _free_slot_pages(self, slot: int) -> None:
        ent = self._slot_pages.pop(slot, None)
        if ent is None:
            return
        priv, shared, footprint = ent
        self._page_alloc.free(priv)
        self._page_alloc.free(shared)
        self._page_alloc.used_tokens -= footprint
        self._tables[slot, :] = SINK
        self._tables_dev = None

    def _alloc_slot_pages(self, slot: int, p_len: int, max_tokens: int,
                          prefix_page: Optional[int] = None,
                          prefix_len: int = 0) -> np.ndarray:
        """Map ``slot``'s table row for one admission: pin the shared
        prefix pages (copy-on-write — refcount, no bytes move),
        allocate the private tail/decode pages, sink-fill the rest.
        Raises :class:`PagesExhausted` (before any state change beyond
        releasing the slot's stale mapping) when the pool is dry.
        Returns the row."""
        self._free_slot_pages(slot)
        p = self.engine_cfg.page_size
        shared: List[int] = []
        if prefix_page is not None:
            shared = list(
                self._prefix_pages[prefix_page][:prefix_len // p])
        need = -(-(p_len + max_tokens) // p) - len(shared)
        priv = self._page_alloc.alloc(need)
        self._page_alloc.share(shared)
        row = np.full((self._max_pages,), SINK, np.int32)
        row[:len(shared)] = shared
        row[len(shared):len(shared) + need] = priv
        self._tables[slot] = row
        self._tables_dev = None
        footprint = p_len + max_tokens - prefix_len
        self._page_alloc.used_tokens += footprint
        self._slot_pages[slot] = (priv, shared, footprint)
        return self._tables[slot]

    # -- host-swap tier (EngineConfig.host_swap) ---------------------------

    @property
    def host_swap_enabled(self) -> bool:
        """True when ``EngineConfig.host_swap`` is on."""
        return self._host_swap

    def host_parked(self, key: Any) -> bool:
        """Whether ``key``'s swap payload is still in the host tier
        (False after a capacity eviction — the recompute-fallback
        signal)."""
        return (self._host_tier is not None
                and key in self._host_tier)

    def swap_in_cost_s(self, n_pages: int) -> Optional[float]:
        """Measured swap-in wall cost for ``n_pages`` (the per-page
        EWMA the auto resume policy prices against replay); ``None``
        before the first measured resume."""
        if self._swap_in_ewma_s <= 0.0:
            return None
        return self._swap_in_ewma_s * max(n_pages, 1)

    def host_tier_stats(self) -> Optional[Dict[str, float]]:
        """Host-tier occupancy snapshot (None without host_swap)."""
        if self._host_tier is None:
            return None
        return self._host_tier.stats()

    def parked_pages(self, key: Any) -> int:
        """Private pages ``key``'s parked payload holds (0 when not
        swap-parked) — what a swap-resume must allocate."""
        if self._host_tier is None:
            return 0
        ent = self._host_tier._entries.get(key)
        return 0 if ent is None else ent.n_pages

    def parked_bytes(self, key: Any) -> int:
        """Host-RAM bytes ``key``'s parked payload holds (0 when not
        swap-parked) — the ``page_swap_out`` flight event's byte
        field."""
        if self._host_tier is None:
            return 0
        ent = self._host_tier._entries.get(key)
        return 0 if ent is None else ent.nbytes

    def slot_page_count(self, slot: int) -> int:
        """PRIVATE pages ``slot``'s live mapping holds (0 when
        unmapped, or in contiguous mode) — what preempting the slot
        would free back to the pool."""
        if not self._paged:
            return 0
        ent = self._slot_pages.get(slot)
        return 0 if ent is None else len(ent[0])

    def park_slot(self, slot: int, key: Any) -> List[Any]:
        """Swap ``slot`` out to the host tier under ``key``: gather its
        PRIVATE pages (compiled per-rung ``pages_out`` — storage form,
        bit-exact round trip) and its full state row (PRNG key
        included) into a host payload, retire the lane, free the
        device pages, and park the payload in the LRU. Shared
        copy-on-write prefix pages never move — they drop the slot's
        pin here and re-pin at resume (the registration pin keeps them
        alive and :meth:`rebuild_slots` re-pages them into the same
        ids, so a parked conversation even survives a fault rebuild).

        Returns the keys the tier capacity-evicted to make room
        (possibly including ``key`` itself) — the caller downgrades
        those conversations to recompute-resume; their page/byte
        accounting is dropped here. The caller must ensure no chunk is
        in flight (parking never happens mid-chunk — the dispatched
        tables still map the pages being freed)."""
        self._check_poisoned()
        if not self._host_swap:
            raise ValueError(
                "park_slot without host_swap (EngineConfig.host_swap "
                "== False)")
        if not 0 <= slot < self.slots:
            raise ValueError(f"slot {slot} outside [0, {self.slots})")
        ent = self._slot_pages.get(slot)
        if ent is None:
            raise ValueError(
                f"slot {slot} has no page mapping — nothing to park")
        priv, shared, footprint = ent
        # the state row FIRST (retire below flips its done flag)
        row = {k: np.asarray(self.state[k])[slot:slot + 1].copy()
               for k in self.state}
        blocks: List[Tuple[int, Any]] = []
        off = 0
        for n in hostswap.plan_rungs(len(priv)):
            chunk = np.asarray(priv[off:off + n], np.int32)
            blocks.append((n, jax.tree.map(
                np.asarray, self._swap_outs[n](self.cache, chunk))))
            off += n
        nbytes = int(sum(x.nbytes for _, b in blocks
                         for x in jax.tree.leaves(b)))
        payload = {
            "blocks": blocks, "state": row, "shared": list(shared),
            "n_priv": len(priv), "footprint": footprint,
            "mask": self._masks[slot].copy(),
            "adapter": int(self._adapter_virtual(
                int(self._adapter_ids[slot]))),
        }
        # freeze the lane, then release its device footprint: the
        # table row redirects to the sink, so the frozen column's
        # writes land in garbage
        self.retire(slot)
        self._free_slot_pages(slot)
        self._page_alloc.note_swap_out(len(priv), nbytes)
        evicted = self._host_tier.park(key, payload, len(priv), nbytes)
        out: List[Any] = []
        for ek, e in evicted:
            self._page_alloc.note_swap_drop(e.n_pages, e.nbytes)
            out.append(ek)
        return out

    def resume_slot(self, slot: int, key: Any) -> None:
        """Swap ``key``'s parked conversation back into ``slot``:
        allocate fresh private pages (:class:`PagesExhausted`
        propagates BEFORE any device work — check
        ``page_allocator.can_alloc(parked_pages(key))`` first), re-pin
        its shared prefix pages, scatter the host payload through the
        per-rung ``pages_in`` programs, and restore the state row /
        vocab mask / adapter binding. The continued stream is
        bit-identical to an uninterrupted run (the restored PRNG key
        and token-history ring carry the sampled path). Raises
        ``KeyError`` when the payload was capacity-evicted — the
        caller's recompute fallback."""
        self._check_poisoned()
        if not self._host_swap:
            raise ValueError(
                "resume_slot without host_swap (EngineConfig.host_swap "
                "== False)")
        if not 0 <= slot < self.slots:
            raise ValueError(f"slot {slot} outside [0, {self.slots})")
        if slot in self._slot_pages:
            raise ValueError(
                f"slot {slot} still holds a page mapping — free it "
                f"before resuming into it")
        if not self.host_parked(key):
            raise KeyError(
                f"{key!r} has no host payload (capacity-evicted or "
                f"never swap-parked) — resume by recompute")
        t0 = time.perf_counter()
        ent = self._host_tier.take(key)
        p = ent.payload
        n_priv, shared = p["n_priv"], p["shared"]
        priv = self._page_alloc.alloc(n_priv)
        self._page_alloc.share(shared)
        tab = np.full((self._max_pages,), SINK, np.int32)
        tab[:len(shared)] = shared
        tab[len(shared):len(shared) + n_priv] = priv
        self._tables[slot] = tab
        self._tables_dev = None
        self._page_alloc.used_tokens += p["footprint"]
        self._slot_pages[slot] = (priv, list(shared), p["footprint"])
        try:
            off = 0
            for n, block in p["blocks"]:
                self.cache = self._swap_ins[n](
                    self.cache, block,
                    np.asarray(priv[off:off + n], np.int32))
                off += n
            self.state = self._state_restore(self.state, p["state"],
                                             np.int32(slot))
        except Exception:
            # the scatter DONATES cache/state — a failure may have
            # consumed them; poison until rebuild_slots() like every
            # other donating seam (the payload is already consumed, so
            # the caller falls back to recompute)
            self._free_slot_pages(slot)
            self._poisoned = True
            raise
        if not np.array_equal(self._masks[slot], p["mask"]):
            self._masks[slot] = p["mask"]
            self._masks_dev = None
        self._bind_slot_adapter(slot, p["adapter"])
        self._page_alloc.note_swap_in(n_priv, ent.nbytes)
        # sync via value fetch (never block_until_ready) so the EWMA
        # prices the whole round trip the auto policy compares
        np.asarray(self.state["tok"])
        sample = (time.perf_counter() - t0) / max(n_priv, 1)
        self._swap_in_ewma_s = (
            sample if self._swap_in_ewma_s <= 0.0
            else 0.7 * self._swap_in_ewma_s + 0.3 * sample)

    def drop_parked(self, key: Any) -> None:
        """Discard ``key``'s swap payload (a recompute-resume or an
        expired parked conversation) — accounting only, no device
        work. No-op when absent."""
        if self._host_tier is None:
            return
        ent = self._host_tier.take(key)
        if ent is not None:
            self._page_alloc.note_swap_drop(ent.n_pages, ent.nbytes)

    def register_prefix(self, tokens) -> int:
        """Prefill a shared prompt prefix (a system-prompt template)
        ONCE into a pool page; returns the page index. The template is
        sliced AT its largest usable split bucket (every stored K/V
        position is real), and indexed at every smaller split too, so
        :meth:`match_prefix` can reuse the longest bucket-aligned
        piece a prompt shares. Registering a template whose
        bucket-aligned slice is already pooled returns the existing
        page (no device work). Raises when the pool is disabled, full,
        or the template is shorter than the smallest split bucket.
        Call AFTER :meth:`warmup` (which resets the pool); the insert
        rides a program warmup already compiled, so a recompile guard
        stays armed through registration.

        Under the latent mixer the prefix is filled straight into
        pinned cache pages, a chunk a dispatch, and may have any whole
        number of pages (:func:`latent_engine.register_prefix`)."""
        if self._latent:
            self._check_poisoned()
            return latent_engine.register_prefix(self, tokens)
        if not self._prefix_splits:
            raise ValueError(
                "prefix pool disabled (EngineConfig.prefix_pool_slots "
                "== 0)")
        tokens = np.asarray(tokens, np.int32)
        if tokens.ndim != 1 or tokens.size < 1:
            raise ValueError("prefix template must be a 1-D token list")
        if tokens.min() < 0 or tokens.max() >= self.cfg.vocab_size:
            raise ValueError(
                f"prefix template tokens outside vocab "
                f"[0, {self.cfg.vocab_size})")
        usable = [b for b in self._prefix_splits if b <= tokens.size]
        if not usable:
            raise ValueError(
                f"prefix template of {tokens.size} tokens is shorter "
                f"than the smallest split bucket "
                f"{self._prefix_splits[0]} — nothing to pool")
        pb = max(usable)
        t = tuple(int(x) for x in tokens[:pb])
        hit = self._prefix_index.get(t)
        if hit is not None and hit[1] == pb:
            return hit[0]
        if self._prefix_used >= self.engine_cfg.prefix_pool_slots:
            raise ValueError(
                f"prefix pool full "
                f"({self.engine_cfg.prefix_pool_slots} pages)")
        page = self._prefix_used
        try:
            self.pool = self._pool_inserts[pb](
                self._params, self.pool,
                np.asarray([t], np.int32), np.int32(page))
        except Exception:
            # the insert DONATES the pool buffer: an error escaping the
            # call may have consumed it, and every already-registered
            # page lives inside it — reset pool + registry to a clean
            # empty state (callers re-register) rather than leave the
            # index pointing into a dead buffer
            self._prefix_index.clear()
            self._prefix_tokens.clear()
            self._prefix_used = 0
            self.pool = self._pool_init(self._params)
            raise
        if self._paged:
            # page-in the quantized prefix ONCE into pinned cache
            # pages — the copy-on-write master every sharing hit maps
            # read-only (refcount 1 here = the registration pin, so
            # the pages survive every hit's release)
            cache_pages = self._page_alloc.alloc(
                pb // self.engine_cfg.page_size)
            try:
                self.cache = self._pool_pageins[pb](
                    self.cache, self.pool, np.int32(page),
                    np.asarray([cache_pages], np.int32))
            except Exception:
                # the page-in DONATES the cache — a failure may have
                # consumed it; poison until rebuild_slots() like every
                # other cache-donating seam
                self._page_alloc.free(cache_pages)
                self._poisoned = True
                raise
            self._prefix_pages[page] = cache_pages
            self._page_alloc.used_tokens += pb
        # page committed only after the insert landed — a failed call
        # must not leak the page
        self._prefix_used += 1
        self._prefix_tokens[page] = t
        for b in usable:
            # first registration wins a shorter shared key — the K/V
            # of tokens[:b] is identical whichever template stored it
            self._prefix_index.setdefault(t[:b], (page, b))
        return page

    def match_prefix(self, prompt) -> Optional[Tuple[int, int]]:
        """Longest-split prefix-pool hit for ``prompt``: returns
        ``(page, split)`` such that ``prompt[:split]`` equals a pooled
        prefix, ``split`` is bucket-aligned, at least one tail token
        remains, and a compiled (split, tail bucket) extend variant
        exists — or ``None`` (cold prefill). O(splits) tuple-hash
        lookups; no device work."""
        if not self._prefix_index:
            return None
        if self._latent:
            return latent_engine.match_prefix(self, prompt)
        t = tuple(int(x) for x in prompt)
        for split in sorted(self._prefix_splits, reverse=True):
            if split >= len(t):
                continue
            tb = self.bucket_for(len(t) - split)
            if (split, tb) not in self._admit_prefix:
                continue
            hit = self._prefix_index.get(t[:split])
            if hit is not None:
                return hit[0], split
        return None

    # -- batched multi-LoRA (EngineConfig.adapter_slots > 0) ---------------

    @property
    def adapter_pool_enabled(self) -> bool:
        """True when ``EngineConfig.adapter_slots > 0``."""
        return self._lora

    @property
    def adapter_names(self) -> Dict[str, int]:
        """Registered adapter name → pool row (copy; excludes the
        pinned base row 0) — the ``/v1/models`` listing source."""
        return dict(self._adapter_names)

    @property
    def adapters_registered(self) -> int:
        """Registered adapter count (excluding the pinned base
        row)."""
        return max(self._adapter_used - 1, 0)

    def adapter_bytes(self) -> int:
        """Device bytes held by the adapter pool (0 when disabled)."""
        if self.adapters is None:
            return 0
        return int(sum(x.nbytes
                       for x in jax.tree.leaves(self.adapters)))

    def _lora_expected_shapes(self) -> Dict[str, Dict[str, Tuple[int, ...]]]:
        cfg, r = self.cfg, self.engine_cfg.adapter_rank
        L, h, f = cfg.num_layers, cfg.hidden_size, cfg.ffn
        return {
            "qkv": {"a": (L, r, h), "b": (L, r, 3, h)},
            "proj": {"a": (L, r, h), "b": (L, r, h)},
            "fc1": {"a": (L, r, h), "b": (L, r, f)},
            "fc2": {"a": (L, r, f), "b": (L, r, h)},
        }

    def register_adapter(self, weights=None, *, name: Optional[str] = None,
                         seed: Optional[int] = None) -> int:
        """Register one LoRA adapter into the next free pool row;
        returns its id (the value requests pass as
        ``Admission.adapter`` / ``Request.adapter``). Either pass
        ``weights`` — GLOBAL per-site ``{"qkv"/"proj"/"fc1"/"fc2":
        {"a", "b"}}`` arrays in the :func:`gpt.init_lora_weights`
        layout — or ``seed`` to generate the deterministic synthetic
        adapter that seed names (the bench/demo path; post-mortem
        replay rebuilds seeded adapters bit-identically from the
        recorded seed). Registering an already-registered ``name``
        returns the existing id (idempotent, like
        :meth:`register_prefix`). Call AFTER :meth:`warmup` — the set
        program is compiled there, so registration never trips an
        armed recompile guard. The pool is never donated: registered
        rows survive :meth:`rebuild_slots` and fault replay."""
        if not self._lora:
            raise ValueError(
                "adapter pool disabled (EngineConfig.adapter_slots "
                "== 0)")
        if not self._warmed:
            raise ValueError(
                "register_adapter() before warmup(): the adapter-set "
                "program compiles during warmup — call warmup() "
                "first, then register (the prefix-pool lifecycle)")
        if (weights is None) == (seed is None):
            raise ValueError(
                "pass exactly one of weights= or seed=")
        if name is None:
            name = (f"adapter-seed-{seed}" if seed is not None
                    else f"adapter-{self._adapter_used}")
        hit = self._adapter_names.get(name)
        if hit is not None:
            return hit
        if seed is not None:
            weights = gpt.init_lora_weights(
                self.cfg, self.engine_cfg.adapter_rank, seed)
        # validate the payload BEFORE the capacity check: a malformed
        # adapter should fail as malformed whether or not the pool
        # happens to be full
        expected = self._lora_expected_shapes()
        row: Dict[str, Dict[str, np.ndarray]] = {}
        for site, parts in expected.items():
            if site not in weights:
                raise ValueError(f"adapter weights missing site "
                                 f"{site!r}")
            row[site] = {}
            for part, shape in parts.items():
                arr = np.asarray(weights[site][part], np.float32)
                if arr.shape != shape:
                    raise ValueError(
                        f"adapter {site}.{part} shape {arr.shape} != "
                        f"expected {shape} (rank/layers/hidden are "
                        f"compile-time static — ADAPTER-STATIC)")
                row[site][part] = arr
        if self._host_swap:
            # paged registry: ids are LOGICAL (no cap — hundreds of
            # registrations against a static pool); the row lives in
            # the host registry and pages into a physical pool row at
            # admission (immediately while free rows remain, so the
            # under-capacity path matches the historical engine)
            idx = self._adapter_used
            self._adapter_rows_host[idx] = row
            self._adapter_used += 1
            if self._adapter_free_rows:
                try:
                    self._adapter_physical(idx)
                except Exception:
                    self._adapter_rows_host.pop(idx, None)
                    self._adapter_used -= 1
                    raise
            self._adapter_names[name] = idx
            self._adapter_meta[idx] = {
                "id": idx, "name": name, "seed": seed,
                "rank": self.engine_cfg.adapter_rank}
            return idx
        if self._adapter_used >= self.engine_cfg.adapter_slots:
            raise ValueError(
                f"adapter pool full ({self.engine_cfg.adapter_slots} "
                f"rows incl. the pinned base row 0)")
        idx = self._adapter_used
        # NOT donated: a failed set leaves every serving row intact
        self.adapters = self._adapter_set(self.adapters, row,
                                          np.int32(idx))
        self._adapter_used += 1
        self._adapter_names[name] = idx
        self._adapter_meta[idx] = {"id": idx, "name": name,
                                   "seed": seed,
                                   "rank": self.engine_cfg.adapter_rank}
        return idx

    def describe(self) -> Dict[str, Any]:
        """JSON-safe snapshot of everything needed to REBUILD this
        engine elsewhere — the post-mortem bundle's ``config.json``
        (``apex_tpu.telemetry.replay`` reconstructs the GPTConfig /
        EngineConfig / prefix templates from it). Dtypes serialise by
        numpy name (``compute_dtype: "float32"``); anything else
        non-primitive falls back to ``str`` (reported, not
        replayable)."""
        model: Dict[str, Any] = {}
        for f in dataclasses.fields(self.cfg):
            v = getattr(self.cfg, f.name)
            if not isinstance(v, (int, float, str, bool, type(None))):
                try:  # dtype-valued fields (compute_dtype, param_dtype)
                    v = np.dtype(v).name
                except TypeError:
                    v = str(v)
            model[f.name] = v
        return {
            "model": model,
            "engine": dataclasses.asdict(self.engine_cfg),
            "tp": int(self._mesh.shape.get("tp", 1)),
            "prompt_buckets": list(self._buckets),
            "admit_batch_sizes": list(self._batch_sizes),
            "decode_chunks": list(self._chunk_ladder),
            "spec_ks": list(self._spec_ladder),
            "prefix_templates": [list(self._prefix_tokens[p])
                                 for p in sorted(self._prefix_tokens)],
            # seeded registrations replay bit-identically (the seed
            # regenerates the exact weights); explicit-weight ones
            # record seed=None and replay skips their requests
            "adapters": [dict(self._adapter_meta[i])
                         for i in sorted(self._adapter_meta)],
            "warmed": self._warmed,
            "poisoned": self._poisoned,
        }

    def cache_bytes(self) -> int:
        """Device bytes held by the slot KV cache — under a quantized
        ``kv_cache_dtype`` the int8/fp8 data plane plus the fp32 scale
        plane (the capacity number the quantization exists to shrink).
        Shape/dtype metadata only; no transfer."""
        return int(sum(x.nbytes for x in jax.tree.leaves(self.cache)))

    def pool_bytes(self) -> int:
        """Device bytes held by the shared-prefix pool (0 when
        disabled)."""
        if self.pool is None:
            return 0
        return int(sum(x.nbytes for x in jax.tree.leaves(self.pool)))

    def bucket_for(self, prompt_len: int) -> int:
        """The smallest prefill bucket that fits ``prompt_len``."""
        for b in self._buckets:
            if b >= prompt_len:
                return b
        raise ValueError(
            f"prompt length {prompt_len} exceeds max_prompt_len "
            f"{self.engine_cfg.max_prompt_len}")

    def pad_prompt(self, prompt, length: Optional[int] = None) -> np.ndarray:
        """Right-pad ``prompt`` (1-D ints) to ``length`` (default
        ``max_prompt_len``), validating its length — the static
        admission shape of one bucket."""
        length = self.engine_cfg.max_prompt_len if length is None else length
        prompt = np.asarray(prompt, np.int32)
        if prompt.ndim != 1 or not 1 <= prompt.size <= length:
            raise ValueError(
                f"prompt must be 1-D with 1..{length}"
                f" tokens, got shape {prompt.shape}")
        out = np.full((length,), self.engine_cfg.pad_token_id, np.int32)
        out[:prompt.size] = prompt
        return out

    def _validate_admission(self, a: Admission) -> Tuple[np.ndarray, int]:
        """Shared per-request admission validation; returns the raw
        prompt array and its length (padding happens per group, once
        the group's bucket is known)."""
        if not 0 <= a.slot < self.slots:
            raise ValueError(
                f"slot {a.slot} outside [0, {self.slots}) — a traced "
                f"out-of-range index would silently clamp into a "
                f"neighbouring slot's cache")
        # same stop-token contract as gpt.generate (rejects vocab-range
        # violations AND an explicit -1, which would alias the
        # no-eos sentinel)
        gpt._check_stop_tokens(self.cfg, a.eos_token_id, None)
        prompt = np.asarray(a.prompt, np.int32)
        if prompt.ndim != 1 or not \
                1 <= prompt.size <= self.engine_cfg.max_prompt_len:
            raise ValueError(
                f"prompt must be 1-D with "
                f"1..{self.engine_cfg.max_prompt_len} tokens, got shape "
                f"{prompt.shape}")
        room = self.engine_cfg.max_seq_len - prompt.size
        if a.max_tokens < 1 or a.max_tokens > room:
            raise ValueError(
                f"max_tokens {a.max_tokens} outside [1, {room}] for a "
                f"{prompt.size}-token prompt at max_seq_len "
                f"{self.engine_cfg.max_seq_len}")
        if a.allowed_tokens is not None:
            # pre-flight (admit_many is all-or-nothing: nothing may
            # dispatch if any row is invalid); the expansion itself is
            # owned by set_slot_mask
            self._check_allowed_tokens(a.allowed_tokens)
        if a.adapter:
            if not self._lora:
                raise ValueError(
                    f"admission carries adapter {a.adapter} but the "
                    f"adapter pool is disabled "
                    f"(EngineConfig.adapter_slots == 0)")
            if not 1 <= a.adapter < self._adapter_used:
                raise ValueError(
                    f"adapter {a.adapter} outside the registered rows "
                    f"[1, {self._adapter_used}) — register_adapter() "
                    f"first (0 is the pinned base adapter)")
            if a.prefix_page is not None:
                raise ValueError(
                    "prefix-pool hits require the base adapter (id "
                    "0): the pooled prefix was prefilled with base "
                    "weights, so an adapter-carrying hit would decode "
                    "against K/V a cold adapter prefill would not "
                    "produce")
        if a.prefix_page is not None:
            ps = a.prefix_len
            if not self._prefix_splits:
                raise ValueError(
                    "admission carries a prefix_page but the prefix "
                    "pool is disabled (EngineConfig.prefix_pool_slots "
                    "== 0)")
            if ps not in self._prefix_splits:
                raise ValueError(
                    f"prefix_len {ps} is not a usable split point "
                    f"{self._prefix_splits}")
            if not 0 <= a.prefix_page < self._prefix_used:
                raise ValueError(
                    f"prefix_page {a.prefix_page} outside the "
                    f"{self._prefix_used} registered pages")
            if prompt.size <= ps:
                raise ValueError(
                    f"prompt of {prompt.size} tokens leaves no tail "
                    f"beyond prefix_len {ps}")
            tb = self.bucket_for(prompt.size - ps)
            if (ps, tb) not in self._admit_prefix:
                raise ValueError(
                    f"no compiled extend variant for (split {ps}, "
                    f"tail bucket {tb}) — the combined block exceeds "
                    f"max_seq_len")
            stored = self._prefix_tokens[a.prefix_page]
            if tuple(int(x) for x in prompt[:ps]) != stored[:ps]:
                raise ValueError(
                    f"prompt[:{ps}] does not match the tokens "
                    f"registered on prefix page {a.prefix_page} — a "
                    f"mismatched copy would silently decode against "
                    f"another template's K/V")
        elif a.prefix_len:
            raise ValueError(
                "prefix_len without prefix_page — pass both (a "
                "match_prefix hit) or neither")
        return prompt, prompt.size

    def _check_allowed_tokens(self, allowed: Sequence[int]) -> List[int]:
        """THE constrained-decoding whitelist validation (shared by
        admission pre-flight and :meth:`set_slot_mask`)."""
        allowed = [int(t) for t in allowed]
        if not allowed or any(not 0 <= t < self.cfg.vocab_size
                              for t in allowed):
            raise ValueError(
                f"allowed token whitelist must be a non-empty subset "
                f"of vocab [0, {self.cfg.vocab_size})")
        return allowed

    def admit(self, slot: int, prompt, max_tokens: int, *,
              temperature: float = 0.0, top_k: int = 0, top_p: float = 1.0,
              seed: Optional[int] = None,
              eos_token_id: Optional[int] = None) -> Tuple[int, bool, bool]:
        """Admit one request into ``slot``: prefill + first token (the
        k=1 lane of :meth:`admit_many`). Returns ``(first_token,
        hit_eos, finished)`` — ``finished`` True when the request is
        already complete after its first token (eos, or a budget of 1).
        ``max_tokens`` must fit the slot's cache horizon."""
        res = self.admit_many([Admission(
            slot=slot, prompt=prompt, max_tokens=max_tokens,
            temperature=temperature, top_k=top_k, top_p=top_p, seed=seed,
            eos_token_id=eos_token_id)])[0]
        return res.first_token, res.hit_eos, res.finished

    def admit_many(self, items: Sequence[Admission]) -> List[AdmitResult]:
        """Admit a batch of requests in as few dispatches as the ladders
        allow: ``items`` (FIFO order, distinct slots) is split into
        ``admit_batch_sizes`` groups largest-first; each group prefills
        at the smallest bucket that fits its longest prompt and runs
        ONE compiled ``(bucket, k)`` program — one forward + one cache/
        state scatter for the whole group. Per-row results are
        bit-identical to k single :meth:`admit` calls in the same
        order (the admission-parity oracle pins this)."""
        items = list(items)
        if not items:
            return []
        self._check_poisoned()
        spec = self._take_fault("admit")
        if spec is not None and spec.kind == KIND_ERROR:
            # a device error escaping the admission call: the donated
            # cache/state must be assumed consumed — poison until rebuilt
            self._poisoned = True
            raise InjectedFault(
                f"injected device error at admit: {spec.describe()}",
                point="admit", spec=spec)
        if self._latent:
            return latent_engine.admit_many(self, items, AdmitResult)
        validated = [self._validate_admission(a) for a in items]
        slots_used = [a.slot for a in items]
        if len(set(slots_used)) != len(slots_used):
            raise ValueError(
                f"admit_many slots must be distinct, got {slots_used}")
        if self._paged:
            # all-or-nothing: refuse the whole batch BEFORE any
            # dispatch when the pool cannot cover it (conservative —
            # stale mappings on the target slots are not counted as
            # reclaimable; the scheduler releases slots first)
            total = sum(
                self.pages_needed(n, a.max_tokens, a.prefix_len)
                for a, (_, n) in zip(items, validated))
            if not self._page_alloc.can_alloc(total):
                raise PagesExhausted(total, self._page_alloc.free_pages)
        pending = []  # (device futures, bucket, k, group) per dispatch
        i, group = 0, 0
        while i < len(items):
            if items[i].prefix_page is not None:
                # a prefix-pool hit rides its own compiled (split,
                # tail-bucket) extend program, k=1: the copied prefix
                # replaces most of the prefill forward, so batching it
                # with cold admissions would drag it back to the full
                # bucket
                pending.append(
                    (self._dispatch_prefix_admit(items[i],
                                                 validated[i]),
                     self.bucket_for(
                         validated[i][1] - items[i].prefix_len),
                     1, group))
                i += 1
                group += 1
                continue
            run = i
            while run < len(items) and items[run].prefix_page is None:
                run += 1
            k = max(s for s in self._batch_sizes if s <= run - i)
            batch = items[i:i + k]
            proms = validated[i:i + k]
            bucket = self.bucket_for(max(n for _, n in proms))
            prompts = np.stack([self.pad_prompt(p, bucket)
                                for p, _ in proms])
            keys = np.stack([
                _threefry_key_data(a.seed) if a.seed is not None
                else np.zeros((2,), np.uint32) for a in batch])
            seeded = np.asarray([a.seed is not None for a in batch], bool)
            req_idx = np.arange(self._req_counter,
                                self._req_counter + k, dtype=np.int32)
            self._req_counter += k
            # first-token masks, and the per-slot mask rows the decode
            # steps will use (set BEFORE the dispatch that reads them;
            # unconstrained rows reset any stale mask the slot carried)
            # — one expansion, owned by set_slot_mask
            for a in batch:
                self.set_slot_mask(a.slot, a.allowed_tokens)
            masks = np.stack([self._masks[a.slot] for a in batch])
            arr = lambda vals, dt: np.asarray(vals, dt)
            fn = self._admits[(bucket, k)]
            extra: Tuple[Any, ...] = ()
            if self._paged:
                # map each row's table BEFORE the dispatch that reads
                # it; the insert writes the first ceil(bucket/P)
                # entries of each row (sink-padded past the
                # allocation)
                n_ins = -(-bucket // self.engine_cfg.page_size)
                rows = [self._alloc_slot_pages(a.slot, n, a.max_tokens)
                        for a, (_, n) in zip(batch, proms)]
                extra += (np.stack([r[:n_ins] for r in rows]),)
            if self._spec:
                extra += (np.stack([self._hist_seed(p)
                                    for p, _ in proms]),)
            if self._lora:
                # the slot's decode-path id-table entry is set BEFORE
                # the dispatch that admits it (the vocab-mask
                # contract); the admission forward reads the per-row
                # (physical — any cold row pages in here, BEFORE the
                # pool is captured into extra) ids argument
                phys = [self._adapter_physical(a.adapter)
                        for a in batch]
                for a, pr in zip(batch, phys):
                    self._set_slot_adapter(a.slot, pr)
                extra += (self.adapters,
                          np.asarray(phys, np.int32))
            self.cache, self.state, first, first_lp, hit_eos, done = fn(
                self._params, self.cache, self.state,
                arr([a.slot for a in batch], np.int32), prompts,
                arr([n for _, n in proms], np.int32),
                arr([a.max_tokens for a in batch], np.int32),
                arr([a.temperature for a in batch], np.float32),
                arr([a.top_k for a in batch], np.int32),
                arr([a.top_p for a in batch], np.float32),
                keys,
                arr([_NO_EOS if a.eos_token_id is None
                     else int(a.eos_token_id) for a in batch], np.int32),
                req_idx, seeded, masks, *extra)
            pending.append(((first, first_lp, hit_eos, done), bucket, k,
                            group))
            i += k
            group += 1
        # fetch AFTER every group is dispatched — later groups ride the
        # async queue behind earlier ones instead of waiting for each
        # fetch round trip
        results: List[AdmitResult] = []
        for (first, first_lp, hit_eos, done), bucket, k, group in pending:
            first = np.asarray(first)
            first_lp = np.asarray(first_lp)
            hit_eos, done = np.asarray(hit_eos), np.asarray(done)
            for j in range(k):
                tok = int(first[j])
                if spec is not None and spec.kind == KIND_NAN \
                        and len(results) in spec.slots:
                    tok = spec.token  # NaN prefill: garbage first token
                results.append(AdmitResult(
                    tok, bool(hit_eos[j]), bool(done[j]),
                    bucket=bucket, batch_size=k, group=group,
                    logprob=float(first_lp[j])))
        return results

    def _dispatch_prefix_admit(self, a: Admission,
                               validated: Tuple[np.ndarray, int]):
        """Dispatch ONE prefix-hit admission through its (split, tail
        bucket) extend program; returns the (first, first_lp, hit_eos,
        done) device futures (fetch deferred like every admission
        group)."""
        prompt, n = validated
        ps = a.prefix_len
        tb = self.bucket_for(n - ps)
        tails = np.full((1, tb), self.engine_cfg.pad_token_id, np.int32)
        tails[0, :n - ps] = prompt[ps:]
        keys = (_threefry_key_data(a.seed) if a.seed is not None
                else np.zeros((2,), np.uint32))[None]
        seeded = np.asarray([a.seed is not None], bool)
        req_idx = np.asarray([self._req_counter], np.int32)
        self._req_counter += 1
        self.set_slot_mask(a.slot, a.allowed_tokens)
        masks = self._masks[a.slot][None]
        fn = self._admit_prefix[(ps, tb)]
        extra: Tuple[Any, ...] = ()
        if self._paged:
            # copy-on-write mapping: shared prefix pages pinned into
            # the row, private pages allocated for the tail + decode;
            # the insert gets the row entries from the split onward
            p_szc = self.engine_cfg.page_size
            row = self._alloc_slot_pages(
                a.slot, n, a.max_tokens, prefix_page=a.prefix_page,
                prefix_len=ps)
            n_tail = -(-tb // p_szc)
            pages = np.full((n_tail,), SINK, np.int32)
            avail = row[ps // p_szc: ps // p_szc + n_tail]
            pages[:avail.size] = avail
            extra += (pages[None],)
        if self._spec:
            extra += (self._hist_seed(prompt)[None],)
        if self._lora:
            # validated adapter == 0 on the prefix path — the slot's
            # table entry resets to base and the zero row rides along
            self._set_slot_adapter(a.slot, a.adapter)
            extra += (self.adapters,
                      np.asarray([a.adapter], np.int32))
        self.cache, self.state, first, first_lp, hit_eos, done = fn(
            self._params, self.cache, self.state, self.pool,
            np.asarray([a.slot], np.int32), tails,
            np.asarray([n - ps], np.int32),
            np.asarray([a.max_tokens], np.int32),
            np.asarray([a.temperature], np.float32),
            np.asarray([a.top_k], np.int32),
            np.asarray([a.top_p], np.float32), keys,
            np.asarray([_NO_EOS if a.eos_token_id is None
                        else int(a.eos_token_id)], np.int32),
            req_idx, seeded, masks, np.int32(a.prefix_page), *extra)
        return first, first_lp, hit_eos, done

    # -- chunked prefill (EngineConfig.prefill_chunk > 0) ------------------

    @property
    def chunked_prefill_enabled(self) -> bool:
        """True when ``EngineConfig.prefill_chunk > 0``."""
        return self._chunk_size > 0

    def chunked_for(self, prompt_len: int) -> bool:
        """Whether a prompt of this length admits through chunked
        prefill (longer than one chunk) instead of :meth:`admit_many`."""
        return self._chunk_size > 0 and prompt_len > self._chunk_size

    def admit_chunked_start(self, a: Admission) -> ChunkedAdmission:
        """Begin a chunked-prefill admission: validate, map the slot's
        pages (paged mode — :class:`PagesExhausted` backpressure fires
        HERE, before any device work), and dispatch chunk 0 (the
        bucket-sized cold prefill into the compute-dtype scratch).
        Exactly one chunked admission may be in progress (the scratch
        holds one prompt); the scheduler interleaves decode waves
        between the subsequent :meth:`admit_chunked_step` calls."""
        self._check_poisoned()
        if not self._chunk_size:
            raise ValueError(
                "chunked prefill disabled "
                "(EngineConfig.prefill_chunk == 0)")
        if self._chunked is not None:
            raise RuntimeError(
                "a chunked admission is already in progress — the "
                "scratch buffer holds one prompt at a time")
        if a.prefix_page is not None:
            raise ValueError(
                "chunked prefill does not compose with prefix-pool "
                "hits (a hit already skips the prefix forward — "
                "nothing long is left to chunk)")
        prompt, n = self._validate_admission(a)
        if n <= self._chunk_size:
            raise ValueError(
                f"prompt of {n} tokens fits one {self._chunk_size}-"
                f"token chunk — use admit_many")
        if self._paged:
            self._alloc_slot_pages(a.slot, n, a.max_tokens)
        if self._lora:
            self._bind_slot_adapter(a.slot, a.adapter)
        c = self._chunk_size
        ca = ChunkedAdmission(a, prompt, n, -(-n // c))
        tok0 = prompt[:c].astype(np.int32)[None]
        lx = self._lora_args(a.adapter)
        try:
            self._chunk_scratch = self._chunk0(
                self._params, self._chunk_scratch, tok0, *lx)
        except Exception:
            # scratch donated into the failing call
            self._poisoned = True
            raise
        self._chunked = ca
        return ca

    def admit_chunked_step(self, ca: ChunkedAdmission
                           ) -> Optional[AdmitResult]:
        """Advance one chunked admission by ONE device dispatch: the
        next ``prefill_extend`` chunk while prefilling (returns None),
        then the finish — first-token draw + whole-prompt cache insert
        + slot-state scatter — returning the :class:`AdmitResult`.
        The scheduler runs decode waves between calls; that is the
        entire stall-free-admission mechanism."""
        self._check_poisoned()
        if ca is not self._chunked:
            raise ValueError(
                "stale ChunkedAdmission — not the one in progress")
        c = self._chunk_size
        a = ca.admission
        if not ca.done_prefilling:
            i = ca.next_chunk
            chunk = ca.prompt[i * c: min((i + 1) * c, ca.p_len)]
            tail = np.full((1, c), self.engine_cfg.pad_token_id,
                           np.int32)
            tail[0, :chunk.size] = chunk
            try:
                self._chunk_scratch, ca._logits = self._chunk_exts[i](
                    self._params, self._chunk_scratch, tail,
                    np.asarray([chunk.size - 1], np.int32),
                    *self._lora_args(a.adapter))
            except Exception:
                self._poisoned = True
                self._chunked = None
                raise
            ca.next_chunk += 1
            return None
        # the finish dispatch — the admission's only cache/state write
        keys = (_threefry_key_data(a.seed) if a.seed is not None
                else np.zeros((2,), np.uint32))[None]
        seeded = np.asarray([a.seed is not None], bool)
        req_idx = np.asarray([self._req_counter], np.int32)
        self._req_counter += 1
        self.set_slot_mask(a.slot, a.allowed_tokens)
        masks = self._masks[a.slot][None]
        extra: Tuple[Any, ...] = ()
        if self._paged:
            n_fin = -(-self.engine_cfg.max_prompt_len
                      // self.engine_cfg.page_size)
            extra += (self._tables[a.slot][:n_fin][None],)
        if self._spec:
            extra += (self._hist_seed(ca.prompt)[None],)
        try:
            self.cache, self.state, first, first_lp, hit_eos, done = \
                self._chunk_finish(
                    self._params, self.cache, self.state,
                    self._chunk_scratch, ca._logits,
                    np.asarray([a.slot], np.int32),
                    np.asarray([ca.p_len], np.int32),
                    np.asarray([a.max_tokens], np.int32),
                    np.asarray([a.temperature], np.float32),
                    np.asarray([a.top_k], np.int32),
                    np.asarray([a.top_p], np.float32), keys,
                    np.asarray([_NO_EOS if a.eos_token_id is None
                                else int(a.eos_token_id)], np.int32),
                    req_idx, seeded, masks, *extra)
        except Exception:
            self._poisoned = True
            self._chunked = None
            raise
        self._chunked = None
        return AdmitResult(
            int(np.asarray(first)[0]), bool(np.asarray(hit_eos)[0]),
            bool(np.asarray(done)[0]), bucket=c, batch_size=1,
            group=0, logprob=float(np.asarray(first_lp)[0]))

    def _set_slot_adapter(self, slot: int, adapter: int) -> None:
        """Point ``slot``'s decode-path adapter-id table entry at
        ``adapter`` (host mirror; the cached device copy invalidates
        only when a row actually changes — the vocab-mask upload
        discipline, so single-tenant steady state never re-uploads)."""
        if self._adapter_ids[slot] == adapter:
            return
        self._adapter_ids[slot] = adapter
        self._aids_dev = None

    def _adapter_physical(self, adapter: int) -> int:
        """Resolve a request's adapter id to its resident pool row,
        paging the row in from the host registry when cold (host_swap
        engines — ids stay DATA and the set program is pre-warmed, so
        a page-in never recompiles; identity elsewhere, where virtual
        == physical by construction). Eviction skips rows bound to a
        live slot's id-table entry — spilling one would silently swap
        weights under a decoding stream."""
        if not (self._host_swap and self._lora) or adapter == 0:
            return adapter
        phys = self._adapter_phys.get(adapter)
        if phys is not None:
            self._adapter_lru.touch(phys)
            return phys
        if self._adapter_free_rows:
            phys = self._adapter_free_rows.pop()
        else:
            pinned = {int(r) for r in self._adapter_ids if r}
            phys = self._adapter_lru.pop_coldest(pinned)
            if phys is None:
                raise ValueError(
                    f"adapter pool thrash: every resident row "
                    f"(adapter_slots={self.engine_cfg.adapter_slots}) "
                    f"is bound to a live slot — raise adapter_slots")
            stale = self._adapter_virt.pop(phys)
            self._adapter_phys.pop(stale, None)
            self._adapter_spills += 1
        # NOT donated — a failed page-in leaves every serving row
        # intact (and the maps untouched: they update after the call)
        self.adapters = self._adapter_set(
            self.adapters, self._adapter_rows_host[adapter],
            np.int32(phys))
        self._adapter_phys[adapter] = phys
        self._adapter_virt[phys] = adapter
        self._adapter_lru.touch(phys)
        self._adapter_pageins += 1
        return phys

    def _adapter_virtual(self, phys: int) -> int:
        """Inverse of :meth:`_adapter_physical` for a bound row — the
        id a park payload stores, so resume re-resolves (the physical
        row may have been spilled while parked)."""
        if not (self._host_swap and self._lora) or phys == 0:
            return phys
        return self._adapter_virt.get(phys, 0)

    def _bind_slot_adapter(self, slot: int, adapter: int) -> None:
        """Resolve-and-bind: the admission/resume seam (virtual in,
        physical in the slot's id-table entry)."""
        self._set_slot_adapter(slot, self._adapter_physical(adapter))

    def adapter_paging_stats(self) -> Optional[Dict[str, float]]:
        """Adapter-paging snapshot (None unless host_swap + adapters):
        logical registrations vs resident pool rows, spill/page-in
        traffic."""
        if not (self._host_swap and self._lora):
            return None
        return {
            "registered": float(self.adapters_registered),
            "resident": float(len(self._adapter_virt)),
            "rows": float(self.engine_cfg.adapter_slots - 1),
            "spills_total": float(self._adapter_spills),
            "pageins_total": float(self._adapter_pageins),
        }

    def _lora_args(self, adapter: int) -> Tuple[Any, ...]:
        """The trailing (pool, ids) args of a k=1 forward program
        (chunked prefill's chunk/extend dispatches) — empty when the
        pool is disabled."""
        if not self._lora:
            return ()
        aid = self._adapter_physical(adapter)
        return (self.adapters, np.asarray([aid], np.int32))

    def _hist_seed(self, prompt) -> np.ndarray:
        """The drafter-ring admission seed for one prompt: its last
        ``spec_hist - 1`` tokens, left-padded with the ``-1`` sentinel
        (the device appends the admission's first sampled token to
        complete the ring). Host-side numpy — the variable-length
        logic stays out of the compiled programs."""
        h = self.engine_cfg.spec_hist
        row = np.full((h - 1,), -1, np.int32)
        tail = np.asarray(prompt, np.int32)[-(h - 1):]
        if tail.size:
            row[h - 1 - tail.size:] = tail
        return row

    def step_async(self, *, spec: bool = False,
                   chunk: Optional[int] = None,
                   spec_k: Optional[int] = None) -> StepHandle:
        """Dispatch one decode chunk WITHOUT fetching its outputs: the
        engine rebinds its (donated) cache/state to the returned device
        futures immediately, so the caller may enqueue further work —
        the next chunk, an admission — behind it before syncing, and
        the device never idles through the host's fetch + event
        processing. Returns the chunk's :class:`StepHandle`.

        ``spec=True`` dispatches the SPECULATIVE chunk variant (a
        compiled ``spec_ks`` rung required — every variant is
        pre-warmed, so a payoff-gated or tuner-driven scheduler
        switches per dispatch without a recompile): the handle's
        tokens/logprobs/finished are ``[B, chunk * (spec_k + 1)]``
        with ``handle.valid`` marking the real emissions (rejected
        draft lanes emit pad).

        ``chunk``/``spec_k`` select among the pre-warmed step variants
        (``EngineConfig.decode_chunks`` / ``spec_ks`` — the self-tuning
        scheduler's per-dispatch knob values); ``None`` means the base
        ``decode_chunk`` / ``spec_k``. A value outside the compiled
        ladder raises instead of compiling mid-serve: dispatching an
        unwarmed variant is exactly the trace-stability breach the
        armed recompile guard exists to catch."""
        self._check_poisoned()
        c = self.engine_cfg.decode_chunk if chunk is None else int(chunk)
        if c not in self._step_variants:
            raise ValueError(
                f"decode_chunk {c} is not a pre-warmed step variant "
                f"{self._chunk_ladder} — declare it in "
                f"EngineConfig.decode_chunks (dispatching it would "
                f"compile mid-serve)")
        if spec:
            if not self._spec:
                raise ValueError(
                    "step_async(spec=True) needs a compiled spec "
                    "variant (EngineConfig.spec_k > 0 or spec_ks)")
            k = (self.engine_cfg.spec_k if spec_k is None
                 else int(spec_k))
            if (c, k) not in self._spec_variants:
                raise ValueError(
                    f"spec_k {k} (at decode_chunk {c}) is not a "
                    f"pre-warmed spec variant — declare it in "
                    f"EngineConfig.spec_ks {self._spec_ladder}")
        elif spec_k not in (None, 0):
            raise ValueError(
                f"spec_k={spec_k} without spec=True — a plain chunk "
                f"has no draft width")
        fspec = self._take_fault("dispatch")
        if fspec is not None and fspec.kind == KIND_ERROR:
            self._poisoned = True
            raise InjectedFault(
                f"injected device error at dispatch: "
                f"{fspec.describe()}", point="dispatch", spec=fspec)
        if self._masks_dev is None:
            self._masks_dev = jnp.asarray(self._masks)
        step_extra: Tuple[Any, ...] = ()
        if self._paged:
            # the block tables ride every dispatch as DATA (one static
            # [B, max_pages] int32 argument — same contract as the
            # masks; the device copy is cached until a row changes)
            if self._tables_dev is None:
                self._tables_dev = jnp.asarray(self._tables)
            step_extra = (self._tables_dev,)
        if self._lora:
            # the adapter pool + per-slot id table ride every dispatch
            # as DATA (ids cached like the masks/tables; the pool is
            # the engine-owned device buffer registrations update)
            if self._aids_dev is None:
                self._aids_dev = jnp.asarray(self._adapter_ids)
            step_extra += (self.adapters, self._aids_dev)
        valid = None
        if spec:
            (self.cache, self.state, emit, logprobs, finished,
             valid) = self._spec_variants[(c, k)](
                self._params, self.cache, self.state, self._masks_dev,
                *step_extra)
            spec_k, ncols = k, c * (k + 1)
        else:
            self.cache, self.state, emit, logprobs, finished = \
                self._step_variants[c](
                    self._params, self.cache, self.state,
                    self._masks_dev, *step_extra)
            spec_k, ncols = 0, c
        plan = None if self._warming else self.fault_plan
        return StepHandle(emit, logprobs, finished, plan=plan,
                          hang=fspec if fspec is not None
                          and fspec.kind == KIND_HANG else None,
                          on_poison=self._mark_poisoned,
                          valid=valid, spec_k=spec_k, ncols=ncols)

    def step(self) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        """One decode chunk over every slot — ``decode_chunk`` fused
        per-token steps in one dispatch, fetched synchronously
        (:meth:`step_async` + :meth:`StepHandle.fetch`). Returns
        ``(tokens [B, n], logprobs [B, n], finished [B, n])`` with
        ``n = decode_chunk``; column ``j`` holds step ``j``'s emissions,
        ``pad_token_id`` for slots that were done entering that step (a
        slot that finishes at column ``j`` emits pad from ``j + 1``
        on)."""
        return self.step_async().fetch()

    def set_slot_mask(self, slot: int,
                      allowed: Optional[Sequence[int]] = None) -> None:
        """Replace ``slot``'s constrained-decoding vocab mask with the
        whitelist ``allowed`` (``None`` = unconstrained, all-True). The
        schema DFA advances host-side per emitted token; the scheduler
        calls this between chunk dispatches, so the next compiled step
        reads the advanced mask — no recompile (the mask is data, one
        static ``[B, vocab]`` bool argument of the step program)."""
        if not 0 <= slot < self.slots:
            raise ValueError(f"slot {slot} outside [0, {self.slots})")
        if allowed is None:
            # the hot unconstrained case (every admission resets its
            # slot's row): an already-all-True row must NOT invalidate
            # the cached device copy — that would re-upload the whole
            # [B, vocab] array after every admission wave
            if self._masks[slot].all():
                return
            self._masks[slot, :] = True
        else:
            allowed = self._check_allowed_tokens(allowed)
            row = np.zeros((self.cfg.vocab_size,), bool)
            row[allowed] = True
            if (self._masks[slot] == row).all():
                return  # unchanged (e.g. a DFA state with the same set)
            self._masks[slot] = row
        self._masks_dev = None

    def retire(self, slot: int) -> None:
        """Force ``slot`` done (scheduler deadline expiry). The slot's
        lane keeps riding the compiled step unmodified; its output is
        pad until the next admission overwrites the state. Takes effect
        for chunks dispatched AFTER this call — chunks already in
        flight still carry the slot's real tokens (a pipelined
        scheduler drops them)."""
        self._check_poisoned()
        spec = self._take_fault("retire")
        if spec is not None and spec.kind == KIND_ERROR:
            self._poisoned = True
            raise InjectedFault(
                f"injected device error at retire: {spec.describe()}",
                point="retire", spec=spec)
        self.state = self._retire(self.state, np.int32(slot))

    # -- failure isolation (apex_tpu.serving.resilience) -------------------

    def _take_fault(self, point: str):
        plan = self.fault_plan
        if plan is None or self._warming:
            return None
        return plan.take(point)

    def _mark_poisoned(self) -> None:
        self._poisoned = True

    def _check_poisoned(self) -> None:
        if self._poisoned:
            raise EngineFault(
                "engine state is poisoned (a prior fault invalidated "
                "the donated cache/state buffers); call rebuild_slots() "
                "before the next device call")

    @property
    def poisoned(self) -> bool:
        """True after a fault invalidated the donated cache/state
        buffers (every device call raises until
        :meth:`rebuild_slots`)."""
        return self._poisoned

    def rebuild_slots(self) -> None:
        """Recovery: rebuild the donated cache/state buffers from the
        compiled ``init`` program (every slot comes back FREE — the
        scheduler deterministically replays interrupted requests from
        its host-side slot snapshot, see
        :mod:`apex_tpu.serving.resilience`). No recompilation: ``init``
        was compiled at construction, so a recompile guard stays armed
        through recovery. The shared-prefix pool is untouched — it is
        never donated to a failing step/admit call, so registered
        templates survive recovery and replayed prefix hits reuse
        them."""
        if self._paged:
            # slot mappings die with the slots; registered prefixes
            # keep their registration pin (the pool block survives,
            # and the quantized page-in is replayed below into the
            # fresh cache)
            for slot in list(self._slot_pages):
                self._free_slot_pages(slot)
            self._tables[:, :] = SINK
            self._tables_dev = None
        self._chunked = None
        self.cache, self.state = self._init(self._params)
        if self._chunk_size:
            self._chunk_scratch = self._chunk_scratch_init(self._params)
        if self._latent:
            latent_engine.refill_prefixes(self)
        elif self._paged and self._prefix_pages:
            for page in sorted(self._prefix_pages):
                pb = len(self._prefix_tokens[page])
                self.cache = self._pool_pageins[pb](
                    self.cache, self.pool, np.int32(page),
                    np.asarray([self._prefix_pages[page]], np.int32))
        self._masks[:, :] = True
        self._masks_dev = None
        if self._lora:
            # the adapter POOL survives (never donated — registered
            # tenants keep serving); only the per-slot id table resets
            # with the slots it describes
            self._adapter_ids[:] = 0
            self._aids_dev = None
        self._poisoned = False

    def warmup(self) -> "Engine":
        """Compile every engine program up front — ``init``, ``step``,
        ``retire``, and ALL ``(bucket, k)`` admission variants — then
        reset the slot state, so :meth:`recompile_guard` can be armed
        immediately after and stay flat across any serve cycle (the
        host admission path is jax-free — seeded keys are packed with
        numpy — so nothing else can compile mid-serve). Call BEFORE
        admitting real requests (the reset frees every slot);
        idempotent. Replaces the hand-rolled one-admit-one-step
        warmups tests and examples used to do."""
        if self._warmed:
            return self
        self._warming = True  # warmup must not consume fault-plan seams
        try:
            with expected_compiles():
                # warmup IS the sanctioned compile pass: its events
                # must never be attributed to another live engine's
                # armed guard (the fleet router warms replacement
                # replicas mid-serve)
                self._warmup_body()
        finally:
            self._warming = False
        self._warmed = True
        return self

    def _warmup_body(self) -> None:
        ecfg = self.engine_cfg
        hseed = lambda k: (
            (np.full((k, ecfg.spec_hist - 1), -1, np.int32),)
            if self._spec else ())
        # paged warm args: sink-page indices — every warmup insert
        # lands in the garbage page, so no allocator state is touched
        wpages = lambda k, span: (
            (np.full((k, -(-span // ecfg.page_size)), SINK, np.int32),)
            if self._paged else ())
        # lora warm args: every row rides the pinned zero adapter —
        # shapes are what compile, and id 0 is the base row anyway
        wlora = lambda k: ((self.adapters, np.zeros((k,), np.int32))
                           if self._lora else ())
        if self._latent:
            latent_engine.warmup(self)
        for (bucket, k), fn in (() if self._latent
                                else sorted(self._admits.items())):
            # dummy args exercise shapes only: k pad-token prompts of
            # length 1, budget 1 (done at admission), no sampling
            self.cache, self.state, first, _, _, _ = fn(
                self._params, self.cache, self.state,
                np.arange(k, dtype=np.int32),
                np.full((k, bucket), ecfg.pad_token_id, np.int32),
                np.ones((k,), np.int32), np.ones((k,), np.int32),
                np.zeros((k,), np.float32), np.zeros((k,), np.int32),
                np.ones((k,), np.float32),
                np.zeros((k, 2), np.uint32),
                np.full((k,), _NO_EOS, np.int32),
                np.zeros((k,), np.int32), np.zeros((k,), bool),
                np.ones((k, self.cfg.vocab_size), bool),
                *wpages(k, bucket), *hseed(k), *wlora(k))
            np.asarray(first)
        if self._lora:
            # compile the registration write against a zero row — row
            # 0 is the pinned zero adapter, so the warm write is a
            # no-op on pool CONTENT and register_adapter() later never
            # trips an armed recompile guard. Shapes come from THE
            # shape table registration validates against, so the two
            # can never compile different programs.
            zero_row = {
                site: {part: np.zeros(shape, np.float32)
                       for part, shape in parts.items()}
                for site, parts in self._lora_expected_shapes().items()}
            self.adapters = self._adapter_set(self.adapters, zero_row,
                                              np.int32(0))
        if self._chunk_size:
            # the chunked-prefill ladder: chunk 0, every extend
            # variant, then the finish — junk tokens, logits flow
            # through so the finish compiles against the real dtypes
            c = self._chunk_size
            self._chunk_scratch = self._chunk0(
                self._params, self._chunk_scratch,
                np.full((1, c), ecfg.pad_token_id, np.int32),
                *wlora(1))
            lg = None
            for i, fn in sorted(self._chunk_exts.items()):
                self._chunk_scratch, lg = fn(
                    self._params, self._chunk_scratch,
                    np.full((1, c), ecfg.pad_token_id, np.int32),
                    np.zeros((1,), np.int32), *wlora(1))
            self.cache, self.state, first, _, _, _ = self._chunk_finish(
                self._params, self.cache, self.state,
                self._chunk_scratch, lg,
                np.zeros((1,), np.int32),
                np.full((1,), 2, np.int32), np.ones((1,), np.int32),
                np.zeros((1,), np.float32), np.zeros((1,), np.int32),
                np.ones((1,), np.float32), np.zeros((1, 2), np.uint32),
                np.full((1,), _NO_EOS, np.int32),
                np.zeros((1,), np.int32), np.zeros((1,), bool),
                np.ones((1, self.cfg.vocab_size), bool),
                *wpages(1, ecfg.max_prompt_len), *hseed(1))
            np.asarray(first)
        # prefix pool: compile every pool-insert and (split, tail
        # bucket) extend variant against page 0 junk
        if self._prefix_used:
            raise ValueError(
                "register_prefix() was called before warmup(): warmup "
                "resets the pool to shed its compile-time junk, which "
                "would silently drop the registered templates — call "
                "warmup() first, then register")
        for pb, fn in sorted(self._pool_inserts.items()):
            self.pool = fn(self._params, self.pool,
                           np.full((1, pb), ecfg.pad_token_id,
                                   np.int32), np.int32(0))
        for pb, fn in sorted(self._pool_pageins.items()):
            self.cache = fn(self.cache, self.pool, np.int32(0),
                            *wpages(1, pb))
        for (ps, tb), fn in sorted(self._admit_prefix.items()):
            self.cache, self.state, first, _, _, _ = fn(
                self._params, self.cache, self.state, self.pool,
                np.zeros((1,), np.int32),
                np.full((1, tb), ecfg.pad_token_id, np.int32),
                np.ones((1,), np.int32), np.ones((1,), np.int32),
                np.zeros((1,), np.float32), np.zeros((1,), np.int32),
                np.ones((1,), np.float32), np.zeros((1, 2), np.uint32),
                np.full((1,), _NO_EOS, np.int32),
                np.zeros((1,), np.int32), np.zeros((1,), bool),
                np.ones((1, self.cfg.vocab_size), bool), np.int32(0),
                *wpages(1, tb), *hseed(1), *wlora(1))
            np.asarray(first)
        # every step variant compiles here — each decode-chunk rung
        # and each (chunk, spec_k) cross — so the scheduler's payoff
        # gate AND the self-tuning controller can flip variants per
        # dispatch under an armed recompile guard (the serving.tuner
        # pre-warm contract; WARMUP-COVERAGE pins this loop statically)
        for c in sorted(self._step_variants):
            self.step_async(chunk=c).fetch()
        for (c, k) in sorted(self._spec_variants):
            self.step_async(spec=True, chunk=c, spec_k=k).fetch()
        if self._host_swap:
            # the swap tier: gather sink junk out at every rung and
            # scatter it straight back into the sink page — allocator
            # untouched, shapes/dtypes exactly what park/resume pass
            # (host-fetched blocks and state rows), so the armed guard
            # stays flat across swap churn
            srow = {k: np.asarray(self.state[k])[:1]
                    for k in self.state}
            self.state = self._state_restore(self.state, srow,
                                             np.int32(0))
            for n in self._swap_rungs:
                pages = np.full((n,), SINK, np.int32)
                block = jax.tree.map(np.asarray,
                                     self._swap_outs[n](self.cache,
                                                        pages))
                self.cache = self._swap_ins[n](self.cache, block,
                                               pages)
        self.state = self._retire(self.state, np.int32(0))
        # drop the warmup junk: a fresh init (compiled at construction)
        # frees every slot again
        self.cache, self.state = self._init(self._params)
        if self._chunk_size:
            self._chunk_scratch = self._chunk_scratch_init(self._params)
            self._chunked = None
        if self._paged:
            # warmup only ever wrote sink pages, but reset the host
            # mappings anyway so registration starts from a clean pool
            self._page_alloc.reset()
            self._tables[:, :] = SINK
            self._tables_dev = None
            self._slot_pages.clear()
            self._prefix_pages.clear()
        if self._latent:
            self._prefix_index.clear()
            self._prefix_tokens.clear()
            self._prefix_used = 0
        if self._prefix_splits:
            # warmup wrote junk into pool page 0 — reset the pool AND
            # the host registry, so templates register on clean pages
            # (register AFTER warmup; the insert programs are compiled
            # now, so registration never trips a recompile guard)
            self.pool = self._pool_init(self._params)
            self._prefix_index.clear()
            self._prefix_tokens.clear()
            self._prefix_used = 0
        if self._lora:
            # symmetric reset: warmup only ever wrote zeros into the
            # (all-zero) pool, but a fresh init keeps the adapter
            # lifecycle identical to the prefix pool's — warmup, then
            # register on a clean pool, both programs already compiled
            self.adapters = self._adapter_init(self._params)
            self._adapter_names.clear()
            self._adapter_meta.clear()
            self._adapter_used = 1
            self._adapter_ids[:] = 0
            self._aids_dev = None
            if self._host_swap:
                self._adapter_rows_host.clear()
                self._adapter_phys.clear()
                self._adapter_virt.clear()
                self._adapter_lru = hostswap.LRUIndex()
                self._adapter_free_rows = list(
                    range(self.engine_cfg.adapter_slots - 1, 0, -1))

    def _admit_variant_name(self, bucket: int, k: int) -> str:
        return f"admit_p{bucket}_k{k}"

    def _prefix_program_items(self):
        """(name, compiled fn) for every prefix-pool program — shared
        by :meth:`compiled_cache_sizes` and the recompile sentinel so
        the two can never disagree on what is tracked."""
        items = []
        if self._latent:
            return latent_engine.program_items(self)
        if self._prefix_splits:
            items.append(("pool_init", self._pool_init))
            for pb, fn in sorted(self._pool_inserts.items()):
                items.append((f"pool_p{pb}", fn))
            for pb, fn in sorted(self._pool_pageins.items()):
                items.append((f"pool_pagein_p{pb}", fn))
            for (ps, tb), fn in sorted(self._admit_prefix.items()):
                items.append((f"admit_prefix_p{ps}_t{tb}", fn))
        return items

    def _lora_program_items(self):
        """(name, compiled fn) for the multi-LoRA programs — shared by
        :meth:`compiled_cache_sizes` and the recompile sentinel, same
        contract as :meth:`_prefix_program_items`. (``adapter_init``
        runs at construction, ``adapter_set`` at warmup + every
        registration — both must stay at one cache entry.)"""
        items = []
        if self._lora:
            items.append(("adapter_init", self._adapter_init))
            items.append(("adapter_set", self._adapter_set))
        return items

    def _swap_program_items(self):
        """(name, compiled fn) for every host-swap program — shared by
        :meth:`compiled_cache_sizes` and the recompile sentinel, same
        contract as :meth:`_prefix_program_items`: one gather + one
        scatter per swap-batch rung, plus the state-row restore."""
        items = []
        if self._host_swap:
            for n, fn in sorted(self._swap_outs.items()):
                items.append((f"swap_out_n{n}", fn))
            for n, fn in sorted(self._swap_ins.items()):
                items.append((f"swap_in_n{n}", fn))
            items.append(("state_restore", self._state_restore))
        return items

    def _chunk_program_items(self):
        """(name, compiled fn) for every chunked-prefill program —
        shared by :meth:`compiled_cache_sizes` and the recompile
        sentinel, same contract as :meth:`_prefix_program_items`."""
        items = []
        if self._chunk_size:
            items.append(("chunk_scratch_init",
                          self._chunk_scratch_init))
            items.append(("chunk0", self._chunk0))
            for i, fn in sorted(self._chunk_exts.items()):
                items.append((f"chunk_ext_{i}", fn))
            items.append(("chunk_finish", self._chunk_finish))
        return items

    def compiled_cache_sizes(self) -> Dict[str, Any]:
        """jit-cache entry count per program — the trace-stability
        probe: after warmup each must stay at 1 no matter how many
        requests were admitted (the oracle test asserts this). The
        aggregate ``"admit"`` key is the MAX over the per-(bucket, k)
        variants (each also reported under ``admit_p{bucket}_k{k}``;
        prefix-pool extend variants ``admit_prefix_p{split}_t{tail}``
        count too — they ARE admissions), so it reads exactly like the
        single-program days: 1 = stable."""
        size_of = lambda fn: (fn._cache_size()
                              if callable(getattr(fn, "_cache_size", None))
                              else None)
        out = {name: size_of(getattr(self, f"_{name}"))
               for name in ("init", "retire")}
        # step variants: one entry per rung (`step_c{chunk}` /
        # `step_spec_c{chunk}_k{k}`) plus the aggregate MAX under the
        # historical names, exactly the "admit" convention below — the
        # tuner switches among these, so each must stay at 1
        step_sizes, spec_sizes = [], []
        for c, fn in sorted(self._step_variants.items()):
            s = size_of(fn)
            out[f"step_c{c}"] = s
            if s is not None:
                step_sizes.append(s)
        out["step"] = max(step_sizes) if step_sizes else None
        for (c, k), fn in sorted(self._spec_variants.items()):
            s = size_of(fn)
            out[f"step_spec_c{c}_k{k}"] = s
            if s is not None:
                spec_sizes.append(s)
        if self._spec:
            out["step_spec"] = max(spec_sizes) if spec_sizes else None
        admit_sizes = []
        for (bucket, k), fn in sorted(self._admits.items()):
            s = size_of(fn)
            out[self._admit_variant_name(bucket, k)] = s
            if s is not None:
                admit_sizes.append(s)
        for name, fn in (self._prefix_program_items()
                         + self._chunk_program_items()
                         + self._lora_program_items()
                         + self._swap_program_items()):
            s = size_of(fn)
            out[name] = s
            if s is not None and name.startswith("admit_prefix"):
                admit_sizes.append(s)
        out["admit"] = max(admit_sizes) if admit_sizes else None
        return out

    # -- recompile sentinel (apex_tpu.telemetry.recompile) -----------------

    def recompile_sentinel(self, registry=None):
        """The engine's installed
        :class:`apex_tpu.telemetry.recompile.RecompileSentinel`, created
        on first call with every compiled program tracked —
        init/step/retire plus one ``admit_p{bucket}_k{k}`` entry per
        admission variant (so ``compiles_total()["tracked"]``
        attributes growth by name). Pass ``registry`` on the first
        call to mirror compile/alarm counters into ``/metrics`` —
        passing it once a registry-less sentinel exists raises rather
        than silently dropping the wiring (the counters would simply
        never appear in scrapes)."""
        if self._sentinel is not None and registry is not None \
                and registry is not self._sentinel.registry:
            raise ValueError(
                "this engine's recompile sentinel already exists (an "
                "earlier recompile_sentinel()/recompile_guard() call) "
                "and cannot adopt a different registry retroactively; "
                "pass registry on the FIRST call, or engine.close() to "
                "discard the old sentinel")
        if self._sentinel is None:
            from apex_tpu.telemetry.recompile import RecompileSentinel

            sentinel = RecompileSentinel(registry=registry).install()
            for name in ("init", "retire"):
                sentinel.track(name, getattr(self, f"_{name}"))
            for c, fn in sorted(self._step_variants.items()):
                sentinel.track(f"step_c{c}", fn)
            for (c, k), fn in sorted(self._spec_variants.items()):
                sentinel.track(f"step_spec_c{c}_k{k}", fn)
            for (bucket, k), fn in sorted(self._admits.items()):
                sentinel.track(self._admit_variant_name(bucket, k), fn)
            for name, fn in (self._prefix_program_items()
                             + self._chunk_program_items()
                             + self._lora_program_items()
                             + self._swap_program_items()):
                sentinel.track(name, fn)
            self._sentinel = sentinel
        return self._sentinel

    def recompile_guard(self, *, raise_on_recompile: bool = True,
                        registry=None):
        """Arm the never-recompile-after-warmup invariant: enter the
        returned context once every program has compiled
        (:meth:`warmup` covers all of them) and any later compilation —
        process-wide event or growth of this engine's program caches —
        increments the alarm counter and (by default) raises
        :class:`~apex_tpu.telemetry.recompile.RecompileError`::

            engine.warmup()
            with engine.recompile_guard():
                serve_forever()
        """
        return self.recompile_sentinel(registry=registry).guard(
            raise_on_recompile=raise_on_recompile)

    def close(self) -> None:
        """Release process-wide telemetry hooks — the recompile
        sentinel's ``jax.monitoring`` listener stays registered for
        process lifetime otherwise, so engines created in a loop (the
        bench's chunk sweep, a service rebuilding on config reload)
        must close the old one. Idempotent AND re-entrant: the sentinel
        reference is detached BEFORE the listener is released, so a
        second ``close()`` — or one racing a bundle-triggered dump that
        reads the sentinel — can never double-release (a double
        unregister-by-callback could detach a listener a NEWER sentinel
        just registered). The engine itself remains usable, and a later
        :meth:`recompile_sentinel` call reinstalls a fresh sentinel."""
        sentinel, self._sentinel = self._sentinel, None
        if sentinel is not None:
            sentinel.uninstall()

    def __enter__(self) -> "Engine":
        """Context-manager form: ``with Engine(...) as eng:`` closes on
        exit — the ergonomic fix for the "engines created in a loop
        must call close()" footgun (a leaked sentinel listener outlives
        the engine otherwise)."""
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        self.close()
