"""Single-query (flash-decode) attention Pallas kernel for the KV-cache
decode hot path.

The XLA decode path under vector per-slot positions (the serving
engine's form) cannot express "write one column at per-row offsets" —
``dynamic_update_slice`` takes one start index per operand — so it
rewrites the ENTIRE ``[b, h, S, d]`` K and V caches through a one-hot
``jnp.where`` every layer every token: O(b·h·S·d) HBM read+write
traffic that scales with the cache horizon just to land one
``[b, h, d]`` column. This module replaces that with two kernels
composed by :func:`decode_attention`:

- **column write**: the new K/V column lands at each row's own ``pos``
  through a scalar-prefetch index map with the cache aliased
  input→output (``input_output_aliases``). The operand is the WHOLE
  stacked cache ``[L, 2, b, h, S, d]`` and the layer is one more
  scalar-prefetch operand: the block index carries the leading
  ``(layer, plane)`` coordinates, so the model's layer scan carries the
  cache and never slices a layer out or stacks it back. Mosaic moves
  whole tiles, so each grid step reads the one tile-aligned window
  that holds ``pos[b]`` in the K and the V plane of that layer,
  replaces position ``pos[b] % window`` and writes the window back;
  the rest of the cache — every other layer, and the rows the caller
  marks dead (:func:`live_rows`) — is never touched;
- **split-K read**: flash-decode attention — the cache horizon is swept
  in ``block_k`` chunks with a running online-softmax ``(out, lse)``
  merge (the same ``m/l/acc`` update as the training flash kernel),
  per-row masking ``col <= pos[b]`` matching ``gpt.decode_step``'s
  vector-``pos`` semantics exactly: garbage cache entries past a row's
  position contribute exact softmax zeros. K and V chunks are blocks
  of plane 0 and plane 1 of the same stacked operand at the prefetched
  layer. The grid is ``(rows, h // hb, chunks)``: ``hb`` heads of one
  batch row a grid step (as many as a VMEM budget holds; all of them
  at GPT-2's 16 x 64), their scores and statistics dense ``(hb, bk)``
  tiles. It fetches only what a step attends: the index maps clamp the
  chunk to the row's fill (``min(j, pos[b] // block_k)``), so a grid
  step past the row's last chunk names the block already resident and
  the pipeline copies nothing.

Both grids walk only the rows the caller marks live: their first axis
is a dynamic bound, the live count, over the scalar-prefetched list
:func:`live_rows` builds (live rows first), so a done or empty slot is
neither written nor read and its output is the zeros aliased to the
read's output.

**Which way the operand lies** (:func:`_positions_on_lanes`). The
device does not keep ``[.., S, 64]`` row-major between programs: a
minor dimension that does not fill the 128 lanes would be padded to
them, so it lays the array out with ``S`` on the lanes and the head
dim on the sublanes (``{4,5,3,2,1,0}``, unpadded). Both kernels
therefore take ``swapaxes(cache, 4, 5)`` — a bitcast of that layout,
no bytes move — with ``(d, bk)`` K and V blocks in the read (scores
``q (hb, d) · k (d, bk)``, values ``p (hb, bk) · v (d, bk)ᵀ``, every
mask along the lanes) and a window of 128 positions on the lanes in
the write; the write's aliased output is swapped back, a bitcast
again. A cache declared row-major to Mosaic instead was relaid, all of
it, into a copy padded to twice its size at the entry of every program
that ran a kernel and back at its exit (PERF.md, PR 25 and PR 30).
Where the head dim fills the lanes (128) the array does lie row-major,
and where the span of positions does not (pages of 16) the device puts
something else there; both keep the row-major blocks — ``(bk, d)``
chunks, a window one sublane tile high (8 rows f32 / 16 bf16 / 32
int8, fp8), one cache position one ROW of a tile. One algorithm, the
orientation read from head size, span and storage; the fp32 scale
planes ``[L, 2, b, h, S]`` lie with the positions on the lanes either
way.

The stacked forms (:func:`stacked_decode_attention`,
:func:`stacked_write_columns`) are what the model calls; the per-layer
functions (:func:`decode_attention`, the ``paged_*`` family) take one
layer's K and V planes, stack them into a one-layer cache — a copy —
and run the same two kernels at layer 0.

Numerics match the materialised-scores XLA path: scores are computed
with fp32 accumulation (``preferred_element_type``) and the softmax
statistics are fp32; the only divergence is where the ``1/sqrt(d)``
scale is applied (fp32 scores here vs compute-dtype q there), which the
oracle test covers with per-dtype tolerances
(``tests/test_decode_attention.py``).

**Quantized cache layout** (:func:`decode_attention_quantized`): K/V
stored int8 (or fp8 e4m3) with per-head, per-slot, per-position fp32
scales. The incoming ``[h, d]`` rows are quantized by
:func:`quantize_kv_rows` (symmetric absmax per head — the same
deterministic round-to-nearest quantizer every other cache-write path
calls) and land as one quantized column plus one scale column per
batch row through the same window write; the split-K read streams int8
chunks from HBM — ~2x less read traffic than bf16, ~4x less than f32 —
and folds the scales into the fp32 scores and probabilities in VMEM.

Like every kernel in this package it runs interpreted off-TPU, so the
CPU test backbone exercises identical semantics; the model-level
dispatch (``GPTConfig.decode_attn_impl="auto"``) keeps the XLA path for
interpret mode and short horizons per the repo's crossover convention.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from apex_tpu.kernels._utils import round_up, use_interpret, widen_f16

_NEG = -1e30
_LANES = 128  # stat scratch lane width (matches flash_attention)
#: default split-K chunk of the cache horizon; _fit cuts it down for
#: short/misaligned horizons
_DEFAULT_BLOCK_K = 256


def _sublane_tile(dtype) -> int:
    """Rows of one Mosaic tile of ``dtype``: 8 f32 / 16 bf16 / 32 int8,
    fp8 — narrower dtypes pack more rows per 32-bit sublane."""
    return 8 * (4 // jnp.dtype(dtype).itemsize)


def _positions_on_lanes(d: int, span: int, dtype) -> bool:
    """Which way the kernels take their K/V operand: True — ``(d,
    bk)`` blocks of ``swapaxes(kv, 4, 5)``, the positions on the lanes
    and the head dim on the sublanes; False — row-major ``(bk, d)``
    blocks of ``kv``. THE rule, for both kernels and
    :func:`_heads_per_step`, from what the code can see: head size
    ``d``, the ``span`` of positions of one row of the operand (the
    horizon ``S``, or the page ``P`` of a pool) and the storage
    ``dtype``.

    It follows the layout the device gives the array between programs
    (the module docstring has the why): a minor dim that does not fill
    the 128 lanes is not left minor, so ``[.., S, 64]`` lies with ``S``
    on the lanes — in bf16, int8, fp8 and f32 alike — and the turned
    view is a bitcast of it. A head dim of 128 lies row-major already;
    under a span off the lane grid (pages of 16: the device puts the
    PAGES on the lanes) neither view is a bitcast and the row-major
    one is kept. The head dim must also fill whole sublane tiles of the
    storage."""
    return (d % _LANES != 0 and span % _LANES == 0
            and d % _sublane_tile(dtype) == 0)


def _fit_block_k(want: int, sk: int, align: int) -> int:
    """Split-K chunk for a horizon of ``sk``: the whole horizon as one
    block when it fits ``want`` (a full-dimension block is always
    tile-legal and sweeps nothing extra), else the largest halving of
    ``want`` that doesn't over-sweep by more than a quarter (same
    policy as flash's ``_fit_block``). ``align`` is the smallest chunk
    Mosaic accepts: the cache dtype's sublane tile, or 128 when the
    positions lie on the lanes (fp32 scale rows riding along, or the
    storage itself: :func:`_positions_on_lanes`)."""
    if sk <= want:
        return sk
    b = max(want, align)
    while b > align and round_up(sk, b) - sk > sk // 4:
        b //= 2
    return b


# ---------------------------------------------------------------------------
# column write: cache[layer, :, row, :, pos, ...] = new[:, b]
# (the K and the V window of one layer per row, in one block)
# ---------------------------------------------------------------------------

def _write_kernel(*refs, n_scalar, windows, page):
    """Land one column per cache operand. Each operand's block is the
    K and the V plane's tile-aligned window of ``w`` positions around
    the position of grid row ``i``'s row (:func:`_row_of`; at ``-1``,
    the one step of a grid with no live row, nothing lands and the
    window goes back as it was) (``windows[k] == (w, lanes)``).
    Row-major storage ``[2, 1,
    h, w, d]`` (the window down the sublanes) and scale planes ``[2,
    1, h, w]`` take an incoming block one position wide, which
    broadcasts over the window. Storage with the positions on the
    lanes, ``[2, 1, h, d, w]`` (``lanes``), takes the incoming rows as
    ``[2, 1, d, h]`` — a head is one lane of it, spread over the
    window's lanes — since a trailing dim of 1 would pad every row to a
    tile."""
    n = len(windows)
    news = refs[n_scalar:n_scalar + n]
    olds = refs[n_scalar + n:n_scalar + 2 * n]
    outs = refs[n_scalar + 2 * n:]
    _, pos = _row_of(pl.program_id(0), refs[1], refs[2])
    if page:
        pos = lax.rem(pos, page)        # -1 stays -1: no cell is hit
    for new_ref, old_ref, out_ref, (w, lanes) in zip(news, olds, outs,
                                                     windows):
        if lanes:
            hit = (lax.broadcasted_iota(jnp.int32, (1, 1, w), 2)
                   == lax.rem(pos, w))
            new = new_ref[:, 0]                           # (2, d, h)
            for head in range(out_ref.shape[2]):
                out_ref[:, 0, head] = jnp.where(
                    hit, new[:, :, head:head + 1], old_ref[:, 0, head])
        else:
            hit = (lax.broadcasted_iota(jnp.int32, out_ref.shape, 3)
                   == lax.rem(pos, w))
            out_ref[...] = jnp.where(hit, new_ref[...], old_ref[...])


def _layer_scalar(layer):
    """The layer index as a scalar-prefetch operand."""
    return jnp.asarray(layer, jnp.int32).reshape(1)


def live_rows(live):
    """The decode kernels' row list for one step, ``[b + 1] int32``:
    the rows that ``live [b] bool`` marks, in order, then the others,
    in order, then ``n``, how many are live. Grid row ``i < n`` of
    both kernels works on row ``rows[i]``; the grids stop at ``n``
    (one step where ``n`` is 0), so a dead row is neither written nor
    read. The live set does not change between layers: a caller that
    runs the kernels for every layer of a step builds this once.

    Masked ``[b, b]`` reductions, not a sort or a gather: each of those
    is a device operation of its own, and a sort of ``b`` keys costs
    more than the whole list."""
    live = jnp.asarray(live, jnp.bool_)
    idx = jnp.arange(live.shape[0], dtype=jnp.int32)
    n = jnp.sum(live, dtype=jnp.int32)
    live_before = jnp.sum(live[None] & (idx[None] < idx[:, None]), axis=1,
                          dtype=jnp.int32)
    # a live row's place is the live rows before it; a dead row's, n
    # plus the dead rows before it
    place = jnp.where(live, live_before, n + idx - live_before)
    rows = jnp.sum(jnp.where(place[None] == idx[:, None], idx[None], 0),
                   axis=1, dtype=jnp.int32)
    return jnp.concatenate([rows, n[None]])


def _every_row(b: int):
    """:func:`live_rows` of a step whose ``b`` rows are all live."""
    return jnp.arange(b + 1, dtype=jnp.int32)


def _row_of(i, pos, rows):
    """``(row, position)`` of grid row ``i``: row ``rows[i]`` at its
    ``pos`` while ``i`` is under the live count ``rows[-1]``; past it —
    only the one step of a grid with no live row — at ``-1``, which no
    cell and no chunk is at or before. Works on prefetched scalar refs
    and, for the tests' walk of the grid, on host arrays."""
    row = rows[i]
    return row, jnp.where(i < rows[rows.shape[0] - 1], pos[row], -1)


def _grid_rows(rows, b: int):
    """The kernels' row-axis extent: ``b`` when every row is live
    (``rows`` None, the list :func:`_every_row`), else the live count
    as a dynamic grid bound, at least 1 (a grid with no live row keeps
    one step, which writes a window back as it was and reads
    nothing)."""
    if rows is None:
        return _every_row(b), b
    return rows, jnp.maximum(rows[b], 1)


def _write_column_planes(news, planes, layer, pos, table=None, live=None):
    """Write ``news[k] [2, b, h(, d)]`` (the K and the V row) into
    position ``pos[b]`` of layer ``layer`` of ``planes[k]`` — stacked
    contiguous caches ``[L, 2, b, h, S(, d)]``, or, with ``table [b,
    max_pages]``, page pools ``[L, 2, num_pages, h, P(, d)]`` where the
    cell is ``(table[b, pos // P], pos % P)``. ``planes`` is ``[kv]``
    or the quantized ``[kv, scale]``. One grid step per row that
    ``live`` (a :func:`live_rows` list; None: every row) names; a dead
    row's window is neither read nor written. Every operand is aliased
    input→output, so only the windows holding the written cells move,
    whatever ``L`` is. ``0 <= pos[b]`` must lie inside the row's
    horizon, and rows must target distinct windows — except inside a
    shared garbage/sink page, where the pipelined read-modify-write of
    one window by two rows keeps only one row's cell (the sink holds
    garbage by contract)."""
    b = news[0].shape[1]
    p_sz = planes[0].shape[4]
    paged = table is not None
    mp = table.shape[1] if paged else 0
    rows, grid_b = _grid_rows(live, b)
    new_ops, new_specs, plane_ops, plane_specs, windows = [], [], [], [], []
    for new, plane in zip(news, planes):
        h = plane.shape[3]
        lanes = plane.ndim == 6 and _positions_on_lanes(
            plane.shape[5], p_sz, plane.dtype)
        # the block past (layer, plane, row, head), the place of the
        # positions in it, and the incoming rows' block
        if lanes:                               # storage as [.., d, S]
            plane = jnp.swapaxes(plane, 4, 5)
            w, d = min(_LANES, p_sz), plane.shape[4]
            block, at = (d, w), 1
            new, new_block = jnp.swapaxes(new, 2, 3), (2, 1, d, h)
        elif plane.ndim == 6:                   # storage as [.., S, d]
            w, d = min(_sublane_tile(plane.dtype), p_sz), plane.shape[5]
            block, at = (w, d), 0
            new, new_block = jnp.expand_dims(new, 3), (2, 1, h, 1, d)
        else:                                   # scale plane [.., S]
            w = min(_LANES, p_sz)
            block, at = (w,), 0
            new, new_block = jnp.expand_dims(new, 3), (2, 1, h, 1)

        def where(i, layer_ref, pos_ref, rows_ref, *tbl_ref, w=w, at=at,
                  n=len(block)):
            row, col = _row_of(i, pos_ref, rows_ref)
            col = jnp.maximum(col, 0)
            if paged:
                row = tbl_ref[0][row * mp + lax.div(col, p_sz)]
                col = lax.rem(col, p_sz)
            tail = [0] * n
            tail[at] = lax.div(col, w)
            return (layer_ref[0], 0, row, 0, *tail)

        new_ops.append(new.astype(plane.dtype))
        new_specs.append(pl.BlockSpec(
            new_block,
            lambda i, layer_ref, pos_ref, rows_ref, *_, n=len(new_block):
            (0, rows_ref[i]) + (0,) * (n - 2)))
        plane_ops.append(plane)
        plane_specs.append(pl.BlockSpec((None, 2, 1, h) + block, where))
        windows.append((w, lanes))
    scalars = [_layer_scalar(layer), jnp.asarray(pos, jnp.int32), rows]
    if paged:
        scalars.append(jnp.asarray(table, jnp.int32).reshape(-1))
    n_scalar, n = len(scalars), len(planes)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=n_scalar,
        grid=(grid_b,),
        in_specs=new_specs + plane_specs,
        out_specs=plane_specs,
    )
    outs = pl.pallas_call(
        functools.partial(_write_kernel, n_scalar=n_scalar,
                          windows=tuple(windows),
                          page=p_sz if paged else 0),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(p.shape, p.dtype)
                   for p in plane_ops],
        # operand order: (*scalars, *news, *planes)
        input_output_aliases={n_scalar + n + k: k for k in range(n)},
        name="decode_attn_write",
        interpret=use_interpret(),
    )(*scalars, *new_ops, *plane_ops)
    # a turned operand goes back as the cache's own [.., S, d]
    return [jnp.swapaxes(out, 4, 5) if lanes else out
            for out, (_, lanes) in zip(outs, windows)]


def _write_columns_planes(news, planes, layer, pos, table=None):
    """The T-column write: ``news[k] [2, b, h, T(, d)]`` land at
    positions ``pos[b] .. pos[b] + T - 1``, one
    :func:`_write_column_planes` pass per lane (T is the tiny static
    draft width; two lanes of one row usually share a window, so they
    cannot ride one pipelined grid). Lanes past the row's horizon CLAMP
    onto its last position."""
    p_sz = planes[0].shape[4]
    smax = p_sz * table.shape[1] if table is not None else p_sz
    pos = jnp.asarray(pos, jnp.int32)
    for j in range(news[0].shape[3]):
        planes = _write_column_planes(
            [new[:, :, :, j] for new in news], planes, layer,
            jnp.minimum(pos + j, smax - 1), table)
    return planes


def _stack_news(k_new, v_new, kind):
    """The incoming K and V rows as the write's operands: ``[kv_new]``,
    or — ``kind`` a quantized storage — ``[kv_q, kv_scale]`` through
    :func:`quantize_kv_rows`, the one deterministic quantizer."""
    k_new, _ = widen_f16(k_new)
    v_new, _ = widen_f16(v_new)
    kv_new = jnp.stack([k_new, v_new])
    if kind is None:
        return [kv_new]
    if kind not in KV_QMAX:
        raise ValueError(f"unknown quantized-KV kind {kind!r}")
    return list(quantize_kv_rows(kv_new, kind))


def stacked_write_columns(k_new, v_new, cache, layer, pos, *, table=None,
                          kind: Optional[str] = None):
    """Write ``k_new/v_new [b, h, T, d]`` into columns ``pos[b] ..
    pos[b] + T - 1`` of layer ``layer`` of the stacked cache ``[L, 2,
    b, h, S, d]`` — or of the page pool ``[L, 2, num_pages, h, P, d]``
    under ``table [b, max_pages]``; or, ``kind`` ``"int8"``/``"fp8"``,
    of the quantized ``{"kv", "scale"}`` pair, each incoming row
    quantized into one storage column plus one fp32 scale cell. The
    cache is aliased input→output, so only the touched windows of that
    one layer move (the speculative verify forward's cache landing, T =
    draft k + 1). Every row is written: only the one-column step of
    :func:`stacked_decode_attention` takes a ``live`` list and skips
    the dead rows. Returns the cache.

    Columns past the horizon are CLAMPED onto the row's last column
    ``S - 1``: a row whose tail lanes overrun the cache end (a
    near-budget slot drafting past its horizon, or a done slot's frozen
    lanes) smashes only the last column. That can never corrupt an
    emitted token: a lane's draw is only emitted when the row's
    remaining budget covers it, and the engine bounds ``pos + remaining
    <= S - 1`` — so any lane whose query would attend column ``S - 1``
    (``pos + j = S - 1``) needs ``remaining >= j + 1 = S - pos``, a
    contradiction. Column ``S - 1`` is therefore only ever read by
    discarded lanes, and only ever holds a real token's K/V once the
    row is done (frozen done-row writes) — the same masked-garbage
    contract every over-position cache entry already lives under."""
    # [kv], or [kv, scale] of the quantized {"kv", "scale"} pair
    planes, layout = jax.tree.flatten(cache)
    planes = _write_columns_planes(_stack_news(k_new, v_new, kind), planes,
                                   layer, pos, table)
    return jax.tree.unflatten(layout, planes)


def _one_layer(k, v, k_scale=None, v_scale=None):
    """One layer's planes as a one-layer stacked cache — the per-layer
    functions' way into the stacked kernels (a copy of both planes)."""
    kv = jnp.stack([k, v])[None]
    if k_scale is None:
        return kv
    return {"kv": kv, "scale": jnp.stack([k_scale, v_scale])[None]}


def _layer_planes(cache):
    """:func:`_one_layer` undone: ``(k, v)`` or ``(k, k_scale, v,
    v_scale)``."""
    if isinstance(cache, dict):
        kv, sc = cache["kv"][0], cache["scale"][0]
        return kv[0], sc[0], kv[1], sc[1]
    return cache[0, 0], cache[0, 1]


def cache_write_columns(k_new, v_new, k_cache, v_cache, pos):
    """:func:`stacked_write_columns` for one layer's planes: ``k_new/
    v_new [b, h, T, d]`` into columns ``pos[b] .. pos[b] + T - 1`` of
    the caches ``[b, h, S, d]``, same clamped over-horizon contract.
    Returns ``(k_cache, v_cache)``."""
    return _layer_planes(stacked_write_columns(
        k_new, v_new, _one_layer(k_cache, v_cache), 0, pos))


def cache_write_columns_xla(cache, new, pos):
    """The XLA (one-hot select) spelling of the multi-column masked
    write, one plane at a time: ``cache [b, h, S, d]`` (or a scale
    plane ``[b, h, S]``) gains ``new [b, h, T, d]`` (/``[b, h, T]``) at
    columns ``pos[b] + j``; columns at or past ``S`` are dropped (the
    write guard the verify forward relies on — an over-horizon lane
    must not clamp into a neighbouring column). This is the vector-pos
    one-hot rewrite the one-column Pallas kernel exists to remove,
    generalised to T columns — the CPU-testable correctness backbone
    and the off-TPU path, exactly like the rest of this module."""
    sk = cache.shape[2]
    t = new.shape[2]
    pos = jnp.asarray(pos, jnp.int32)
    cols = pos[:, None] + jnp.arange(t, dtype=jnp.int32)[None]  # [b, T]
    # onehot [b, T, S]: lane j of row b lands at column pos[b] + j;
    # over-horizon lanes have no hit (arange(S) never reaches them)
    onehot = (jnp.arange(sk, dtype=jnp.int32)[None, None]
              == cols[:, :, None])
    if cache.ndim == 4:
        gathered = jnp.einsum(
            "bts,bhtd->bhsd", onehot.astype(cache.dtype), new.astype(
                cache.dtype))
        hit = onehot.any(axis=1)[:, None, :, None]
    elif cache.ndim == 3:
        gathered = jnp.einsum(
            "bts,bht->bhs", onehot.astype(cache.dtype),
            new.astype(cache.dtype))
        hit = onehot.any(axis=1)[:, None, :]
    else:
        raise ValueError(
            f"cache plane must be [b, h, S(, d)], got rank {cache.ndim}")
    return jnp.where(hit, gathered, cache)


def cache_write_columns_quant(k_new, v_new, k_q, k_s, v_q, v_s, pos,
                              kind):
    """:func:`cache_write_columns` over the quantized cache layout:
    each of the T incoming ``[h, d]`` rows is quantized
    (:func:`quantize_kv_rows` — the one deterministic quantizer) and
    lands one quantized column plus one fp32 scale column at ``pos[b] +
    j`` across all four planes; same clamped over-horizon contract as
    the plain variant."""
    return _layer_planes(stacked_write_columns(
        k_new, v_new, _one_layer(k_q, v_q, k_s, v_s), 0, pos, kind=kind))


# ---------------------------------------------------------------------------
# split-K read: a block of heads of one row against the chunks of the
# horizon that the row attends
# ---------------------------------------------------------------------------

#: VMEM the read kernel's double-buffered K and V blocks may take (of
#: the 16 MiB Mosaic scopes a kernel by default): it sets how many
#: heads ride one grid step
_KV_VMEM_BUDGET = 4 << 20


def decode_block_k(horizon: int, storage_dtype, *, quantized: bool = False,
                   block_k: Optional[int] = None,
                   head_dim: Optional[int] = None) -> int:
    """Positions in one split-K chunk of the read kernel over a
    contiguous cache of ``horizon`` positions stored as
    ``storage_dtype`` (a paged pool's chunk is its page). THE rule:
    the kernel calls it, and so does whoever counts the chunks a step
    needs (the scheduler's ``decode.chunks_*`` counts). The kernel
    hands it ``head_dim`` so that a ``block_k`` of the caller's is held
    to the lanes where the operand lies that way; the default chunk
    needs none (a horizon with its positions on the lanes is a
    multiple of 128, which gets the same chunk under either
    alignment)."""
    # fp32 scale rows put the chunk on the lane dimension too
    lanes = quantized or (head_dim is not None and _positions_on_lanes(
        head_dim, horizon, storage_dtype))
    return _fit_block_k(block_k or _DEFAULT_BLOCK_K, horizon,
                        _LANES if lanes else _sublane_tile(storage_dtype))


def _heads_per_step(h: int, d: int, bk: int, dtype, quant: bool,
                    lanes: bool) -> int:
    """Heads one grid step of the read takes: the largest divisor of
    ``h`` whose K and V blocks, double-buffered and as laid out on the
    tiles — ``(d, bk)`` with the positions on the lanes (``lanes``),
    else ``(bk, d)`` with the head dim padded to them — fit
    :data:`_KV_VMEM_BUDGET`; the fp32 scale blocks of a quantized cache
    ride along."""
    rows, cols = (d, bk) if lanes else (bk, d)
    per_head = 4 * round_up(rows, _sublane_tile(dtype)) * round_up(
        cols, _LANES) * jnp.dtype(dtype).itemsize
    if quant:
        per_head += 4 * round_up(bk, _LANES) * 4
    fit = max(1, _KV_VMEM_BUDGET // per_head)
    return max(n for n in range(1, h + 1) if h % n == 0 and n <= fit)


def _block_index(i, g, j, pos, rows, bk: int):
    """``(row, head group, chunk)`` of the K / V block that grid step
    ``(i, g, j)`` names: row ``rows[i]`` (:func:`_row_of`) at its chunk
    ``j`` clamped to its fill, so steps past its last chunk name the
    block already resident and the pipeline skips the copy. The one
    step of a grid with no live row names chunk 0 of ``rows[0]``."""
    row, p = _row_of(i, pos, rows)
    return row, g, jnp.minimum(j, jnp.maximum(p, 0) // bk)


def _attn_kernel(*refs, n_scalar, quant, lanes, scale, bk, smax, zeros):
    """Grid ``(rows, h // hb, chunks)``: ``hb`` heads of one batch row
    (:func:`_row_of`) swept over the row's horizon in ``bk``-position
    chunks, scores and statistics as dense ``(hb, bk)`` / ``(hb,
    lanes)`` tiles. A head's K and V chunk are ``(d, bk)`` blocks where
    the operand has the positions on the lanes (``lanes``), ``(bk, d)``
    where it is row-major: the same two dots, each contracting the
    other way. ``quant`` adds the two fp32 scale-block refs of the
    int8/fp8 layout; ``zeros`` (0 or 1) counts the output's aliased
    zeros, which come before them all, left in HBM and never touched. A row at ``pos == -1`` (a grid
    with no live row) has no chunk at or before it, so it does no
    arithmetic and writes zeros."""
    blocks = refs[n_scalar + zeros:]
    if quant:
        (q_ref, k_ref, ks_ref, v_ref, vs_ref, o_ref, acc_ref, m_ref,
         l_ref) = blocks
    else:
        q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref = blocks
    hb, d = acc_ref.shape
    j = pl.program_id(2)        # split-K chunk of the horizon
    nk = pl.num_programs(2)
    _, pos = _row_of(pl.program_id(0), refs[1], refs[2])

    @pl.when(j == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, _NEG)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    # chunks entirely past the row's position contribute nothing (the
    # decode analogue of the causal block skip); their grid steps name
    # the block already resident, so they fetch nothing either
    @pl.when(j * bk <= pos)
    def _block():
        q = q_ref[0, 0]                                   # (hb, d)
        if quant:
            q = q.astype(jnp.float32)
        head = lax.broadcasted_iota(jnp.int32, (hb, 1), 0)
        col = lax.broadcasted_iota(jnp.int32, (1, bk), 1) + j * bk
        valid = (col <= pos) & (col < smax)
        if lanes:
            valid_v = valid     # V's positions run along the lanes too
        else:
            # the same mask down the sublanes (Mosaic cannot transpose
            # i1)
            row = lax.broadcasted_iota(jnp.int32, (bk, 1), 0) + j * bk
            valid_v = (row <= pos) & (row < smax)
        # the two dots contract a chunk's head dim and its positions:
        # dims 0 and 1 of a (d, bk) block, 1 and 0 of a (bk, d) one
        over_d = (((1,), (0 if lanes else 1,)), ((), ()))
        over_bk = (((1,), (1 if lanes else 0,)), ((), ()))
        # every head's query against head n's chunk, row n kept: M = hb
        # costs the MXU what M = 1 does, and the scores come out as one
        # dense (hb, bk) tile instead of hb one-row tiles
        s = jnp.zeros((hb, bk), jnp.float32)
        for n in range(hb):
            k = k_ref[0, n]                     # (d, bk), or (bk, d)
            if quant:
                # int8/fp8 chunk straight from HBM; the per-column
                # scale folds into the SCORE (q·(k_int·s) ==
                # (q·k_int)·s) so the chunk is never materialised
                # dequantized
                k = k.astype(jnp.float32)
            s = jnp.where(head == n, jax.lax.dot_general(
                q, k, over_d, preferred_element_type=jnp.float32), s)
        if quant:
            s = s * ks_ref[0, 0]
        s = jnp.where(valid, s * scale, _NEG)
        m_prev = m_ref[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        corr = jnp.exp(m_prev - m_new)
        p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
        l_ref[:] = jnp.broadcast_to(
            corr * l_ref[:, :1] + jnp.sum(p, axis=-1, keepdims=True),
            l_ref.shape)
        if quant:
            # the V scale folds into p the same way (Σ p_j·(v_j·s_j)
            # == Σ (p_j·s_j)·v_j); masked scale columns are zeroed too
            p = p * jnp.where(valid, vs_ref[0, 0], 0.0)
        pv = jnp.zeros((hb, d), jnp.float32)
        for n in range(hb):
            v = v_ref[0, n]                     # (d, bk), or (bk, d)
            if quant:
                v = v.astype(jnp.float32)
            # masked V rows can be horizon padding (NaN in interpret
            # mode, arbitrary garbage on chip, NaN bit patterns of
            # stale fp8): zero them so 0·garbage can't poison the
            # accumulator dot
            v = jnp.where(valid_v, v, 0.0).astype(v.dtype)
            pv = jnp.where(head == n, jax.lax.dot_general(
                p.astype(v.dtype), v, over_bk,
                preferred_element_type=jnp.float32), pv)
        acc_ref[:] = acc_ref[:] * corr + pv
        m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)

    @pl.when(j == nk - 1)
    def _finish():
        o_ref[0, 0] = (acc_ref[:] / jnp.maximum(l_ref[:, :1], 1e-30)
                       ).astype(o_ref.dtype)


def _run_attn(q, planes, layer, pos, scale, *, bk, table=None, live=None):
    """Sweep ``q [b, h, d]`` over layer ``layer`` of ``planes``:
    ``[kv]`` or the quantized ``[kv, scale]``, stacked contiguous ``[L,
    2, b, h, S(, d)]`` in ``bk``-position chunks or — ``table [b,
    max_pages]`` given — page pools ``[L, 2, num_pages, h, P(, d)]``
    one page per chunk (``bk == P``; chunk ``j`` of row ``b`` streams
    page ``table[b, j]``). The K and the V chunk are blocks of plane 0
    and plane 1 of the one ``kv`` operand, read where and as it lies
    (:func:`_positions_on_lanes`: ``(d, bk)`` blocks of ``swapaxes(kv,
    4, 5)``, or ``(bk, d)`` blocks of ``kv``), ``hb`` heads at a time
    (:func:`_heads_per_step`). The grid's first axis runs over the
    rows that ``live`` (a :func:`live_rows` list; None: every row)
    names, and only the chunks a row attends are fetched
    (:func:`_block_index`); a dead row is not in the grid at all, and
    its output is the zeros aliased to the output, untouched.

    One layer's fp32 scale planes (a sixteenth of its int8/fp8 bytes
    at head size 64) are sliced out for the read, heads on the
    sublanes and positions on the lanes as they lie; the storage
    planes are not."""
    b, h, d = q.shape
    kv = planes[0]
    quant = len(planes) == 2
    paged = table is not None
    mp = table.shape[1] if paged else 0
    smax = mp * bk if paged else kv.shape[4]
    chunks = mp if paged else -(-smax // bk)
    lanes = _positions_on_lanes(d, kv.shape[4], kv.dtype)
    if lanes:
        kv = jnp.swapaxes(kv, 4, 5)
    hb = _heads_per_step(h, d, bk, kv.dtype, quant, lanes)
    groups = h // hb

    rows, grid_b = _grid_rows(live, b)

    def block(i, g, j, pos_ref, rows_ref, tbl_ref):
        # (leading, head-group, position-chunk) block index
        row, g, c = _block_index(i, g, j, pos_ref, rows_ref, bk)
        if paged:
            return tbl_ref[0][row * mp + c], g, 0
        return row, g, c

    def data_map(plane):
        def index(i, g, j, layer_ref, pos_ref, rows_ref, *tbl_ref):
            lead, g, c = block(i, g, j, pos_ref, rows_ref, tbl_ref)
            return (layer_ref[0], plane, lead, g) + (
                (0, c) if lanes else (c, 0))
        return index

    def scale_map(plane):
        def index(i, g, j, layer_ref, pos_ref, rows_ref, *tbl_ref):
            lead, g, c = block(i, g, j, pos_ref, rows_ref, tbl_ref)
            return plane, lead, g, 0, c
        return index

    row_spec = pl.BlockSpec(
        (1, 1, hb, d),
        lambda i, g, j, layer_ref, pos_ref, rows_ref, *_:
        (rows_ref[i], g, 0, 0))
    operands, specs = [], []
    if quant:
        sc = lax.dynamic_index_in_dim(
            planes[1], jnp.asarray(layer, jnp.int32), 0, keepdims=False)
        scale_rows = sc.reshape(sc.shape[:2] + (groups, hb, sc.shape[3]))
    for plane in (0, 1):
        operands.append(kv)
        specs.append(pl.BlockSpec(
            (None, None, 1, hb) + ((d, bk) if lanes else (bk, d)),
            data_map(plane)))
        if quant:
            operands.append(scale_rows)
            specs.append(pl.BlockSpec((None, 1, 1, hb, bk),
                                      scale_map(plane)))
    out_shape = jax.ShapeDtypeStruct((b, groups, hb, d), q.dtype)
    # the rows the grid does not name keep these zeros: an output block
    # no step names is never written back
    zeros = ([] if live is None else
             [jnp.zeros(out_shape.shape, out_shape.dtype)])
    scalars = [_layer_scalar(layer), pos, rows]
    if paged:
        scalars.append(jnp.asarray(table, jnp.int32).reshape(-1))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(scalars),
        grid=(grid_b, groups, chunks),
        in_specs=([pl.BlockSpec(memory_space=pl.ANY)] * len(zeros)
                  + [row_spec] + specs),
        out_specs=row_spec,
        scratch_shapes=[
            pltpu.VMEM((hb, d), jnp.float32),
            pltpu.VMEM((hb, _LANES), jnp.float32),
            pltpu.VMEM((hb, _LANES), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_attn_kernel, n_scalar=len(scalars),
                          quant=quant, lanes=lanes, scale=scale, bk=bk,
                          smax=smax, zeros=len(zeros)),
        grid_spec=grid_spec,
        out_shape=out_shape,
        # operand order: (*scalars, *zeros, q, *operands)
        input_output_aliases={len(scalars): 0} if zeros else {},
        name="decode_attn_read",
        interpret=use_interpret(),
    )(*scalars, *zeros, q.reshape(b, groups, hb, d), *operands)
    return out.reshape(b, h, d)


# ---------------------------------------------------------------------------
# public API
# ---------------------------------------------------------------------------

def stacked_decode_attention(q, k_new, v_new, cache, layer, pos, *,
                             table=None, live=None,
                             kind: Optional[str] = None,
                             scale: Optional[float] = None,
                             block_k: Optional[int] = None):
    """One decode step of attention for every (batch, head) row, in
    layer ``layer`` of the stacked cache — the form the model's layer
    scan calls with the cache in its carry.

    ``q``/``k_new``/``v_new`` are ``[b, h, d]`` (this token's projected
    query and cache entries); ``cache`` is ``[L, 2, b, h, S, d]``, or
    the page pool ``[L, 2, num_pages, h, P, d]`` under ``table [b,
    max_pages] int32``, or — ``kind`` ``"int8"``/``"fp8"`` — the
    quantized ``{"kv": storage, "scale": fp32 [..., S]}`` pair of
    either; ``layer`` an int32 scalar (traced or not); ``pos`` ``[b]
    int32`` each row's write/attend position (``0 <= pos[i] < S``;
    ``gpt.decode_step`` guarantees this by freezing done slots);
    ``live`` optional, :func:`live_rows` of the ``[b] bool`` mask that
    is False for a row whose output the caller discards (a done or an
    empty slot): both kernels' grids run over the live rows alone, so
    a dead row's column is NOT written — its cache keeps the bytes it
    had, every layer — the read fetches none of its history, does no
    arithmetic for it and returns zeros in its row, and a step with no
    live row leaves the cache byte for byte as it was. ``live`` None:
    every row is live. Returns ``(out [b, h, d], cache)``.

    The cache holds the new column at ``(layer, pos)`` of every live
    row: the write kernel aliases the whole stacked cache input→output
    and moves only the windows it touches, so a caller that owns the
    buffer (a
    scan carry) keeps it in place; a caller that still holds the input
    pays XLA's copy of all of it. ``out`` attends over positions
    ``0..pos[i]`` inclusive, bit-exactly masked like the XLA path: rows
    past ``pos`` are exact softmax zeros, so stale cache garbage — NaN
    bit patterns of stale quantized cells included — never leaks into
    the output. Under a quantized layout the incoming rows are
    quantized (:func:`quantize_kv_rows` — bit-identical to the XLA
    fallback and bulk prefill) and the split-K sweep reads the narrow
    cache and folds the scales into the fp32 scores/probabilities per
    chunk, so the steady-decode HBM read traffic shrinks with the
    storage width.

    ``scale`` defaults to ``1/sqrt(d)`` and is applied to the fp32
    scores (no overflow at any IO dtype — the XLA path instead folds it
    into q in compute dtype, the fp16-range guard a fp32-accumulating
    kernel doesn't need). A float16-stored cache is widened to fp32
    and narrowed back WHOLE (Mosaic has no f16): correct, and never the
    fast path — ``gpt._decode_attn_impl`` keeps such caches on XLA.
    """
    # [kv], or [kv, scale] of the quantized {"kv", "scale"} pair
    planes, layout = jax.tree.flatten(cache)
    kv = planes[0]
    b, h, d = q.shape
    if kv.ndim != 6 or kv.shape[1] != 2 or kv.shape[3::2] != (h, d):
        raise ValueError(
            f"expected q [b, h, d] and a stacked cache [L, 2, rows, h, "
            f"S, d], got {q.shape} / {kv.shape}")
    if table is None and kv.shape[2] != b:
        raise ValueError(
            f"cache shape {kv.shape} inconsistent with q {q.shape}")
    if pos.shape != (b,):
        raise ValueError(f"pos must be [{b}], got {pos.shape}")
    if live is not None and live.shape != (b + 1,):
        raise ValueError(f"live must be live_rows of a [{b}] mask, "
                         f"[{b + 1}], got {live.shape}")
    s = float(scale) if scale is not None else 1.0 / d ** 0.5
    q, was16 = widen_f16(q)
    cache16 = kv.dtype == jnp.float16
    planes[0] = kv = widen_f16(kv)[0]
    pos = jnp.asarray(pos, jnp.int32)
    planes = list(_write_column_planes(
        _stack_news(k_new, v_new, kind), planes, layer, pos, table,
        live))
    if table is not None:
        bk = kv.shape[4]
    else:
        bk = decode_block_k(kv.shape[4], kv.dtype, quantized=bool(kind),
                            block_k=block_k, head_dim=d)
    out = _run_attn(q, planes, layer, pos, s, bk=bk, table=table,
                    live=live)
    if was16:
        out = out.astype(jnp.float16)
    if cache16:
        planes[0] = planes[0].astype(jnp.float16)
    return out, jax.tree.unflatten(layout, planes)


def decode_attention(q, k_new, v_new, k_cache, v_cache, pos, *,
                     scale: Optional[float] = None,
                     block_k: Optional[int] = None):
    """:func:`stacked_decode_attention` for one layer's planes:
    ``k_cache``/``v_cache`` ``[b, h, S, d]``. Returns ``(out [b, h, d],
    k_cache, v_cache)`` with the new column at ``pos``. The two planes
    are stacked into a one-layer cache on the way in — a copy of both;
    a caller that wants the column landed in place hands the stacked
    form its whole cache."""
    if q.ndim != 3 or k_cache.ndim != 4:
        raise ValueError(
            f"expected q [b, h, d] and caches [b, h, S, d], got "
            f"{q.shape} / {k_cache.shape}")
    b, h, d = q.shape
    if k_cache.shape != (b, h, k_cache.shape[2], d):
        raise ValueError(
            f"cache shape {k_cache.shape} inconsistent with q {q.shape}")
    out, cache = stacked_decode_attention(
        q, k_new, v_new, _one_layer(k_cache, v_cache), 0, pos,
        scale=scale, block_k=block_k)
    return (out, *_layer_planes(cache))


# ---------------------------------------------------------------------------
# quantized cache layout: int8/fp8 storage + per-row fp32 scales
# ---------------------------------------------------------------------------

#: symmetric quantization range per storage kind (int8 keeps the signed
#: range symmetric at ±127; fp8 e4m3fn saturates at ±448)
KV_QMAX = {"int8": 127.0, "fp8": 448.0}


def kv_storage_dtype(kind: str):
    """jnp storage dtype of a quantized-KV kind."""
    if kind == "int8":
        return jnp.int8
    if kind == "fp8":
        return jnp.float8_e4m3fn
    raise ValueError(f"unknown quantized-KV kind {kind!r}")


def quantize_kv_rows(x, kind: str):
    """THE KV quantizer: ``x [..., head_dim]`` (one K or V row per
    leading coordinate) → ``(q [..., head_dim] storage, scale [...]
    fp32)``. Symmetric absmax per row, deterministic round-to-nearest-
    even — the kernel column write, the XLA-fallback write, bulk
    prefill, and the prefix pool all call exactly this, so any two
    paths fed the same K/V bits produce the same cache bytes (the
    prefix-reuse bit-parity oracle leans on that; kernel-vs-XLA decode
    runs are separate compiled programs whose K/V inputs already differ
    at the usual ulp level, so THAT pair is tolerance-bounded like
    every other kernel oracle)."""
    xf = x.astype(jnp.float32)
    qmax = KV_QMAX[kind]
    amax = jnp.max(jnp.abs(xf), axis=-1)
    # multiply by the reciprocal EXPLICITLY: XLA rewrites x / <const>
    # into x * (1/<const>) in some lowerings but not others — spelling
    # it one way keeps every lowering of THIS function bit-identical
    scale = jnp.maximum(amax, jnp.float32(1e-12)) * jnp.float32(
        1.0 / qmax)
    y = xf / scale[..., None]
    if kind == "int8":
        q = jnp.clip(jnp.round(y), -qmax, qmax).astype(jnp.int8)
    else:
        q = jnp.clip(y, -qmax, qmax).astype(jnp.float8_e4m3fn)
    return q, scale


# ---------------------------------------------------------------------------
# paged cache layout: a global page pool + per-row block tables
#
# The contiguous layout above stores one [S]-horizon stripe per batch
# row; the paged layout stores a GLOBAL pool of fixed-size pages
# ``[num_pages, h, P, d]`` plus a per-row block table ``[b, max_pages]
# int32`` mapping each row's logical chunk j of the horizon onto a
# physical page. The split-K sweep already walks the horizon in
# ``block_k`` chunks through a scalar-prefetched index map — a page is
# nothing but a SECOND indirection on that chunk index (``block_k`` ==
# the page size, and the chunk's block index is ``table[b, j]`` instead
# of ``j``), so the read kernel is the same online-softmax merge with a
# remapped prefetch. Writes land at ``(table[b, pos // P], pos % P)``.
# Everything stays static-shaped: tables are DATA (never shapes), and
# a row's effective horizon is ``max_pages * P`` with the same
# ``col <= pos`` masking contract as the contiguous kernels. The XLA
# fallbacks (`paged_gather_xla` / `paged_write_columns_xla`) give the
# CPU tier-1 suite bit-exact oracle semantics: a gather of the same
# cache bytes into the contiguous shape, followed by the SAME
# materialised-scores expressions.
# ---------------------------------------------------------------------------


def paged_gather_xla(plane, table):
    """Gather a row-contiguous view of a paged cache plane: ``plane
    [num_pages, h, P(, d)]`` indexed by ``table [b, max_pages]`` →
    ``[b, h, max_pages * P(, d)]``. THE paged read fallback: the
    gathered array holds exactly the bytes a contiguous cache would,
    so feeding it to the contiguous score expressions keeps paged
    decode bit-identical to contiguous decode (the paged == contiguous
    stream oracle stands on this)."""
    g = jnp.take(plane, jnp.asarray(table, jnp.int32), axis=0)
    if plane.ndim == 4:
        b, mp, h, p, d = g.shape
        return jnp.transpose(g, (0, 2, 1, 3, 4)).reshape(b, h, mp * p, d)
    if plane.ndim == 3:
        b, mp, h, p = g.shape
        return jnp.transpose(g, (0, 2, 1, 3)).reshape(b, h, mp * p)
    raise ValueError(
        f"paged plane must be [num_pages, h, P(, d)], got rank "
        f"{plane.ndim}")


def paged_write_columns_xla(plane, new, table, pos):
    """Write ``new [b, h, T(, d)]`` into logical columns ``pos[b] + j``
    of a paged cache plane ``plane [num_pages, h, P(, d)]`` under
    ``table [b, max_pages]`` — the paged spelling of
    :func:`cache_write_columns_xla`. Columns at or past the row's
    ``max_pages * P`` horizon are DROPPED (the same over-horizon write
    guard). Rows must target distinct physical (page, offset) cells
    except inside a shared garbage/sink page, where a collision writes
    an arbitrary colliding row's value — the sink holds garbage by
    contract (done rows redirected there never have their lanes read).
    """
    p = plane.shape[2]
    n_pages = plane.shape[0]
    mp = table.shape[1]
    smax = mp * p
    t = new.shape[2]
    pos = jnp.asarray(pos, jnp.int32)
    cols = pos[:, None] + jnp.arange(t, dtype=jnp.int32)[None]   # [b, T]
    inb = cols < smax
    colc = jnp.clip(cols, 0, smax - 1)
    pages = jnp.take_along_axis(jnp.asarray(table, jnp.int32),
                                colc // p, axis=1)               # [b, T]
    flat = pages * p + colc % p                                  # [b, T]
    s_total = n_pages * p
    onehot = ((jnp.arange(s_total, dtype=jnp.int32)[None, None]
               == flat[:, :, None]) & inb[:, :, None])           # [b,T,S]
    oh = onehot.reshape(-1, s_total)                             # [bT, S]
    hit = oh.any(axis=0)                                         # [S]
    # per-cell source row: argmax picks the first hitter (selection,
    # not arithmetic — an int8 einsum accumulation could overflow)
    src = jnp.argmax(oh, axis=0)                                 # [S]
    if plane.ndim == 4:
        new_flat = jnp.transpose(new, (0, 2, 1, 3)).reshape(
            -1, new.shape[1], new.shape[3])                      # [bT,h,d]
        taken = jnp.take(new_flat, src, axis=0)                  # [S,h,d]
        flat_plane = jnp.transpose(plane, (0, 2, 1, 3)).reshape(
            s_total, plane.shape[1], plane.shape[3])
        out = jnp.where(hit[:, None, None], taken.astype(plane.dtype),
                        flat_plane)
        return jnp.transpose(
            out.reshape(n_pages, p, plane.shape[1], plane.shape[3]),
            (0, 2, 1, 3))
    if plane.ndim == 3:
        new_flat = jnp.transpose(new, (0, 2, 1)).reshape(
            -1, new.shape[1])                                    # [bT, h]
        taken = jnp.take(new_flat, src, axis=0)                  # [S, h]
        flat_plane = jnp.transpose(plane, (0, 2, 1)).reshape(
            s_total, plane.shape[1])
        out = jnp.where(hit[:, None], taken.astype(plane.dtype),
                        flat_plane)
        return jnp.transpose(out.reshape(n_pages, p, plane.shape[1]),
                             (0, 2, 1))
    raise ValueError(
        f"paged plane must be [num_pages, h, P(, d)], got rank "
        f"{plane.ndim}")


def paged_write_column(k_new, v_new, k_pool, v_pool, table, pos):
    """Write ``k_new/v_new [b, h, d]`` into logical column ``pos[b]``
    of the paged pools ``[num_pages, h, P, d]`` under ``table [b,
    max_pages]``: the cell is ``(table[b, pos // P], pos % P)``.
    Returns ``(k_pool, v_pool)``."""
    return paged_write_columns(k_new[:, :, None], v_new[:, :, None],
                               k_pool, v_pool, table, pos)


def paged_write_column_quant(k_new, v_new, k_q, k_s, v_q, v_s, table,
                             pos, kind):
    """:func:`paged_write_column` over the quantized pool layout
    (``[num_pages, h, P, d]`` storage + ``[num_pages, h, P]`` fp32
    scales): the incoming rows are quantized (:func:`quantize_kv_rows`
    — the one deterministic quantizer) and land one quantized + one
    scale cell at ``(table[b, pos // P], pos % P)`` across all four
    planes."""
    return paged_write_columns_quant(
        k_new[:, :, None], v_new[:, :, None], k_q, k_s, v_q, v_s, table,
        pos, kind)


def paged_write_columns(k_new, v_new, k_pool, v_pool, table, pos):
    """Write ``k_new/v_new [b, h, T, d]`` into logical columns
    ``pos[b] .. pos[b] + T - 1`` of the paged pools — the paged
    :func:`cache_write_columns` (the speculative verify forward's cache
    landing). Over-horizon lanes CLAMP onto the row's last logical
    column ``max_pages * P - 1`` (the contiguous kernel's contract —
    that cell is only ever read by discarded lanes)."""
    return _layer_planes(stacked_write_columns(
        k_new, v_new, _one_layer(k_pool, v_pool), 0, pos, table=table))


def paged_write_columns_quant(k_new, v_new, k_q, k_s, v_q, v_s, table,
                              pos, kind):
    """:func:`paged_write_columns` over the quantized pool layout:
    each incoming row is quantized and lands one quantized + one scale
    cell per lane; same clamped over-horizon contract."""
    return _layer_planes(stacked_write_columns(
        k_new, v_new, _one_layer(k_q, v_q, k_s, v_s), 0, pos,
        table=table, kind=kind))


def _paged_read(q, cache, table, pos, scale):
    """The split-K sweep alone, over a one-layer page pool."""
    d = q.shape[2]
    s = float(scale) if scale is not None else 1.0 / d ** 0.5
    q, was16 = widen_f16(q)
    planes = jax.tree.leaves(cache)
    planes[0], _ = widen_f16(planes[0])
    out = _run_attn(q, planes, 0, jnp.asarray(pos, jnp.int32), s,
                    bk=planes[0].shape[4], table=table)
    return out.astype(jnp.float16) if was16 else out


def paged_attention(q, k_pool, v_pool, table, pos, *,
                    scale: Optional[float] = None):
    """Split-K flash-decode over the paged pool: ``q [b, h, d]``
    against ``k_pool/v_pool [num_pages, h, P, d]`` under ``table [b,
    max_pages]`` and per-row ``pos [b]`` — chunk ``j`` of row ``b``'s
    sweep streams page ``table[b, j]`` (the scalar-prefetched remap of
    the contiguous chunk index). Returns ``out [b, h, d]`` attending
    columns ``0..pos[b]`` with the contiguous kernel's exact masking
    contract; the read alone, for a column that
    :func:`paged_write_column` has landed."""
    return _paged_read(q, _one_layer(k_pool, v_pool), table, pos, scale)


def paged_attention_quantized(q, k_q, k_s, v_q, v_s, table, pos, *,
                              kind: str = "int8",
                              scale: Optional[float] = None):
    """:func:`paged_attention` over the quantized pool layout: int8/fp8
    ``[num_pages, h, P, d]`` storage with fp32 ``[num_pages, h, P]``
    scales, scales folded into the fp32 scores/probabilities per page
    exactly like the contiguous quantized sweep."""
    if kind not in KV_QMAX:
        raise ValueError(f"unknown quantized-KV kind {kind!r}")
    return _paged_read(q, _one_layer(k_q, v_q, k_s, v_s), table, pos,
                       scale)


def decode_attention_quantized(q, k_new, v_new, k_q, k_scale, v_q,
                               v_scale, pos, *, kind: str = "int8",
                               scale: Optional[float] = None,
                               block_k: Optional[int] = None):
    """:func:`decode_attention` over the quantized cache layout: K/V
    stored as ``kind`` (``"int8"``/``"fp8"``) ``[b, h, S, d]`` with
    per-head, per-slot, per-position fp32 scales ``[b, h, S]``.
    Returns ``(out [b, h, d], k_q, k_scale, v_q, v_scale)``; masking
    and quantization as :func:`stacked_decode_attention` states them."""
    if q.ndim != 3 or k_q.ndim != 4:
        raise ValueError(
            f"expected q [b, h, d] and quantized caches [b, h, S, d], "
            f"got {q.shape} / {k_q.shape}")
    b, h, d = q.shape
    sk = k_q.shape[2]
    if k_q.shape != (b, h, sk, d) or k_scale.shape != (b, h, sk):
        raise ValueError(
            f"cache shapes {k_q.shape} / {k_scale.shape} inconsistent "
            f"with q {q.shape}")
    if kind not in KV_QMAX:
        raise ValueError(f"unknown quantized-KV kind {kind!r}")
    out, cache = stacked_decode_attention(
        q, k_new, v_new, _one_layer(k_q, v_q, k_scale, v_scale), 0, pos,
        kind=kind, scale=scale, block_k=block_k)
    return (out, *_layer_planes(cache))
