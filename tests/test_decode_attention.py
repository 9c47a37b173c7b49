"""Flash-decode kernel oracles (`kernels/decode_attention.py`).

Oracle pattern (SURVEY.md §4): the Pallas kernel vs the materialised-
scores XLA decode path with per-dtype tolerances — both standalone
(kernel vs fp32 numpy reference) and integrated (a full ``decode_step``
with ``decode_attn_impl="kernel"`` vs ``"xla"``), plus the one-column
cache-write contract: every cache byte outside the written column is
bit-identical to the input. The layer-indexed (stacked-cache) forms are
held to the per-layer calls for every layer, and a greedy and a sampled
``generate`` stream are pinned to what the slice-and-stack scan of
PR 24 emitted."""

import dataclasses
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from apex_tpu import mesh as mx
from apex_tpu.kernels import decode_attention
from apex_tpu.kernels.decode_attention import (
    cache_write_columns,
    cache_write_columns_quant,
    decode_attention_quantized,
    kv_storage_dtype,
    paged_attention,
    paged_attention_quantized,
    paged_write_column,
    paged_write_column_quant,
    paged_write_columns,
    paged_write_columns_quant,
    quantize_kv_rows,
    stacked_decode_attention,
    stacked_write_columns,
)
from apex_tpu.models import gpt
from apex_tpu.transformer.testing import standalone_gpt_config

# the module, not the function the package re-exports under its name
decode_attention_mod = importlib.import_module(
    "apex_tpu.kernels.decode_attention")

_TOL = {
    jnp.float32: dict(rtol=2e-5, atol=2e-5),
    jnp.bfloat16: dict(rtol=3e-2, atol=3e-2),
    jnp.float16: dict(rtol=2e-3, atol=2e-3),
}


_ORIENTATIONS = {"lanes": True, "rows": False}


@pytest.fixture(params=list(_ORIENTATIONS))
def orientation(request, monkeypatch):
    """Both ways the kernels take their K/V operand — positions on the
    lanes, ``(d, bk)`` blocks of the turned cache, or row-major ``(bk,
    d)`` — whatever the shapes' own rule would pick (the interpreter
    has no tiles to respect). Programs are jitted through a fresh
    lambda in every test that uses this: a trace cached under the other
    orientation must not be handed back."""
    lanes = _ORIENTATIONS[request.param]
    monkeypatch.setattr(decode_attention_mod, "_positions_on_lanes",
                        lambda d, span, dtype: lanes)
    return lanes


@pytest.mark.parametrize("d, span, dtype, lanes", [
    (64, 1024, jnp.bfloat16, True),      # GPT-2 heads, the cells' horizon
    (64, 1024, jnp.int8, True),
    (64, 1024, jnp.float8_e4m3fn, True),
    (64, 128, jnp.bfloat16, True),       # pages of 128
    (80, 2048, jnp.bfloat16, True),      # GPT-3 2.7B heads
    (128, 1024, jnp.bfloat16, False),    # the head dim fills the lanes
    (256, 1024, jnp.bfloat16, False),
    (64, 16, jnp.bfloat16, False),       # pages of 16
    (64, 1000, jnp.bfloat16, False),     # a horizon off the lane grid
    (80, 2048, jnp.int8, False),         # 80 is no multiple of int8's 32
], ids=lambda v: getattr(v, "__name__", str(v)))
def test_orientation_rule(d, span, dtype, lanes):
    """The one function that turns the kernels' operand: positions on
    the lanes where the device lays the array out that way (a head dim
    that does not fill the lanes, over a span that does) and the head
    dim fills whole sublane tiles; row-major otherwise."""
    assert decode_attention_mod._positions_on_lanes(d, span, dtype) is lanes


def _reference(q, k_new, v_new, k_cache, v_cache, pos):
    """fp32 numpy: write the column, mask ``<= pos``, plain softmax."""
    q, k_new, v_new, k_cache, v_cache = (
        np.asarray(t, np.float32)
        for t in (q, k_new, v_new, k_cache, v_cache))
    b, h, S, d = k_cache.shape
    kc, vc = k_cache.copy(), v_cache.copy()
    for i in range(b):
        kc[i, :, int(pos[i])] = k_new[i]
        vc[i, :, int(pos[i])] = v_new[i]
    s = np.einsum("bhd,bhsd->bhs", q, kc) / np.sqrt(d)
    valid = np.arange(S)[None, None] <= np.asarray(pos)[:, None, None]
    s = np.where(valid, s, -1e30)
    p = np.exp(s - s.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    return np.einsum("bhs,bhsd->bhd", p, vc), kc, vc


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16, jnp.float16])
def test_kernel_matches_fp32_reference(orientation, dtype):
    """Standalone oracle across dtypes, at a horizon that is not a
    multiple of the split-K chunk (exercises the padded tail) and with
    per-row positions spanning first/mid/last slots."""
    b, h, S, d = 3, 4, 19, 32
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    mk = lambda k, shp: (jax.random.normal(k, shp) * 0.5).astype(dtype)
    q = mk(ks[0], (b, h, d))
    k_new = mk(ks[1], (b, h, d))
    v_new = mk(ks[2], (b, h, d))
    k_cache = mk(ks[3], (b, h, S, d))
    v_cache = mk(ks[4], (b, h, S, d))
    pos = jnp.asarray([2, 0, 18], jnp.int32)
    out, kc, vc = jax.jit(lambda *a: decode_attention(*a))(
        q, k_new, v_new, k_cache, v_cache, pos)
    ref_out, ref_kc, ref_vc = _reference(
        q, k_new, v_new, k_cache, v_cache, pos)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), ref_out, **_TOL[dtype])
    # one-column write contract: outside the written column the cache
    # is BIT-identical to the input; the column holds k_new/v_new
    for got, want, orig in ((kc, ref_kc, k_cache), (vc, ref_vc, v_cache)):
        got = np.asarray(got, np.float32)
        orig = np.asarray(orig, np.float32)
        col = np.zeros((b, h, S, d), bool)
        for i in range(b):
            col[i, :, int(pos[i])] = True
        np.testing.assert_array_equal(got[~col], orig[~col])
        np.testing.assert_allclose(got[col], want[col], **_TOL[dtype])


def test_kernel_masks_stale_cache_garbage(orientation):
    """Entries past a row's position must be exact softmax zeros: a
    cache whose masked tail holds huge garbage yields the same output
    as one holding zeros (the engine's padded-prefill contract)."""
    b, h, S, d = 2, 2, 12, 32
    ks = jax.random.split(jax.random.PRNGKey(1), 5)
    q = jax.random.normal(ks[0], (b, h, d))
    k_new = jax.random.normal(ks[1], (b, h, d))
    v_new = jax.random.normal(ks[2], (b, h, d))
    k_cache = jax.random.normal(ks[3], (b, h, S, d))
    v_cache = jax.random.normal(ks[4], (b, h, S, d))
    pos = jnp.asarray([3, 7], jnp.int32)
    tail = jnp.arange(S)[None, None, :, None] > pos[:, None, None, None]
    run = jax.jit(lambda *a: decode_attention(*a))
    out_clean, _, _ = run(
        q, k_new, v_new,
        jnp.where(tail, 0.0, k_cache), jnp.where(tail, 0.0, v_cache), pos)
    out_junk, _, _ = run(
        q, k_new, v_new,
        jnp.where(tail, 1e30, k_cache), jnp.where(tail, -1e30, v_cache),
        pos)
    np.testing.assert_array_equal(
        np.asarray(out_clean), np.asarray(out_junk))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_step_kernel_matches_xla(devices8, dtype):
    """Integration oracle: a full ``decode_step`` (vector per-slot
    positions, tp sharded) through ``decode_attn_impl="kernel"``
    matches the materialised-scores XLA path at unchanged per-dtype
    tolerances — logits AND updated cache."""
    cfg0 = standalone_gpt_config(vocab_size=96, seq_len=32,
                                 compute_dtype=dtype)
    prompt = jax.random.randint(jax.random.PRNGKey(1), (4, 6), 0, 96)
    tok = jax.random.randint(jax.random.PRNGKey(2), (4,), 0, 96)
    pos = jnp.asarray([6, 3, 1, 5], jnp.int32)
    outs = {}
    for tp in (1, 2):
        mesh = mx.build_mesh(tp=tp, devices=devices8[:tp])
        for impl in ("xla", "kernel"):
            cfg = dataclasses.replace(cfg0, decode_attn_impl=impl)
            params = gpt.init(cfg, jax.random.PRNGKey(0))
            pspecs = gpt.param_specs(cfg)

            def run(p, t, tk):
                cache, _ = gpt.prefill(cfg, p, t, max_len=cfg.seq_len)
                return gpt.decode_step(cfg, p, cache, tk, pos)

            lg, cache = jax.jit(jax.shard_map(
                run, mesh=mesh,
                in_specs=(pspecs, P(None, None), P(None)),
                out_specs=(P(None, None),
                           P(None, None, None, "tp", None, None)),
                check_vma=False))(params, prompt, tok)
            outs[(tp, impl)] = (np.asarray(lg, np.float32),
                                np.asarray(cache, np.float32))
    tol = _TOL[dtype]
    for tp in (1, 2):
        got_lg, got_c = outs[(tp, "kernel")]
        want_lg, want_c = outs[(tp, "xla")]
        np.testing.assert_allclose(got_lg, want_lg, err_msg=f"tp{tp}",
                                   **tol)
        np.testing.assert_allclose(got_c, want_c, err_msg=f"tp{tp}",
                                   **tol)


_QTOL = {"int8": dict(rtol=3e-2, atol=3e-2),
         "fp8": dict(rtol=6e-2, atol=6e-2)}


@pytest.mark.parametrize("kind", ["int8", "fp8"])
def test_quantized_kernel_matches_fp32_reference(orientation, kind):
    """Quantized-cache kernel oracle: output within the quantization
    error band of the unquantized fp32 reference, and the one-column
    write contract holds on BOTH planes — outside the written column
    the int8/fp8 data and fp32 scales are bit-identical to the input,
    the column holds exactly ``quantize_kv_rows(new)``."""
    b, h, S, d = 3, 4, 19, 32
    ks = jax.random.split(jax.random.PRNGKey(3), 5)
    mk = lambda k, shp: jax.random.normal(k, shp) * 0.5
    q = mk(ks[0], (b, h, d))
    k_new = mk(ks[1], (b, h, d))
    v_new = mk(ks[2], (b, h, d))
    k_raw = mk(ks[3], (b, h, S, d))
    v_raw = mk(ks[4], (b, h, S, d))
    kq0, ks0 = quantize_kv_rows(k_raw, kind)
    vq0, vs0 = quantize_kv_rows(v_raw, kind)
    pos = jnp.asarray([2, 0, 18], jnp.int32)
    out, kq, ksc, vq, vsc = jax.jit(
        lambda *a: decode_attention_quantized(
            *a, kind=kind))(q, k_new, v_new, kq0, ks0, vq0, vs0, pos)
    # reference: unquantized fp32 math over the DEQUANTIZED cache (the
    # cache held quantized values; the new column is exact pre-quant)
    deq = lambda qv, s: np.asarray(qv, np.float32) * np.asarray(
        s, np.float32)[..., None]
    ref_out, _, _ = _reference(q, k_new, v_new, deq(kq0, ks0),
                               deq(vq0, vs0), pos)
    np.testing.assert_allclose(np.asarray(out, np.float32), ref_out,
                               **_QTOL[kind])
    # write contract, both planes
    col = np.zeros((b, h, S), bool)
    for i in range(b):
        col[i, :, int(pos[i])] = True
    for got, orig, new in ((kq, kq0, k_new), (vq, vq0, v_new)):
        got = np.asarray(got, np.float32)
        orig = np.asarray(orig, np.float32)
        np.testing.assert_array_equal(got[~col], orig[~col])
        want_q, _ = quantize_kv_rows(new, kind)
        np.testing.assert_array_equal(
            got[col].reshape(b, h, d), np.asarray(want_q, np.float32))
    for got, orig, new in ((ksc, ks0, k_new), (vsc, vs0, v_new)):
        got, orig = np.asarray(got), np.asarray(orig)
        np.testing.assert_array_equal(got[~col], orig[~col])
        _, want_s = quantize_kv_rows(new, kind)
        np.testing.assert_array_equal(got[col].reshape(b, h),
                                      np.asarray(want_s))


def test_quantized_kernel_masks_stale_garbage(orientation):
    """Positions past a row's ``pos`` are exact softmax zeros even when
    the quantized tail holds saturated garbage and the scale plane
    holds NaN (an uninitialised-HBM bit pattern a fresh fp32 plane can
    legally contain)."""
    b, h, S, d = 2, 2, 12, 32
    ks = jax.random.split(jax.random.PRNGKey(5), 5)
    q = jax.random.normal(ks[0], (b, h, d))
    k_new = jax.random.normal(ks[1], (b, h, d))
    v_new = jax.random.normal(ks[2], (b, h, d))
    kq0, ks0 = quantize_kv_rows(
        jax.random.normal(ks[3], (b, h, S, d)), "int8")
    vq0, vs0 = quantize_kv_rows(
        jax.random.normal(ks[4], (b, h, S, d)), "int8")
    pos = jnp.asarray([3, 7], jnp.int32)
    tail3 = jnp.arange(S)[None, None, :] > pos[:, None, None]
    tail4 = tail3[..., None]
    run = jax.jit(lambda *a: decode_attention_quantized(
        *a, kind="int8"))
    out_clean, *_ = run(q, k_new, v_new,
                        jnp.where(tail4, 0, kq0),
                        jnp.where(tail3, 0.0, ks0),
                        jnp.where(tail4, 0, vq0),
                        jnp.where(tail3, 0.0, vs0), pos)
    out_junk, *_ = run(q, k_new, v_new,
                       jnp.where(tail4, jnp.int8(-127), kq0),
                       jnp.where(tail3, jnp.float32(jnp.nan), ks0),
                       jnp.where(tail4, jnp.int8(127), vq0),
                       jnp.where(tail3, jnp.float32(jnp.nan), vs0), pos)
    np.testing.assert_array_equal(np.asarray(out_clean),
                                  np.asarray(out_junk))


def test_decode_attention_validation():
    b, h, S, d = 2, 2, 8, 32
    z3 = jnp.zeros((b, h, d))
    z4 = jnp.zeros((b, h, S, d))
    with pytest.raises(ValueError, match="expected q"):
        decode_attention(z4, z3, z3, z4, z4, jnp.zeros((b,), jnp.int32))
    with pytest.raises(ValueError, match="pos must be"):
        decode_attention(z3, z3, z3, z4, z4, jnp.zeros((3,), jnp.int32))
    with pytest.raises(ValueError, match="unknown decode_attn_impl"):
        gpt._decode_attn_impl(
            standalone_gpt_config(decode_attn_impl="nope"), 8)
    # off-TPU "auto" resolves to the XLA path (Pallas runs interpreted),
    # and f16 does everywhere (the kernel boundary would widen the full
    # caches per layer per token)
    assert gpt._decode_attn_impl(standalone_gpt_config(), 4096) == "xla"
    assert gpt._decode_attn_impl(
        standalone_gpt_config(compute_dtype=jnp.float16), 4096) == "xla"


# ---------------------------------------------------------------------------
# the layer-indexed kernels on the stacked cache
# ---------------------------------------------------------------------------

_L, _B, _H, _S, _D, _PAGE = 3, 3, 2, 32, 32, 8


def _stacked_case(layout, kind, b=_B, h=_H, s=_S, d=_D, page=_PAGE,
                  pos=None):
    """A random stacked cache (or page pool + block tables) of ``_L``
    layers in the storage of ``kind``, with one step's rows."""
    ks = jax.random.split(jax.random.PRNGKey(3), 6)
    dtype = jnp.bfloat16 if kind == "bf16" else jnp.float32
    mk = lambda k, shp: (jax.random.normal(k, shp) * 0.5).astype(dtype)
    rows = b * s // page + 4 if layout == "paged" else b
    horizon = page if layout == "paged" else s
    raw = mk(ks[0], (_L, 2, rows, h, horizon, d))
    if kind == "bf16":
        cache = raw
    else:
        q, scale = quantize_kv_rows(raw, kind)
        cache = {"kv": q, "scale": scale}
    table = None
    if layout == "paged":
        # each row owns s // page distinct pages, out of order
        perm = jax.random.permutation(ks[1], rows)
        table = perm[:b * (s // page)].reshape(b, -1).astype(jnp.int32)
    return dict(
        cache=cache, table=table, kind=None if kind == "bf16" else kind,
        q=mk(ks[2], (b, h, d)), k_new=mk(ks[3], (b, h, d)),
        v_new=mk(ks[4], (b, h, d)),
        cols=mk(ks[5], (2, b, h, 3, d)),
        pos=jnp.asarray([5, 0, s - 4] if pos is None else pos, jnp.int32))


def _layer_of(cache, layer):
    """``(k, v)`` or ``(k, k_scale, v, v_scale)`` of one layer."""
    if isinstance(cache, dict):
        kv, sc = cache["kv"][layer], cache["scale"][layer]
        return kv[0], sc[0], kv[1], sc[1]
    return cache[layer, 0], cache[layer, 1]


def _per_layer_decode(c, planes):
    """The per-layer calls' ``(out, *planes)`` for one step."""
    q, k_new, v_new, pos, table, kind = (
        c[n] for n in ("q", "k_new", "v_new", "pos", "table", "kind"))
    if table is None:
        if kind:
            return decode_attention_quantized(q, k_new, v_new, *planes,
                                              pos, kind=kind)
        return decode_attention(q, k_new, v_new, *planes, pos)
    if kind:
        planes = paged_write_column_quant(k_new, v_new, *planes, table,
                                          pos, kind)
        return (paged_attention_quantized(q, *planes, table, pos,
                                          kind=kind), *planes)
    planes = paged_write_column(k_new, v_new, *planes, table, pos)
    return (paged_attention(q, *planes, table, pos), *planes)


def _per_layer_columns(c, planes):
    k_new, v_new = c["cols"]
    pos, table, kind = c["pos"], c["table"], c["kind"]
    if table is None:
        if kind:
            return cache_write_columns_quant(k_new, v_new, *planes, pos,
                                             kind)
        return cache_write_columns(k_new, v_new, *planes, pos)
    if kind:
        return paged_write_columns_quant(k_new, v_new, *planes, table,
                                         pos, kind)
    return paged_write_columns(k_new, v_new, *planes, table, pos)


def _bytes(x):
    """``x``'s bytes on the host (a device array can come back strided
    the way it lay on the device: the view needs it contiguous)."""
    return np.ascontiguousarray(x).view(np.uint8)


def _assert_only_layer_moved(got, before, layer, want_planes):
    """Layer ``layer`` of ``got`` holds ``want_planes``; every other
    layer's bytes are those of ``before``."""
    for g, w in zip(_layer_of(got, layer), want_planes):
        np.testing.assert_array_equal(np.asarray(g, np.float32),
                                      np.asarray(w, np.float32))
    for g, b in zip(jax.tree.leaves(got), jax.tree.leaves(before)):
        others = np.arange(_L) != layer
        np.testing.assert_array_equal(
            _bytes(g)[others], _bytes(b)[others])


@pytest.mark.parametrize("layer", range(_L))
@pytest.mark.parametrize("kind", ["bf16", "int8", "fp8"])
@pytest.mark.parametrize("layout", ["contiguous", "paged"])
def test_stacked_kernels_match_per_layer_calls(layout, kind, layer):
    """The write and the read kernel addressed by layer index into the
    stacked cache give, for every layer, exactly what the per-layer
    calls give on that layer's planes — the one-column step and the
    T-column write — and leave every other layer bit-identical. The
    layer is a traced scalar, as the model's scan hands it over."""
    c = _stacked_case(layout, kind)
    planes = _layer_of(c["cache"], layer)
    want_out, *want_planes = _per_layer_decode(c, planes)
    out, cache = jax.jit(
        lambda cache, layer: stacked_decode_attention(
            c["q"], c["k_new"], c["v_new"], cache, layer, c["pos"],
            table=c["table"], kind=c["kind"]))(c["cache"],
                                               jnp.int32(layer))
    np.testing.assert_array_equal(np.asarray(out, np.float32),
                                  np.asarray(want_out, np.float32))
    _assert_only_layer_moved(cache, c["cache"], layer, want_planes)
    if c["kind"]:
        assert cache["kv"].dtype == kv_storage_dtype(c["kind"])

    cache = jax.jit(
        lambda cache, layer: stacked_write_columns(
            *c["cols"], cache, layer, c["pos"], table=c["table"],
            kind=c["kind"]))(c["cache"], jnp.int32(layer))
    _assert_only_layer_moved(cache, c["cache"], layer,
                             _per_layer_columns(c, planes))


@pytest.mark.parametrize("kind", ["bf16", "int8", "fp8"])
@pytest.mark.parametrize("layout", ["contiguous", "paged"])
def test_write_columns_clamp_at_the_horizon(orientation, layout, kind):
    """The T-column write against the XLA one-hot write, plane by
    plane, the operand either way round: lanes inside the horizon land
    the XLA write's bytes, and a row whose last lane overruns the
    horizon (XLA drops it) clamps it onto column ``S - 1``, which then
    holds that lane — nothing else of the cache moves."""
    layer, t = 1, 3
    c = _stacked_case(layout, kind, pos=[5, 0, _S - 2])
    pos, table = c["pos"], c["table"]
    got = jax.jit(lambda cache, layer: stacked_write_columns(
        *c["cols"], cache, layer, pos, table=table, kind=c["kind"]))(
            c["cache"], jnp.int32(layer))
    news = list(c["cols"])                          # [b, h, T, d] each
    if c["kind"]:
        (kq, ksc), (vq, vsc) = (quantize_kv_rows(n, c["kind"])
                                for n in news)
        news = [kq, ksc, vq, vsc]
    xla_write = (
        (lambda plane, new: decode_attention_mod.cache_write_columns_xla(
            plane, new, pos)) if table is None else
        (lambda plane, new: decode_attention_mod.paged_write_columns_xla(
            plane, new, table, pos)))
    want = [xla_write(plane, new)
            for plane, new in zip(_layer_of(c["cache"], layer), news)]
    rows = lambda plane: np.asarray(
        plane if table is None
        else decode_attention_mod.paged_gather_xla(plane, table),
        np.float32)                                 # [b, h, S(, d)]
    for g, w, new in zip(_layer_of(got, layer), want, news):
        g, w, new = rows(g), rows(w), np.asarray(new, np.float32)
        np.testing.assert_array_equal(g[:2], w[:2])
        np.testing.assert_array_equal(g[2, :, :_S - 1], w[2, :, :_S - 1])
        np.testing.assert_array_equal(g[2, :, _S - 1], new[2, :, t - 1])
    # every other layer, and the pages in no row's table, keep their bytes
    for g, b in zip(jax.tree.leaves(got), jax.tree.leaves(c["cache"])):
        g, b = _bytes(g), _bytes(b)
        others = np.arange(_L) != layer
        np.testing.assert_array_equal(g[others], b[others])
        if table is not None:
            free = np.setdiff1d(np.arange(g.shape[2]), np.asarray(table))
            assert free.size
            np.testing.assert_array_equal(g[layer][:, free],
                                          b[layer][:, free])


# ---------------------------------------------------------------------------
# the read's grid: heads per step, chunks clamped to the fill, dead rows
# ---------------------------------------------------------------------------

_RB, _RH, _RD, _RBK = 5, 4, 32, 128

_LIVE = {
    "every_row": None,
    "dead_first": [False, False, True, True, True],
    "dead_last": [True, True, True, False, False],
    "dead_adjacent": [True, False, False, True, True],
    "all_but_one": [False, False, False, True, False],
}


def _read_case(layout, kind):
    """One step over a cache whose horizon is two read chunks
    (contiguous: ``_RBK`` positions each) or four (paged: pages of 8),
    rows sitting at the chunk edges: 0, ``bk - 1``, ``bk``, ``S - 1``
    and one in between."""
    bk, s = (8, 32) if layout == "paged" else (_RBK, 2 * _RBK)
    return dict(_stacked_case(layout, kind, b=_RB, h=_RH, s=s, d=_RD,
                              page=8, pos=[0, bk - 1, bk, s - 1, bk + 3]),
                bk=bk)


def _xla_step(c, layer):
    """The step's fp32 output through the module's ``*_xla`` write and
    gather helpers and a materialised softmax."""
    pos, table, kind = c["pos"], c["table"], c["kind"]
    news = [c["k_new"][:, :, None], c["v_new"][:, :, None]]
    planes = _layer_of(c["cache"], layer)
    if kind:
        quant = [quantize_kv_rows(n, kind) for n in news]
        news = [quant[0][0], quant[0][1], quant[1][0], quant[1][1]]
    if table is None:
        planes = [decode_attention_mod.cache_write_columns_xla(p, n, pos)
                  for p, n in zip(planes, news)]
    else:
        planes = [decode_attention_mod.paged_gather_xla(
            decode_attention_mod.paged_write_columns_xla(p, n, table, pos),
            table) for p, n in zip(planes, news)]
    planes = [np.asarray(p, np.float32) for p in planes]
    if kind:
        k, v = (planes[0] * planes[1][..., None],
                planes[2] * planes[3][..., None])
    else:
        k, v = planes
    q = np.asarray(c["q"], np.float32)
    s = np.einsum("bhd,bhsd->bhs", q, k) / np.sqrt(_RD)
    valid = np.arange(k.shape[2])[None, None] <= np.asarray(pos)[:, None,
                                                                 None]
    s = np.where(valid, s, -1e30)
    p = np.exp(s - s.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    return np.einsum("bhs,bhsd->bhd", p, v)


def _cell(c, row, layer):
    """Index of row ``row``'s cell at its ``pos`` in every plane of
    layer ``layer``: ``(layer, both planes, leading row or page, every
    head, position)``."""
    p = int(c["pos"][row])
    if c["table"] is None:
        return layer, slice(None), row, slice(None), p
    page = jax.tree.leaves(c["cache"])[0].shape[4]
    return (layer, slice(None), int(c["table"][row, p // page]),
            slice(None), p % page)


def _keep_dead_cells(c, cache, alive, layer):
    """The planes of ``cache`` with each dead row's cell as it was in
    ``c["cache"]``, on the host: what a step under ``alive`` leaves
    where a step that writes every row left ``cache``."""
    planes = [np.array(p) for p in jax.tree.leaves(cache)]
    for p, old in zip(planes, jax.tree.leaves(c["cache"])):
        old = np.asarray(old)
        for i in np.flatnonzero(~alive):
            p[_cell(c, i, layer)] = old[_cell(c, i, layer)]
    return planes


def _assert_read_matches(c, *lives, layer=1):
    """Under each liveness of ``lives`` (None: ``live`` not given), live
    rows match the XLA reference at the file's tolerances and dead rows
    are exact zeros; under a mask, live rows are bit for bit what a
    step with every row live gives, and so is the cache, except that a
    dead row's cell keeps the bytes it had: its column is not
    written."""
    step = jax.jit(lambda cache, layer, live=None: stacked_decode_attention(
        c["q"], c["k_new"], c["v_new"], cache, layer, c["pos"],
        table=c["table"], kind=c["kind"], block_k=_RBK,
        live=None if live is None else
        decode_attention_mod.live_rows(live)))
    want = _xla_step(c, layer)
    tol = _QTOL[c["kind"]] if c["kind"] else _TOL[jnp.bfloat16]

    def run(live):
        out, cache = step(c["cache"], jnp.int32(layer), live)
        out = np.asarray(out, np.float32)
        assert np.isfinite(out).all()
        alive = np.ones(_RB, bool) if live is None else np.asarray(live)
        np.testing.assert_allclose(out[alive], want[alive], **tol)
        np.testing.assert_array_equal(out[~alive], 0.0)
        return out, cache, alive

    full = None
    for live in lives:
        if live is None:
            run(None)
            continue
        # one program serves every mask: the all-live one is a mask too
        full = full or run(jnp.ones(_RB, bool))
        out, cache, alive = run(jnp.asarray(live))
        np.testing.assert_array_equal(out[alive], full[0][alive])
        for g, w in zip(jax.tree.leaves(cache),
                        _keep_dead_cells(c, full[1], alive, layer)):
            np.testing.assert_array_equal(_bytes(g), _bytes(w))


@pytest.mark.parametrize("live", list(_LIVE))
@pytest.mark.parametrize("kind", ["bf16", "int8", "fp8"])
@pytest.mark.parametrize("layout", ["contiguous", "paged"])
def test_read_at_chunk_edges_and_with_dead_rows(orientation, layout, kind,
                                                live):
    """All six variants, the operand either way round, with rows at
    every chunk edge, and the rows' liveness: dead rows first, last,
    adjacent, all but one."""
    _assert_read_matches(_read_case(layout, kind), _LIVE[live])


@pytest.mark.parametrize("kind", ["bf16", "int8", "fp8"])
@pytest.mark.parametrize("layout", ["contiguous", "paged"])
def test_read_with_heads_split_over_grid_steps(monkeypatch, orientation,
                                               layout, kind):
    """A VMEM budget that holds two of the four heads, counted in the
    bytes of the orientation in use: the grid gains a head-group
    dimension and a dead row pins its last group."""
    c = _read_case(layout, kind)
    kv = jax.tree.leaves(c["cache"])[0]
    fits = lambda: decode_attention_mod._heads_per_step(
        _RH, _RD, c["bk"], kv.dtype, bool(c["kind"]), orientation)
    for budget in (1 << n for n in range(10, 24)):
        monkeypatch.setattr(decode_attention_mod, "_KV_VMEM_BUDGET", budget)
        if fits() >= 2:
            break
    assert fits() == 2
    _assert_read_matches(c, _LIVE["dead_adjacent"], _LIVE["dead_first"])


#: the write's dead rows: leading, trailing, between live ones, all
_DEAD = {
    "dead_lead": [False, False, True, True, True],
    "dead_trail": [True, True, True, False, False],
    "interleaved": [False, True, False, True, False],
    "all_dead": [False] * _RB,
}


@pytest.mark.parametrize("live", list(_DEAD))
@pytest.mark.parametrize("kind", ["bf16", "int8", "fp8"])
@pytest.mark.parametrize("layout", ["contiguous", "paged"])
def test_write_lands_only_the_live_rows(orientation, layout, kind, live):
    """The step's write under a live-row list, against the module's XLA
    write of every row: each live row's cell holds what the XLA write
    lands there, each dead row's window keeps its old bytes, and no
    other layer moves; with no live row the cache comes back byte for
    byte as it went in."""
    layer = 1
    c = _read_case(layout, kind)
    alive = np.asarray(_DEAD[live])
    _, got = jax.jit(lambda cache, layer, live: stacked_decode_attention(
        c["q"], c["k_new"], c["v_new"], cache, layer, c["pos"],
        table=c["table"], kind=c["kind"], block_k=_RBK,
        live=decode_attention_mod.live_rows(live)))(
            c["cache"], jnp.int32(layer), jnp.asarray(alive))
    pos, table = c["pos"], c["table"]
    news = [c["k_new"][:, :, None], c["v_new"][:, :, None]]
    if c["kind"]:
        (kq, ksc), (vq, vsc) = (quantize_kv_rows(n, c["kind"])
                                for n in news)
        news = [kq, ksc, vq, vsc]
    write = (
        (lambda plane, new: decode_attention_mod.cache_write_columns_xla(
            plane, new, pos)) if table is None else
        (lambda plane, new: decode_attention_mod.paged_write_columns_xla(
            plane, new, table, pos)))
    written = [np.asarray(write(plane, new)) for plane, new in
               zip(_layer_of(c["cache"], layer), news)]
    want = [np.array(p) for p in jax.tree.leaves(c["cache"])]
    # (k, v) or (k, k_scale, v, v_scale) back into [kv] or [kv, scale]
    for k, plane in enumerate(want):
        plane[layer] = np.stack(written[k::len(want)])
    want = _keep_dead_cells(c, want, alive, layer)
    for g, w in zip(jax.tree.leaves(got), want):
        np.testing.assert_array_equal(_bytes(g), _bytes(w))
    if not alive.any():
        for g, b in zip(jax.tree.leaves(got), jax.tree.leaves(c["cache"])):
            np.testing.assert_array_equal(_bytes(g), _bytes(b))


@pytest.mark.parametrize("groups", [1, 2])
@pytest.mark.parametrize("live", list(_LIVE) + ["none"])
def test_grid_walk_fetches_only_the_chunks_live_rows_need(live, groups):
    """The row list and the index maps alone, walked over the grid in
    Python: the grid's rows are the live rows in order (one step where
    none is), the blocks change ``groups * sum(pos // bk + 1 over live
    rows)`` times (a step that names the block already resident copies
    nothing), and a live row's blocks are its own chunks in order."""
    bk, chunks = 16, 4
    pos = np.asarray([0, 15, 16, 63, 35], np.int32)
    alive = (np.zeros(5, bool) if live == "none" else
             np.ones(5, bool) if _LIVE[live] is None else
             np.asarray(_LIVE[live]))
    rows = np.asarray(
        decode_attention_mod._every_row(5)
        if live != "none" and _LIVE[live] is None
        else decode_attention_mod.live_rows(jnp.asarray(alive)))
    n = int(alive.sum())
    assert rows[-1] == n
    np.testing.assert_array_equal(rows[:n], np.flatnonzero(alive))
    np.testing.assert_array_equal(rows[n:-1], np.flatnonzero(~alive))
    walk = []
    for i in range(max(n, 1)):
        for g in range(groups):
            for j in range(chunks):
                block = tuple(int(x) for x in
                              decode_attention_mod._block_index(
                                  i, g, j, pos, rows, bk))
                if i < n:
                    assert block == (rows[i], g,
                                     min(j, pos[rows[i]] // bk))
                if not walk or walk[-1] != block:
                    walk.append(block)
    needed = groups * int(sum(p // bk + 1 for p in pos[alive]))
    # with no live row, the grid's one step names chunk 0 of each group
    assert len(walk) == (needed if n else groups)
    assert len(set(walk)) == len(walk)      # nothing is fetched twice


def test_stacked_decode_attention_validation():
    z3 = jnp.zeros((_B, _H, _D))
    cache = jnp.zeros((_L, 2, _B, _H, _S, _D))
    pos = jnp.zeros((_B,), jnp.int32)
    with pytest.raises(ValueError, match="stacked cache"):
        stacked_decode_attention(z3, z3, z3, cache[0], 0, pos)
    with pytest.raises(ValueError, match="inconsistent"):
        stacked_decode_attention(z3, z3, z3, cache[:, :, :2], 0, pos)
    with pytest.raises(ValueError, match="pos must be"):
        stacked_decode_attention(z3, z3, z3, cache, 0, pos[:2])
    with pytest.raises(ValueError, match="live must be"):
        stacked_decode_attention(z3, z3, z3, cache, 0, pos,
                                 live=jnp.ones((2,), bool))
    with pytest.raises(ValueError, match="unknown quantized-KV kind"):
        stacked_decode_attention(z3, z3, z3, cache, 0, pos, kind="int4")


#: what PR 24's tree (the layer scan that sliced each layer's cache out
#: and stacked it back) emitted for `_stream` below, 3 rows x 16 steps;
#: the same under either impl and under the int8 cache
_PARENT_STREAM = {
    "greedy": [[64] * 16, [34] * 16, [14] * 14 + [58, 58]],
    "sampled": [
        [74, 19, 54, 18, 76, 34, 43, 83, 28, 63, 54, 74, 91, 56, 76, 94],
        [82, 59, 65, 73, 27, 55, 94, 73, 83, 33, 11, 54, 44, 11, 95, 47],
        [8, 9, 85, 72, 34, 21, 44, 25, 62, 49, 11, 86, 31, 66, 85, 33]],
}


@pytest.mark.parametrize("draw", ["greedy", "sampled"])
@pytest.mark.parametrize("impl", ["kernel", "xla"])
@pytest.mark.parametrize("kv", ["auto", "int8"])
def test_decode_stream_matches_parent(devices8, kv, impl, draw):
    """16 steps of ``generate`` (one ``decode_steps`` scan with the
    cache in the layer scan's carry) emit, token for token, what the
    slice-and-stack scan of PR 24 emitted — under the kernels and
    under the XLA fallback, greedy and sampled. Addressing the cache in
    place changes no logit."""
    cfg = dataclasses.replace(
        standalone_gpt_config(vocab_size=96, seq_len=32,
                              compute_dtype=jnp.float32),
        decode_attn_impl=impl, kv_cache_dtype=kv)
    params = gpt.init(cfg, jax.random.PRNGKey(0))
    prompt = jax.random.randint(jax.random.PRNGKey(1), (3, 5), 0, 96)
    sample = (dict(temperature=1.0, key=jax.random.PRNGKey(7))
              if draw == "sampled" else {})
    out = jax.jit(jax.shard_map(
        lambda p, t: gpt.generate(cfg, p, t, 16, **sample),
        mesh=mx.build_mesh(tp=1, devices=devices8[:1]),
        in_specs=(gpt.param_specs(cfg), P(None, None)),
        out_specs=P(None, None), check_vma=False))(params, prompt)
    assert np.asarray(out).tolist() == _PARENT_STREAM[draw]
