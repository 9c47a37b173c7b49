"""Fused train step: amp + fused optimizer + DP/TP/SP grad sync in one jit.

This is the whole of SURVEY.md §3.2 — apex's per-iteration call stack
(``scale_loss`` → backward → DDP allreduce → ``FusedAdam.step()``) — as a
single compiled XLA program over the mesh:

- loss scaling + fused unscale/overflow-check: :mod:`apex_tpu.amp`
  (apex/amp/scaler.py (U)),
- gradient sync: ``lax.pmean`` on the dp axis replaces apex DDP's bucketed
  NCCL allreduce (apex/parallel/distributed.py (U)); XLA's latency-hiding
  scheduler provides the backward/comm overlap apex managed by hand,
- the sequence-parallel tp-psum for seq-partial replicated grads mirrors
  apex's explicit allreduce of ``sequence_parallel_enabled`` params (U),
- optimizer: one multi-tensor Pallas sweep (apex/optimizers (U)),
- overflow skip: ``lax.cond``-free select via ``apply_if_finite`` — the
  functional form of apex skipping ``optimizer.step()`` on inf/nan.
"""

from __future__ import annotations

from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from apex_tpu.amp import ScalerConfig, ScalerState, apply_if_finite
from apex_tpu.amp import update as scaler_update
from apex_tpu.amp import value_and_scaled_grad
from apex_tpu.mesh.topology import (
    AXIS_DP,
    AXIS_PP,
    AXIS_TP,
    mesh_shape_of,
)
from apex_tpu.models import gpt
from apex_tpu.optimizers import DistributedFusedOptimizer, FusedOptimizer


class TrainState(NamedTuple):
    step: jnp.ndarray
    params: Any
    opt_state: Any
    scaler: ScalerState
    #: non-trainable model state threaded through the loss (BatchNorm
    #: running stats — torch's "buffers"); () when the model has none
    extra: Any = ()


def _local_shape(shape, spec, axis_sizes):
    """Shard a global shape per PartitionSpec."""
    out = list(shape)
    for i, names in enumerate(spec):
        if names is None:
            continue
        for n in names if isinstance(names, (tuple, list)) else (names,):
            out[i] //= axis_sizes[n]
    return tuple(out)


def _opt_state_specs(optimizer: FusedOptimizer, params, pspecs, mesh: Mesh):
    """Infer shard_map specs for the optimizer state.

    The fused optimizers pack *local* param shards into flat buffers, so
    inside shard_map each (pp, tp) rank owns a private buffer: scalars
    (step counts) are replicated, buffers shard on the combined (pp, tp)
    axes (equal-sized per rank — shard_map concatenates them into one
    global array; dp ranks hold identical copies).
    """
    state_pspecs = getattr(optimizer, "state_pspecs", None)
    if state_pspecs is not None:
        # tree-layout optimizers: state mirrors the param tree, so it
        # shards exactly like the params (DistributedFusedOptimizer is a
        # different NamedTuple without the field — getattr keeps the ZeRO
        # path on the flat-buffer inference below)
        return state_pspecs(pspecs)
    sizes = mesh_shape_of(mesh)
    local = jax.tree.map(
        lambda x, s: jax.ShapeDtypeStruct(
            _local_shape(x.shape, s, sizes), x.dtype),
        params, pspecs,
    )
    # ZeRO-style optimizers shard their state over dp too; their init
    # reads the dp size from the axis, which only exists inside shard_map,
    # so the abstract evaluation passes it statically instead
    zero_style = isinstance(optimizer, DistributedFusedOptimizer)
    if zero_style:
        dp = sizes.get(optimizer.axis, 1)
        shapes = jax.eval_shape(lambda p: optimizer.init(p, dp=dp), local)
    else:
        shapes = jax.eval_shape(optimizer.init, local)
    state_axes = (AXIS_DP, AXIS_PP, AXIS_TP) if zero_style else (
        AXIS_PP, AXIS_TP)
    buf_axes = tuple(a for a in state_axes if a in mesh.axis_names)
    buf_spec = P(buf_axes) if buf_axes else P()
    return jax.tree.map(
        lambda x: P() if x.ndim == 0 else buf_spec, shapes)


def _mentions(spec, axis):
    """True when ``axis`` appears in the PartitionSpec (incl. tuples)."""
    return any(
        a == axis or (isinstance(a, (tuple, list)) and axis in a)
        for a in spec if a is not None)


def _validate_fsdp_optimizer(optimizer):
    """The optimizer constraints ZeRO-3 param sharding imposes."""
    if isinstance(optimizer, DistributedFusedOptimizer):
        raise ValueError(
            "fsdp already shards params/grads/state over dp; the "
            "ZeRO-1/2 optimizers would shard them a second time — "
            "use a tree-layout fused optimizer")
    if getattr(optimizer, "state_pspecs", None) is None:
        raise ValueError(
            "fsdp needs a tree-layout optimizer (state mirrors the "
            "dp-sharded params); pass layout='tree'")
    if getattr(optimizer, "per_leaf_norms", False):
        raise ValueError(
            "fsdp shards each kernel over dp, but this optimizer's "
            "update depends on whole-leaf norms (LAMB trust ratios / "
            "NovoGrad layer moments) — computed on a shard they "
            "diverge per rank; use Adam/SGD/Adagrad, or ZeRO-1/2 "
            "distributed_fused_lamb without fsdp")


def _clip_leaf_axes(pspecs, norm_axes):
    """Per-leaf model-parallel axis sets for the global-norm psum
    (leaf order = pspecs treedef order)."""
    return [
        tuple(a for a in norm_axes if _mentions(sp, a))
        for sp in jax.tree.leaves(
            pspecs, is_leaf=lambda x: isinstance(x, P))]


@jax.named_scope("apex.clip")
def _clip_by_global_norm(grads, leaf_axes, clip):
    """(clipped grads, pre-clip global L2 norm): each leaf's shard
    sum-of-squares is psum'd over its sharded axes so every rank clips
    by the same global norm; one psum per distinct axis set."""
    sq = {}
    for g, axes in zip(jax.tree.leaves(grads), leaf_axes):
        v = jnp.sum(jnp.square(g.astype(jnp.float32)))
        sq[axes] = sq.get(axes, jnp.float32(0.0)) + v
    total = jnp.float32(0.0)
    for axes, v in sq.items():
        total = total + (lax.psum(v, axes) if axes else v)
    norm = jnp.sqrt(total)
    coeff = jnp.minimum(1.0, jnp.float32(clip) / (norm + 1e-6))
    return jax.tree.map(lambda g: g * coeff.astype(g.dtype), grads), norm


def _dp_grad_sync(grads, optimizer, axes_present, *, fsdp, fsdp_mask,
                  dp_size):
    """DP gradient averaging (apex DDP allreduce + 1/world_size (U));
    ZeRO optimizers own the dp reduction, fsdp leaves already hold the
    dp-SUM (the all-gather VJP is a psum_scatter) and scale to the
    mean."""
    if AXIS_DP not in axes_present or isinstance(
            optimizer, DistributedFusedOptimizer):
        return grads
    if fsdp:
        inv_dp = 1.0 / dp_size
        return jax.tree.map(
            lambda g, m: g * jnp.asarray(inv_dp, g.dtype) if m
            else lax.pmean(g, AXIS_DP),
            grads, fsdp_mask)
    return lax.pmean(grads, AXIS_DP)


def _make_init_fn(init_params, pspecs, opt_specs, optimizer, scaler_cfg,
                  mesh, init_extra=None, extra_pspecs=None):
    """``init_extra`` is a separate ``key -> extra`` callable, or the
    string ``"with_params"`` meaning ``init_params(key)`` returns the
    ``(params, extra)`` pair in one pass (models whose init builds both,
    e.g. ResNet's params + BN state — avoids running the param RNG
    twice)."""
    combined = init_extra == "with_params"

    def place(sp_tree):
        return jax.tree.map(lambda sp: NamedSharding(mesh, sp), sp_tree)

    def init_fn(key) -> TrainState:
        if combined:
            params, extra = jax.jit(
                init_params,
                out_shardings=(place(pspecs), place(extra_pspecs)),
            )(key)
        else:
            params = jax.jit(
                init_params, out_shardings=place(pspecs))(key)
            extra = ()
            if init_extra is not None:
                extra = jax.jit(
                    init_extra, out_shardings=place(extra_pspecs))(key)
        opt_state = jax.jit(
            jax.shard_map(optimizer.init, mesh=mesh, in_specs=(pspecs,),
                          out_specs=opt_specs, check_vma=False)
        )(params)
        return TrainState(
            step=jnp.zeros((), jnp.int32), params=params,
            opt_state=opt_state, scaler=scaler_cfg.init(), extra=extra)

    return init_fn


def make_train_step(
    cfg: gpt.GPTConfig,
    mesh: Mesh,
    optimizer: FusedOptimizer,
    scaler_cfg: Optional[ScalerConfig] = None,
    *,
    n_micro: int = 1,
    n_chunks: int = 1,
    clip_grad_norm: Optional[float] = None,
):
    """Build ``(init_fn, step_fn)`` for GPT training over ``mesh``.

    ``init_fn(key) -> TrainState`` places params/optimizer state with the
    model's shardings; ``step_fn(state, tokens, targets) -> (state,
    metrics)`` is jitted over the mesh with donated state. ``tokens``/
    ``targets`` are ``[batch, seq]`` with batch sharded on dp.

    A mesh with a nontrivial ``pp`` axis switches to the pipelined loss:
    ``n_micro`` microbatches stream through the stage ring, ``n_chunks``
    virtual stages per rank (apex interleaved 1F1B).

    ``clip_grad_norm`` clips to a global L2 norm between the grad sync
    and the optimizer step — the role ``clip_grad_norm_(amp.
    master_params(opt))`` plays in the reference loop, with Megatron's
    model-parallel norm semantics: leaves sharded over tp/pp/ep
    contribute their shard's sum-of-squares psum'd over those axes,
    replicated leaves count once (``param_is_not_tensor_parallel_
    duplicate`` (U)). Adds a ``grad_norm`` metric (the pre-clip norm).
    """
    scaler_cfg = scaler_cfg or ScalerConfig(enabled=False)
    axes_present = set(mesh.axis_names)
    cp_active = cfg.context_parallel and (
        mesh_shape_of(mesh).get(cfg.cp_axis, 1) > 1)
    if cfg.context_parallel and cfg.cp_axis not in axes_present:
        raise ValueError(
            f"context_parallel needs mesh axis {cfg.cp_axis!r}")
    pp = mesh_shape_of(mesh).get(AXIS_PP, 1)
    pipelined = pp > 1
    if n_chunks > 1 and not pipelined:
        raise ValueError("n_chunks > 1 requires a mesh with pp > 1")
    ep_axis = getattr(cfg, "ep_axis", "ep")
    # ep > 1 shards the batch too (tokens over ("dp", ep)); for a dense
    # model that is extra data parallelism, for MoE the expert leaves
    # additionally shard over ep (composes with pp: the ep all_to_all
    # runs inside each pipeline tick, orthogonal to the stage ring)
    ep_size = mesh_shape_of(mesh).get(ep_axis, 1)
    if cfg.num_experts:
        # fail at build time, not mid-trace (the model raises too, but
        # deep inside the first step)
        gpt._moe_cfg(cfg)  # validates top_k vs num_experts
        if cfg.sequence_parallel:
            raise ValueError(
                "num_experts > 0 does not compose with sequence_parallel; "
                "shard the batch over ep instead")
    dp_size = mesh_shape_of(mesh).get(AXIS_DP, 1)
    if cfg.fsdp:
        # ZeRO-3: params dp-sharded between steps; grads arrive as the
        # all-gather VJP's psum_scatter (already dp-summed)
        _validate_fsdp_optimizer(optimizer)
        if not cfg.remat:
            raise ValueError(
                "fsdp requires remat=True: without recompute the "
                "all-gathered full kernels are saved as backward "
                "residuals, costing MORE memory than fsdp=False")
        if dp_size > 1 and cfg.hidden_size % dp_size:
            raise ValueError(
                f"fsdp shards the kernels' h-dim: hidden_size "
                f"{cfg.hidden_size} must divide by dp={dp_size}")
    if clip_grad_norm is not None and isinstance(
            optimizer, DistributedFusedOptimizer):
        raise ValueError(
            "clip_grad_norm composes with the tree/flat fused optimizers; "
            "the ZeRO optimizers own their dp reduction (clip there would "
            "see pre-reduce partial grads)")
    pspecs = gpt.param_specs(cfg, pipeline=pipelined)
    sp_mask = gpt.seq_partial_grad_mask(cfg)

    # per-leaf model-parallel axes for the clip norm (AXIS_DP appears
    # in pspecs only for fsdp-sharded leaves — their shard needs the dp
    # psum like any sharded leaf)
    _norm_axes = tuple(a for a in (AXIS_TP, AXIS_PP, ep_axis, AXIS_DP)
                       if a in axes_present)
    clip_leaf_axes = _clip_leaf_axes(pspecs, _norm_axes)

    # params NOT sharded over pp see only their stage's loss contribution —
    # psum over pp reassembles them (embedding / position / final LN);
    # derived from the specs so placement changes can't desync the mask
    pp_mask = jax.tree.map(
        lambda s: not _mentions(s, AXIS_PP), pspecs,
        is_leaf=lambda x: isinstance(x, P))
    # ep-sharded leaves (MoE experts): their grads already sum every ep
    # rank's token contributions through the transposed all_to_all, so
    # they get / ep_size instead of a pmean (mean-over-global-batch
    # semantics); everything else is replicated over ep and pmeans
    ep_mask = jax.tree.map(
        lambda s: _mentions(s, ep_axis), pspecs,
        is_leaf=lambda x: isinstance(x, P))
    # fsdp-sharded leaves: pspec mentions dp (only possible via fsdp)
    fsdp_mask = jax.tree.map(
        lambda s: _mentions(s, AXIS_DP), pspecs,
        is_leaf=lambda x: isinstance(x, P))
    if ep_size > 1 and any(jax.tree.leaves(ep_mask)) and getattr(
            optimizer, "state_pspecs", None) is None:
        raise ValueError(
            "MoE over ep > 1 needs a tree-layout optimizer (its state "
            "mirrors the ep-sharded params); pass layout='tree'")
    scaler_specs = jax.tree.map(lambda _: P(), ScalerState(*[0] * 3))

    def _global_init(key):
        params = gpt.init(cfg, key)
        if pipelined:
            params = gpt.interleave_layers(
                params, cfg.num_layers, pp, n_chunks)
        return params

    param_shapes = jax.eval_shape(
        lambda: _global_init(jax.random.PRNGKey(0)))
    opt_specs = _opt_state_specs(optimizer, param_shapes, pspecs, mesh)

    init_fn = _make_init_fn(_global_init, pspecs, opt_specs, optimizer,
                            scaler_cfg, mesh)

    def _local_loss(p, tokens, targets):
        if pipelined:
            return gpt.pipeline_loss(
                cfg, p, tokens, targets, n_micro=n_micro, n_chunks=n_chunks)
        if n_micro > 1:
            # gradient accumulation without a pipeline: scan sequential
            # microbatches, recomputing each forward in backward (apex's
            # forward_backward_no_pipelining capability (U))
            b = tokens.shape[0]
            if b % n_micro:
                raise ValueError(
                    f"local batch {b} not divisible by n_micro={n_micro}")
            mb_tok = tokens.reshape(n_micro, b // n_micro, -1)
            mb_tgt = targets.reshape(n_micro, b // n_micro, -1)

            @jax.checkpoint
            def mb_loss(p, t, y):
                return gpt.loss(cfg, p, t, y)

            def body(acc, mb):
                t, y = mb
                return acc + mb_loss(p, t, y), None

            tot, _ = lax.scan(body, jnp.float32(0.0), (mb_tok, mb_tgt))
            return tot / n_micro
        return gpt.loss(cfg, p, tokens, targets)

    def _local_step(state: TrainState, tokens, targets):
        params = state.params
        vag = value_and_scaled_grad(
            lambda p: _local_loss(p, tokens, targets), scaler_cfg)
        value, grads, finite = vag(params, scaler_state=state.scaler)

        with jax.named_scope("apex.grad_sync"):
            grads = _dp_grad_sync(grads, optimizer, axes_present,
                                  fsdp=cfg.fsdp, fsdp_mask=fsdp_mask,
                                  dp_size=dp_size)
            if ep_size > 1:
                inv = 1.0 / ep_size
                grads = jax.tree.map(
                    lambda g, m: g * inv if m else lax.pmean(g, ep_axis),
                    grads, ep_mask)
            if cp_active:
                # params are replicated over cp but each rank saw only
                # its sequence chunk — mean of equal-sized chunk losses
                grads = lax.pmean(grads, cfg.cp_axis)
            if cfg.sequence_parallel:
                grads = jax.tree.map(
                    lambda g, m: lax.psum(g, AXIS_TP) if m else g,
                    grads, sp_mask)
            if pipelined:
                grads = jax.tree.map(
                    lambda g, m: lax.psum(g, AXIS_PP) if m else g,
                    grads, pp_mask)
        sync_names = [AXIS_DP, AXIS_TP, AXIS_PP]
        if cp_active:
            sync_names.append(cfg.cp_axis)
        if ep_size > 1:
            sync_names.append(ep_axis)
        sync_axes = tuple(a for a in sync_names if a in axes_present)
        # every rank must agree on finiteness (skip decision when the
        # scaler is on; replicated metric either way)
        finite = lax.pmin(finite.astype(jnp.int32), sync_axes) > 0
        grad_norm = None
        if clip_grad_norm is not None:
            # global L2 norm after the sync (grads here ARE the applied
            # update direction)
            grads, grad_norm = _clip_by_global_norm(
                grads, clip_leaf_axes, clip_grad_norm)
        with jax.named_scope("apex.optimizer"):
            new_params, new_opt = optimizer.step(
                grads, state.opt_state, params)
            if scaler_cfg.enabled:
                # a single rank overflowing skips the step everywhere
                new_params = apply_if_finite(new_params, params, finite)
                new_opt = apply_if_finite(new_opt, state.opt_state, finite)
        # identity scaler: like apex without a scaler the step is never
        # skipped — grads_finite stays a truthful observability metric
        new_scaler = scaler_update(scaler_cfg, state.scaler, finite)

        loss_out = value
        if AXIS_DP in axes_present:
            loss_out = lax.pmean(loss_out, AXIS_DP)
        if ep_size > 1:
            loss_out = lax.pmean(loss_out, ep_axis)
        if cp_active:
            loss_out = lax.pmean(loss_out, cfg.cp_axis)
        metrics = {
            "loss": loss_out,
            "grads_finite": finite.astype(jnp.int32),
            "loss_scale": new_scaler.loss_scale,
        }
        if grad_norm is not None:
            metrics["grad_norm"] = grad_norm
        new_state = TrainState(
            state.step + jnp.int32(1), new_params, new_opt, new_scaler)
        return new_state, metrics

    state_specs = TrainState(
        step=P(), params=pspecs, opt_state=opt_specs, scaler=scaler_specs)
    batch_axes = tuple(
        a for a, on in ((AXIS_DP, AXIS_DP in axes_present),
                        (ep_axis, ep_size > 1)) if on)
    data_spec = P(batch_axes, None) if batch_axes else P(None, None)
    metric_specs = {"loss": P(), "grads_finite": P(), "loss_scale": P()}
    if clip_grad_norm is not None:
        metric_specs["grad_norm"] = P()
    step_fn = jax.jit(
        jax.shard_map(
            _local_step, mesh=mesh,
            in_specs=(state_specs, data_spec, data_spec),
            out_specs=(state_specs, metric_specs),
            check_vma=False,
        ),
        donate_argnums=(0,),
    )

    return init_fn, step_fn


def make_loss_train_step(
    loss_fn,
    mesh: Mesh,
    optimizer: FusedOptimizer,
    *,
    init_params,
    pspecs,
    scaler_cfg: Optional[ScalerConfig] = None,
    clip_grad_norm: Optional[float] = None,
    sp_psum_mask=None,
    model_axis: str = AXIS_TP,
    fsdp: bool = False,
    n_batch_args: int = 2,
    init_extra=None,
    extra_pspecs=None,
    extra_sync_dp: bool = True,
):
    """Generic (non-pipelined) fused train step over an arbitrary local
    loss — the machinery of :func:`make_train_step` for models that are
    not the flagship GPT (BERT uses it via
    :func:`apex_tpu.models.bert.make_mlm_train_step`).

    - ``loss_fn(params, *batch) -> scalar`` with local-shard semantics
      (called inside shard_map); ``batch`` is ``n_batch_args`` arrays
      whose leading dim shards on dp.
    - ``init_params(key) -> global param pytree``; ``pspecs`` mirrors it.
    - ``sp_psum_mask``: sequence-parallel psum mask (over
      ``model_axis``) for replicated params consumed on seq-sharded
      activations (None = SP off).
    - ``model_axis``: the tensor-parallel mesh axis name — the SP psum,
      the finite-skip sync, and the clip-norm psums all honour it.
    - ``fsdp``: the model gathers dp-sharded leaves itself (pspecs
      mention dp on them); their grads arrive dp-summed via the gather's
      psum_scatter VJP and are scaled to the mean here.
    - ``init_extra(key) -> pytree`` (or the string ``"with_params"``,
      meaning ``init_params(key)`` returns ``(params, extra)`` in one
      pass) enables non-trainable model state
      (BatchNorm running stats — torch "buffers"): the loss contract
      becomes ``loss_fn(params, extra, *batch) -> (loss, new_extra)``,
      the state rides ``TrainState.extra``, reverts with the params on
      an overflow-skipped step, and (with ``extra_sync_dp``, the torch
      DDP broadcast-buffers role) is dp-pmeaned each step — pass
      ``extra_sync_dp=False`` when the loss already syncs it (SyncBN).

    Covers dp / tp / SP / fsdp + amp + clip. Pipeline/context/expert
    parallelism remain :func:`make_train_step` (they are model-shaped).
    """
    scaler_cfg = scaler_cfg or ScalerConfig(enabled=False)
    axes_present = set(mesh.axis_names)
    dp_size = mesh_shape_of(mesh).get(AXIS_DP, 1)
    if fsdp:
        _validate_fsdp_optimizer(optimizer)
    if clip_grad_norm is not None and isinstance(
            optimizer, DistributedFusedOptimizer):
        raise ValueError(
            "clip_grad_norm composes with the tree/flat fused optimizers")

    _norm_axes = tuple(a for a in (model_axis, AXIS_DP)
                       if a in axes_present)
    clip_leaf_axes = _clip_leaf_axes(pspecs, _norm_axes)
    fsdp_mask = jax.tree.map(
        lambda s: _mentions(s, AXIS_DP), pspecs,
        is_leaf=lambda x: isinstance(x, P))
    scaler_specs = jax.tree.map(lambda _: P(), ScalerState(*[0] * 3))

    has_extra = init_extra is not None
    combined_init = init_extra == "with_params"
    shapes = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0)))
    if combined_init:
        param_shapes, extra_shapes = shapes
    else:
        param_shapes = shapes
        extra_shapes = (jax.eval_shape(
            lambda: init_extra(jax.random.PRNGKey(0)))
            if has_extra else None)
    opt_specs = _opt_state_specs(optimizer, param_shapes, pspecs, mesh)
    if has_extra and extra_pspecs is None:
        extra_pspecs = jax.tree.map(lambda _: P(), extra_shapes)

    init_fn = _make_init_fn(init_params, pspecs, opt_specs, optimizer,
                            scaler_cfg, mesh, init_extra, extra_pspecs)

    def _local_step(state: TrainState, *batch):
        params = state.params
        if has_extra:
            vag = value_and_scaled_grad(
                lambda p: loss_fn(p, state.extra, *batch), scaler_cfg,
                has_aux=True)
            (value, new_extra), grads, finite = vag(
                params, scaler_state=state.scaler)
            if extra_sync_dp and AXIS_DP in axes_present:
                new_extra = lax.pmean(new_extra, AXIS_DP)
        else:
            new_extra = state.extra
            vag = value_and_scaled_grad(
                lambda p: loss_fn(p, *batch), scaler_cfg)
            value, grads, finite = vag(params, scaler_state=state.scaler)

        with jax.named_scope("apex.grad_sync"):
            grads = _dp_grad_sync(grads, optimizer, axes_present,
                                  fsdp=fsdp, fsdp_mask=fsdp_mask,
                                  dp_size=dp_size)
            if sp_psum_mask is not None:
                grads = jax.tree.map(
                    lambda g, m: lax.psum(g, model_axis) if m else g,
                    grads, sp_psum_mask)
        sync_axes = tuple(
            a for a in (AXIS_DP, model_axis) if a in axes_present)
        finite = lax.pmin(finite.astype(jnp.int32), sync_axes) > 0
        grad_norm = None
        if clip_grad_norm is not None:
            grads, grad_norm = _clip_by_global_norm(
                grads, clip_leaf_axes, clip_grad_norm)
        with jax.named_scope("apex.optimizer"):
            new_params, new_opt = optimizer.step(
                grads, state.opt_state, params)
            if scaler_cfg.enabled:
                new_params = apply_if_finite(new_params, params, finite)
                new_opt = apply_if_finite(new_opt, state.opt_state, finite)
                if has_extra:
                    new_extra = apply_if_finite(new_extra, state.extra,
                                                finite)
        new_scaler = scaler_update(scaler_cfg, state.scaler, finite)
        loss_out = value
        if AXIS_DP in axes_present:
            loss_out = lax.pmean(loss_out, AXIS_DP)
        metrics = {
            "loss": loss_out,
            "grads_finite": finite.astype(jnp.int32),
            "loss_scale": new_scaler.loss_scale,
        }
        if grad_norm is not None:
            metrics["grad_norm"] = grad_norm
        return TrainState(state.step + jnp.int32(1), new_params, new_opt,
                          new_scaler, new_extra), metrics

    state_specs = TrainState(
        step=P(), params=pspecs, opt_state=opt_specs, scaler=scaler_specs,
        extra=(extra_pspecs if has_extra else ()))
    data_spec = (P(AXIS_DP) if AXIS_DP in axes_present else P())
    metric_specs = {"loss": P(), "grads_finite": P(), "loss_scale": P()}
    if clip_grad_norm is not None:
        metric_specs["grad_norm"] = P()
    step_fn = jax.jit(
        jax.shard_map(
            _local_step, mesh=mesh,
            in_specs=(state_specs,) + (data_spec,) * n_batch_args,
            out_specs=(state_specs, metric_specs),
            check_vma=False,
        ),
        donate_argnums=(0,),
    )
    return init_fn, step_fn
