"""apex_tpu — TPU-native training-acceleration framework.

A ground-up JAX/XLA/Pallas re-design of the capabilities of apex
(kexinyu/apex, a fork of NVIDIA/apex):

- ``apex_tpu.amp``          — mixed-precision policies O0–O3 + functional
  dynamic loss scaling (reference: apex/amp/* (U)).
- ``apex_tpu.multi_tensor`` — flat-buffer pytree packing, the TPU analogue of
  apex's multi_tensor_apply + apex_C flatten/unflatten (U).
- ``apex_tpu.kernels``      — Pallas TPU kernels: fused LayerNorm/RMSNorm,
  scaled-masked softmax, flash attention, fused dense/MLP, Welford stats,
  fused optimizer sweeps (reference: csrc/* (U)).
- ``apex_tpu.optimizers``   — FusedAdam/FusedLAMB/FusedSGD/FusedNovoGrad/
  FusedAdagrad, LARC, ZeRO-style DistributedFusedAdam
  (reference: apex/optimizers/*, apex/contrib/optimizers/* (U)).
- ``apex_tpu.parallel``     — data-parallel runtime + SyncBatchNorm
  (reference: apex/parallel/* (U)).
- ``apex_tpu.transformer``  — tensor/sequence/pipeline parallelism over a
  device mesh (reference: apex/transformer/* (U)).
- ``apex_tpu.mesh``         — the single first-class communication backend:
  mesh axes over ICI/DCN + XLA collectives, replacing NCCL process groups.
- ``apex_tpu.data``         — native prefetching data loaders (C++ host
  runtime, csrc/host_runtime.cpp).
- ``apex_tpu.profiler``     — tracing/metrics subsystem (xprof hooks,
  per-step timing, structured metrics).
- ``apex_tpu.serving``      — static-shape continuous-batching inference
  engine (slot engine + scheduler).
- ``apex_tpu.telemetry``    — system-wide observability: metrics
  registry, per-request span timelines, recompile sentinel, live
  ``/metrics`` endpoint.

Citation convention: ``(U)`` paths refer to the upstream apex layout as
documented in SURVEY.md (the reference mount was empty at survey time).
"""

__version__ = "0.1.0"

try:
    from apex_tpu import mesh  # noqa: F401
except ImportError:
    # No working jax (lint-only CI, a tree too broken to import): the
    # stdlib-only corners (apex_tpu.analysis) stay usable; every
    # jax-backed subpackage raises with the cause on first access via
    # __getattr__ below.
    pass

__all__ = [
    "mesh",
    "amp",
    "multi_tensor",
    "kernels",
    "optimizers",
    "parallel",
    "transformer",
    "contrib",
    "checkpoint",
    "data",
    "normalization",
    "profiler",
    "fp16_utils",
    "mlp",
    "fused_dense",
    "rnn",
    "reparameterization",
    "models",
    "serving",
    "telemetry",
    "testing",
    "capabilities",
    "has_capability",
    "__version__",
]


def __getattr__(name):
    # Lazy subpackage imports keep `import apex_tpu` light and avoid
    # touching jax backends at import time.
    if name in ("capabilities", "has_capability"):
        import importlib

        mod = importlib.import_module("apex_tpu._capabilities")
        return getattr(mod, name)
    if name in __all__:
        import importlib

        try:
            return importlib.import_module(f"apex_tpu.{name}")
        except ModuleNotFoundError as e:
            if e.name == f"apex_tpu.{name}":
                raise AttributeError(
                    f"module 'apex_tpu' has no attribute {name!r} ({e})"
                ) from e
            # the subpackage exists but a dependency (jax) does not —
            # report the real missing module, not a fake attribute
            raise
    raise AttributeError(f"module 'apex_tpu' has no attribute {name!r}")
