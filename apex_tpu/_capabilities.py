"""Runtime capabilities registry.

The reference gates features at *build* time: ``setup.py --cuda_ext
--fmha --fast_layer_norm ...`` decides which extension modules exist, and
user code probes ``import amp_C`` success (SURVEY.md §5 "Config / flag
system"). On TPU there is no compile step — every feature ships — so the
registry reports *runtime* facts instead: which backend is live, whether
Pallas kernels compile natively or run interpreted, and whether the C++
host runtime loaded (the only genuinely optional native piece; numpy
fallbacks cover its absence).

>>> import apex_tpu
>>> apex_tpu.capabilities()["pallas_native"]   # doctest: +SKIP
True
>>> apex_tpu.has_capability("native_host_runtime")  # doctest: +SKIP
True

Everything here is lazy — importing the module never initialises a JAX
backend.
"""

from __future__ import annotations

from typing import Any, Dict

#: features that are unconditionally present (no build flags on TPU);
#: listed so code ported from apex's "did the extension import?" probes
#: has a stable answer for each upstream flag
_ALWAYS_ON = (
    "amp",                  # --cpp_ext/--cuda_ext amp_C equivalent
    "fused_optimizers",     # multi_tensor_* kernels
    "fused_layer_norm",     # fused_layer_norm_cuda / fast_layer_norm
    "fused_softmax",        # megatron scaled-masked softmax
    "flash_attention",      # fmha / fast_multihead_attn
    "xentropy",             # contrib xentropy
    "transformer",          # apex.transformer TP/PP stack
    "distributed_optimizers",  # distributed_fused_adam/lamb (ZeRO)
    "syncbn",               # syncbn kernels
    "context_parallel",     # ring/Ulysses attention (no apex analogue)
    "moe",                  # expert-parallel MoE over ep (no apex analogue)
)


def capabilities() -> Dict[str, Any]:
    """Snapshot of runtime feature availability (computed per call)."""
    import jax

    from apex_tpu import _native
    from apex_tpu.kernels._utils import use_interpret

    caps: Dict[str, Any] = {name: True for name in _ALWAYS_ON}
    caps["backend"] = jax.default_backend()
    #: False → Pallas kernels run through the interpreter (off-TPU);
    #: numerics identical, throughput is not
    caps["pallas_native"] = not use_interpret()
    #: C++ host runtime (csrc/host_runtime.cpp): pack/unpack staging,
    #: CRC'd .atck IO, prefetching loader; False → numpy fallbacks
    caps["native_host_runtime"] = _native.available()
    return caps


def has_capability(name: str) -> bool:
    """Truthiness of one :func:`capabilities` entry (False if unknown)."""
    return bool(capabilities().get(name, False))


def enable_compilation_cache() -> str:
    """Place JAX's persistent compile cache by the one rule every entry
    point shares: where ``JAX_COMPILATION_CACHE_DIR`` says when it is
    set (JAX reads it itself; an empty value disables the cache), and
    otherwise the fixed ``<checkout>/.jax_cache``. The directory is part
    of the cache key, so it never moves. Returns the directory in effect
    ('' when disabled)."""
    import os

    cache = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if cache is None:
        import jax

        import apex_tpu

        cache = os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(apex_tpu.__file__))), ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", cache)
    return cache
