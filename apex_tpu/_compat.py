"""The one seam onto ``jax.monitoring`` (the compile-event stream)."""

from __future__ import annotations

from jax import monitoring


def register_monitoring_listeners(on_event, on_duration):
    """Subscribe to the runtime's compile-event stream
    (``jax.monitoring``), returning the unregister callable.

    ``on_event(name, **kw)`` receives point events (persistent-cache
    hits/misses); ``on_duration(name, seconds, **kw)`` receives duration
    events — ``/jax/core/compile/backend_compile_duration`` is the one
    that matters: it fires whenever a new executable materialises
    (fresh XLA compile OR persistent-cache load) and never on an
    in-memory jit-cache hit.
    """
    monitoring.register_event_listener(on_event)
    monitoring.register_event_duration_secs_listener(on_duration)

    def unregister():
        monitoring.unregister_event_listener(on_event)
        monitoring.unregister_event_duration_listener(on_duration)

    return unregister
