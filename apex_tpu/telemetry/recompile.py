"""Recompile sentinel — turn "never recompile after warmup" into a
monitored runtime guarantee.

The serving engine's whole design rests on one invariant: after warmup
its compiled programs are trace-stable, so the steady state never eats
a multi-second XLA compile (``apex_tpu/serving/engine.py``). Until now
that invariant was a code-review property plus a jit-cache-size assert
in tests; this module makes it observable and enforceable at runtime:

- :class:`RecompileSentinel` subscribes to the runtime's compile-event
  stream (``jax.monitoring`` via
  :func:`apex_tpu._compat.register_monitoring_listeners`) and counts
  executable materialisations —
  ``/jax/core/compile/backend_compile_duration`` fires on fresh
  compiles AND persistent-cache loads, never on in-memory jit-cache
  hits, so it is exactly "a program the warmup didn't cover". Tracked
  functions (``sentinel.track(name, jitted_fn)``) add per-function
  attribution by polling ``_cache_size``.
- :class:`RecompileGuard` is the armed form: entered after warmup, any
  compile event attributed to this sentinel (or unclaimed by every
  live sentinel) increments an alarm counter and — configurably —
  raises :class:`RecompileError` naming what grew. The engine hands
  one out via ``Engine.recompile_guard()``.

Multi-engine safety: the compile-event stream is process-wide, so a
second live engine's (perfectly legitimate) warmup compiles used to be
indistinguishable from a trace-stability breach of the first engine —
its armed guard alarmed on them. Two mechanisms fix the attribution:

- ONE process listener (:class:`_CompileHub`, refcounted across
  sentinels) queues each compile event and resolves OWNERSHIP by
  polling every live sentinel's tracked jit caches: the sentinel whose
  tracked program grew claims the event (its guards alarm, nobody
  else's). The poll is deferred — the jit-cache entry lands only after
  the compiling call returns, so resolution happens at the next
  sentinel read (``alarms_total``/``compiles_total``/guard exit), not
  inside the event callback. An event NO sentinel claims is a genuine
  process-wide hazard (a stray jit in host code) and alarms every
  armed guard, preserving the old safety net.
- :func:`expected_compiles` brackets sanctioned compile windows —
  engine construction and ``warmup()`` use it — so the compiles that
  BUILD an engine never read as another engine's breach. Events in an
  expected window still count in the process-wide
  ``backend_compiles``/registry mirrors; they are simply never
  attributed to a guard.

Attribution races are only possible across threads (an event fires in
thread T while another thread resolves before T's cache entry lands);
the serving stack's single driver-thread discipline makes resolution
exact there.
"""

from __future__ import annotations

import contextlib
import threading
from typing import Any, Callable, Dict, List, Optional

from apex_tpu import _compat

#: the duration event that marks a new executable materialising
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
#: lowering happens once per new traced variant
LOWERING_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
CACHE_MISS_EVENT = "/jax/compilation_cache/cache_misses"


class RecompileError(RuntimeError):
    """An armed :class:`RecompileGuard` observed a compilation."""


def _cache_size(fn) -> Optional[int]:
    size = getattr(fn, "_cache_size", None)
    return size() if callable(size) else None


class _CompileHub:
    """The ONE process-wide ``jax.monitoring`` subscription, shared by
    every installed sentinel (refcounted: the first attach registers
    the listener pair, the last detach releases it — engines created
    in a loop stay listener-neutral).

    Point events (cache hits/misses) and the raw
    ``backend_compiles``/``lowerings`` counts broadcast to every
    sentinel immediately — they are process-wide observability.
    GUARD attribution of a backend-compile event is deferred: the
    event is queued, and :meth:`resolve` (called from every sentinel
    read) polls each sentinel's tracked jit caches — growth claims the
    event for that sentinel alone. Events inside an
    :func:`expected_compiles` bracket are never queued (sanctioned),
    and events no sentinel ever claims broadcast as process-wide
    hazards once a ``final`` resolve (a guard boundary) demands an
    answer."""

    def __init__(self):
        self._lock = threading.Lock()
        self._sentinels: List["RecompileSentinel"] = []
        self._unregister: Optional[Callable[[], None]] = None
        self._pending: List[str] = []   # unattributed event details
        self._expected_depth = 0

    # -- sanctioned compile windows -----------------------------------------

    @contextlib.contextmanager
    def expect(self):
        with self._lock:
            self._expected_depth += 1
        try:
            yield
        finally:
            with self._lock:
                self._expected_depth -= 1
                outermost = self._expected_depth == 0
                sentinels = list(self._sentinels)
            if outermost:
                # settle anything that was pending from BEFORE the
                # bracket, then consume the bracket's own tracked-cache
                # growth: sanctioned compiles must never linger as
                # claim budget a later (unrelated) event could spend
                self.resolve(final=False)
                for s in sentinels:
                    s._claim_budget()

    # -- sentinel lifecycle --------------------------------------------------

    def attach(self, sentinel: "RecompileSentinel") -> None:
        """Register ``sentinel`` for event delivery (first attach
        performs the one process-wide registration)."""
        with self._lock:
            if not self._sentinels:
                self._unregister = _compat.register_monitoring_listeners(
                    self._on_event, self._on_duration)
            self._sentinels.append(sentinel)

    def detach(self, sentinel: "RecompileSentinel") -> None:
        """Drop ``sentinel``; the last detach releases the process
        listener. Pending events this sentinel could still claim are
        resolved first, so a closed engine's compiles can never be
        mis-broadcast to the survivors later."""
        self.resolve(final=False)
        with self._lock:
            if sentinel in self._sentinels:
                self._sentinels.remove(sentinel)
            if self._sentinels:
                return
            unregister, self._unregister = self._unregister, None
            self._pending.clear()
        if unregister is not None:
            unregister()

    # -- the jax.monitoring callbacks ---------------------------------------

    def _on_event(self, name: str, **kw) -> None:
        with self._lock:
            sentinels = list(self._sentinels)
        for s in sentinels:
            s._observe_point(name)

    def _on_duration(self, name: str, seconds: float, **kw) -> None:
        if name == BACKEND_COMPILE_EVENT:
            with self._lock:
                sentinels = list(self._sentinels)
                expected = self._expected_depth > 0
                if not expected:
                    self._pending.append(
                        f"compile event {name} ({seconds:.3f}s)")
            for s in sentinels:
                s._observe_compile(seconds)
            if not expected:
                # try to settle OLDER events now; this one usually
                # resolves at the next sentinel read, once the
                # compiling call has landed its jit-cache entry
                self.resolve(final=False)
        elif name == LOWERING_EVENT:
            with self._lock:
                sentinels = list(self._sentinels)
            for s in sentinels:
                s._observe_lowering()

    # -- attribution ---------------------------------------------------------

    def resolve(self, *, final: bool) -> None:
        """Attribute queued compile events: each live sentinel claims
        as many as its tracked jit caches grew since its last poll;
        leftovers stay queued (the compiling call may not have landed
        its cache entry yet) unless ``final`` — a guard boundary needs
        an answer NOW, so still-unclaimed events broadcast to every
        sentinel as process-wide hazards."""
        with self._lock:
            if not self._pending:
                return
            sentinels = list(self._sentinels)
            pending = self._pending
            self._pending = []
        budgets = [(s, s._claim_budget()) for s in sentinels]
        unclaimed: List[str] = []
        for detail in pending:
            for i, (s, budget) in enumerate(budgets):
                if budget > 0:
                    budgets[i] = (s, budget - 1)
                    s._attribute(detail)
                    break
            else:
                unclaimed.append(detail)
        if not unclaimed:
            return
        if final:
            for detail in unclaimed:
                for s in sentinels:
                    s._attribute(detail)
        else:
            with self._lock:
                # keep queue order: anything that arrived while we
                # were polling goes behind the survivors
                self._pending = unclaimed + self._pending


_HUB = _CompileHub()


def expected_compiles():
    """Context manager marking a sanctioned compile window — engine
    construction, ``warmup()``, a deliberate ahead-of-time compile
    pass. Backend-compile events inside it still count process-wide
    but are never attributed to any sentinel's armed guard (they are
    the compiles guards exist to PROTECT, not to catch)."""
    return _HUB.expect()


class RecompileSentinel:
    """Per-engine compile counters + guard attribution over the shared
    process listener (:class:`_CompileHub`).

    >>> sentinel = RecompileSentinel().install()
    >>> sentinel.track("step", engine._step)
    >>> ... warmup ...
    >>> with sentinel.guard():          # steady state: no compiles
    ...     serve_forever()

    ``compiles_total()["backend_compiles"]`` stays process-wide (every
    event, including sanctioned warmup windows); ``attributed`` counts
    only events attributed to THIS sentinel — its own tracked
    programs' growth plus unclaimed process-wide hazards — and is what
    an armed :class:`RecompileGuard` alarms and raises on, so one live
    engine's warmup can never trip another's guard.

    When ``registry`` is given, counters mirror into it:
    ``jax_compiles_total``, ``jax_lowerings_total``,
    ``jax_compile_seconds_total``, ``recompile_alarms_total``.
    """

    def __init__(self, registry=None):
        #: the registry the counters mirror into (None = unmirrored);
        #: exposed so owners can tell "already wired to X" from "never
        #: wired"
        self.registry = registry
        self._lock = threading.Lock()
        self._counts = {"backend_compiles": 0, "lowerings": 0,
                        "cache_hits": 0, "cache_misses": 0,
                        "attributed": 0}
        self._compile_seconds = 0.0
        self._tracked: Dict[str, Any] = {}
        #: tracked jit-cache sizes at the last attribution poll — the
        #: claim baseline (NOT a guard baseline; guards snapshot
        #: compiles_total themselves)
        self._sizes_seen: Dict[str, int] = {}
        self._installed = False
        self._guards: List["RecompileGuard"] = []
        self._m_compiles = self._m_lowerings = None
        self._m_compile_secs = self._m_alarms = None
        if registry is not None:
            self._m_compiles = registry.counter(
                "jax_compiles_total",
                "executables materialised (fresh compile or "
                "persistent-cache load)")
            self._m_lowerings = registry.counter(
                "jax_lowerings_total", "jaxpr-to-MLIR lowerings (one per "
                "new traced variant)")
            self._m_compile_secs = registry.counter(
                "jax_compile_seconds_total",
                "wall seconds spent materialising executables")
            self._m_alarms = registry.counter(
                "recompile_alarms_total",
                "compiles attributed to this sentinel while a "
                "RecompileGuard was armed")

    # -- listener plumbing --------------------------------------------------

    def install(self) -> "RecompileSentinel":
        """Attach to the shared process listener (idempotent; the hub
        refcounts, so N live sentinels hold ONE ``jax.monitoring``
        registration)."""
        if not self._installed:
            _HUB.attach(self)
            self._installed = True
        return self

    def uninstall(self) -> None:
        """Detach from the shared listener (idempotent; the installed
        flag is cleared BEFORE the hub detach so a re-entrant or
        repeated uninstall can never double-release)."""
        was_installed, self._installed = self._installed, False
        if was_installed:
            _HUB.detach(self)

    # -- hub delivery (broadcast counting) ----------------------------------

    def _observe_point(self, name: str) -> None:
        if name == CACHE_HIT_EVENT:
            with self._lock:
                self._counts["cache_hits"] += 1
        elif name == CACHE_MISS_EVENT:
            with self._lock:
                self._counts["cache_misses"] += 1

    def _observe_compile(self, seconds: float) -> None:
        with self._lock:
            self._counts["backend_compiles"] += 1
            self._compile_seconds += seconds
        if self._m_compiles is not None:
            self._m_compiles.inc()
            self._m_compile_secs.inc(seconds)

    def _observe_lowering(self) -> None:
        with self._lock:
            self._counts["lowerings"] += 1
        if self._m_lowerings is not None:
            self._m_lowerings.inc()

    # -- hub attribution -----------------------------------------------------

    def _claim_budget(self) -> int:
        """How many queued compile events this sentinel can claim:
        total growth of its tracked jit caches since the last poll
        (the poll consumes the growth)."""
        total = 0
        for name, fn in self._tracked.items():
            size = _cache_size(fn)
            if size is None:
                continue
            seen = self._sizes_seen.get(name, size)
            if size > seen:
                total += size - seen
            self._sizes_seen[name] = size
        return total

    def _attribute(self, detail: str) -> None:
        """One compile event lands on THIS sentinel (owned tracked
        growth, or a process-wide hazard nobody claimed): alarm every
        armed guard, once per event on the shared counter."""
        with self._lock:
            self._counts["attributed"] += 1
            guards = list(self._guards)
        for g in guards:
            g._alarm(detail)
        if guards and self._m_alarms is not None:
            self._m_alarms.inc()

    # -- attribution --------------------------------------------------------

    def track(self, name: str, fn) -> None:
        """Attribute compiles to ``name`` by polling ``fn._cache_size``
        (any ``jax.jit`` result). Snapshot deltas are per-function
        ``compiles_total``. Entries already in the cache at track time
        are never claimed retroactively."""
        self._tracked[name] = fn
        size = _cache_size(fn)
        if size is not None:
            self._sizes_seen[name] = size

    def alarms_total(self) -> float:
        """Total recompile-guard alarms observed so far — the registry
        ``recompile_alarms_total`` counter's value (0.0 when the
        sentinel was created without a registry). The public read the
        serving health machine polls each tick; pending compile events
        are claim-resolved first (non-final: an event whose cache
        entry has not landed stays pending rather than broadcasting —
        a cross-thread scrape mid-compile must never turn one
        replica's claimable compile into everyone's alarm; guard
        boundaries do the final resolution), so an OWNED breach is
        visible by the tick after its call returned."""
        _HUB.resolve(final=False)
        return self._m_alarms.value if self._m_alarms is not None else 0.0

    def compiles_total(self) -> Dict[str, Any]:
        """Counter snapshot: process-wide event counts, events
        ``attributed`` to this sentinel (what guards compare), plus
        per-tracked-function jit-cache sizes. Claim-resolves pending
        events non-finally (safe from any thread — see
        :meth:`alarms_total`); unclaimed process-wide hazards settle
        at guard boundaries."""
        _HUB.resolve(final=False)
        with self._lock:
            out: Dict[str, Any] = dict(self._counts)
            out["compile_seconds"] = self._compile_seconds
        out["tracked"] = {name: _cache_size(fn)
                          for name, fn in self._tracked.items()}
        return out

    def guard(self, *, raise_on_recompile: bool = True) -> "RecompileGuard":
        return RecompileGuard(self, raise_on_recompile=raise_on_recompile)


class RecompileGuard:
    """Armed context: entering snapshots the sentinel, any compile
    attributed to it while inside increments ``alarms`` (and the
    registry alarm counter), and ``check()`` / ``__exit__`` raise
    :class:`RecompileError` when ``raise_on_recompile`` (the default)
    and anything grew."""

    def __init__(self, sentinel: RecompileSentinel, *,
                 raise_on_recompile: bool = True):
        self._sentinel = sentinel
        self._raise = raise_on_recompile
        self._baseline: Optional[Dict[str, Any]] = None
        self.alarms: List[str] = []

    def __enter__(self) -> "RecompileGuard":
        self._sentinel.install()
        # guard boundary: settle anything still pending — including
        # broadcasting pre-guard unclaimed strays — BEFORE the
        # baseline, so an old event can never alarm THIS guard
        _HUB.resolve(final=True)
        self._baseline = self._sentinel.compiles_total()
        with self._sentinel._lock:
            self._sentinel._guards.append(self)
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        # settle attribution while still armed: a deferred event that
        # belongs to this sentinel (or to nobody) must alarm THIS
        # guard, not only later guards
        _HUB.resolve(final=True)
        with self._sentinel._lock:
            if self in self._sentinel._guards:
                self._sentinel._guards.remove(self)
        if exc_type is None:
            # always check on exit: with raise_on_recompile=False this
            # still records the breach in alarms / the alarm counter
            self.check()

    def _alarm(self, detail: str) -> None:
        self.alarms.append(detail)

    @property
    def tripped(self) -> bool:
        return bool(self.alarms) or bool(self.delta())

    def delta(self) -> Dict[str, Any]:
        """What grew since ``__enter__``: increases in compile events
        ATTRIBUTED to this sentinel (its tracked programs' growth plus
        unclaimed process-wide hazards — another live engine's owned
        compiles are excluded), reported under ``backend_compiles``,
        plus tracked functions whose jit cache gained entries."""
        if self._baseline is None:
            raise RuntimeError("guard not entered")
        now = self._sentinel.compiles_total()
        out: Dict[str, Any] = {}
        if now["attributed"] > self._baseline["attributed"]:
            out["backend_compiles"] = (
                now["attributed"] - self._baseline["attributed"])
        grew = {}
        for name, size in now["tracked"].items():
            base = self._baseline["tracked"].get(name)
            if size is not None and base is not None and size > base:
                grew[name] = size - base
        if grew:
            out["tracked"] = grew
        return out

    def check(self) -> Dict[str, Any]:
        """Raise (or return) the delta. Call mid-flight for prompt
        failure; ``__exit__`` calls it for you. A guard boundary:
        still-unclaimed pending events resolve finally here (an event
        no live sentinel claims is a process-wide hazard)."""
        _HUB.resolve(final=True)
        delta = self.delta()
        if delta and not self.alarms:
            # breach seen only through cache polling (a new jit-cache
            # entry that reused an executable fires no compile event):
            # record it so the alarm list and counter reflect it even
            # without raising
            self._alarm(f"tracked-cache growth {delta}")
            if self._sentinel._m_alarms is not None:
                self._sentinel._m_alarms.inc()
        if delta and self._raise:
            raise RecompileError(
                f"compilation inside a RecompileGuard — the "
                f"trace-stability invariant is broken: {delta}; "
                f"alarms: {self.alarms}")
        return delta
