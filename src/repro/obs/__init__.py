"""Flight-recorder observability: structured tracing + metrics.

Zero-dependency, disabled by default.  The hot paths in the engine
guard every emission behind the module-level :data:`ENABLED` flag::

    from .. import obs as _obs
    ...
    if _obs.ENABLED:
        _obs.inc("price.device_uploads")
    with _obs.span("repack", t=t):
        ...

When no :class:`Obs` is active, ``span()`` hands back a shared no-op
singleton and the counter helpers return immediately — no allocation,
no dict lookups — so instrumented code paths stay bit-identical and
within noise of the uninstrumented build (pinned by
``tests/test_obs.py`` and the decision bench).

Activation is scoped: ``engine.run(..., obs=ob)`` installs ``ob`` for
the duration of the run via :func:`activate`, restoring the previous
state on exit; :func:`enable` installs a process-global recorder for
CLI use (``examples/cluster_sim.py --trace out.json``).  See
``docs/OBSERVABILITY.md`` for the span/metric catalog.

Where JAX is installed, a recorder's spans are also opened as
``jax.profiler.TraceAnnotation``s (a profiled run shows them on the
device trace's clock), and every backend compile while a recorder is
installed lands as a ``jax.compile`` instant under the open span.  JAX
is imported on the first ``Obs()``, never by importing this package.
"""
from __future__ import annotations

import contextlib
from typing import Any, Iterator, Optional, Union

from .metrics import DEFAULT_BUCKETS, Histogram, Registry
from .trace import NULL_SPAN, NullSpan, Span, Tracer

__all__ = [
    "ENABLED", "Obs", "activate", "current", "disable", "dispatch",
    "enable", "event", "inc", "observe", "set_gauge", "span",
    "DEFAULT_BUCKETS", "Histogram", "Registry",
    "NULL_SPAN", "NullSpan", "Span", "Tracer",
]

# single check the hot paths read before touching anything else.  True
# exactly while a recorder is installed (scoped or global).
ENABLED: bool = False

_CURRENT: Optional["Obs"] = None

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_jax_hooked = False
_annotation: Any = None


def _hook_jax() -> Any:
    """``jax.profiler.TraceAnnotation`` (None without JAX); on first use
    also registers the one compile listener this package keeps."""
    global _jax_hooked, _annotation
    if not _jax_hooked:
        _jax_hooked = True
        try:
            import jax.monitoring
            from jax.profiler import TraceAnnotation
        except ImportError:
            return None
        _annotation = TraceAnnotation
        jax.monitoring.register_event_duration_secs_listener(_on_compile)
    return _annotation


def _on_compile(event: str, seconds: float, **_: Any) -> None:
    ob = _CURRENT
    if ob is not None and event == COMPILE_EVENT:
        ob.metrics.inc("jax.compiles")
        ob.tracer.instant("jax.compile", seconds=seconds)


class Obs:
    """One tracer + one metrics registry, recorded together."""

    def __init__(self, capacity: int = 65536):
        self.tracer = Tracer(capacity=capacity, annotate=_hook_jax())
        self.metrics = Registry()

    def export_chrome(self, path: str) -> int:
        """Chrome-trace file with the metrics snapshot embedded."""
        return self.tracer.export_chrome(
            path, metrics=self.metrics.snapshot())

    def reset(self) -> None:
        self.tracer.clear()
        self.metrics.reset()


def current() -> Optional[Obs]:
    return _CURRENT


def enable(ob: Optional[Obs] = None, capacity: int = 65536) -> Obs:
    """Install ``ob`` (or a fresh recorder) process-globally."""
    global _CURRENT, ENABLED
    _CURRENT = ob if ob is not None else Obs(capacity=capacity)
    ENABLED = True
    return _CURRENT


def disable() -> None:
    global _CURRENT, ENABLED
    _CURRENT = None
    ENABLED = False


@contextlib.contextmanager
def activate(ob: Optional[Obs]) -> Iterator[Optional[Obs]]:
    """Scoped install: ``with activate(ob): ...``.

    ``activate(None)`` is a no-op passthrough so call sites can thread
    an optional ``obs=`` parameter without branching."""
    global _CURRENT, ENABLED
    if ob is None:
        yield _CURRENT
        return
    prev = _CURRENT
    _CURRENT = ob
    ENABLED = True
    try:
        yield ob
    finally:
        _CURRENT = prev
        ENABLED = prev is not None


# -- hot-path helpers (no-ops unless ENABLED) --------------------------

def span(name: str, **attrs: Any) -> Union[Span, NullSpan]:
    ob = _CURRENT
    if ob is None:
        return NULL_SPAN
    return ob.tracer.span(name, **attrs)


def dispatch(program: str) -> Union[Span, NullSpan]:
    """``launch.dispatch`` span around a call that enqueues device work
    (``program``: the jitted function's name); counts the launch in
    ``decide.launches``."""
    ob = _CURRENT
    if ob is None:
        return NULL_SPAN
    ob.metrics.inc("decide.launches")
    return ob.tracer.span("launch.dispatch", program=program)


def event(name: str, **attrs: Any) -> None:
    ob = _CURRENT
    if ob is not None:
        ob.tracer.instant(name, **attrs)


def inc(name: str, n: float = 1) -> None:
    ob = _CURRENT
    if ob is not None:
        ob.metrics.inc(name, n)


def observe(name: str, value: float) -> None:
    ob = _CURRENT
    if ob is not None:
        ob.metrics.observe(name, value)


def set_gauge(name: str, value: float) -> None:
    ob = _CURRENT
    if ob is not None:
        ob.metrics.set_gauge(name, value)
