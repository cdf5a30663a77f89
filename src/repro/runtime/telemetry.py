"""Spans and counters of the program: the names an operator reads in a
profiler trace of a campaign or an analog forward.

* ``span(name, **attrs)`` is the only way the program records a span: a
  ``jax.profiler.TraceAnnotation`` named ``repro.<name>`` whose keyword
  attributes become stats of the host event.  It lands in the same
  ``.xplane.pb``, on the same clock, as the device's operations.  With no
  profiler running it costs about a microsecond; there is no switch.
* ``count(name, n)`` adds to a process-wide counter and ``snapshot()``
  returns every counter; callers take differences of two snapshots.
* One ``jax.monitoring`` listener, registered at import, counts JAX's own
  compile pipeline: ``xla.traces`` (jaxprs traced, nested jits included),
  ``xla.lowerings`` (jaxprs lowered to a module), ``xla.compiles``
  (backend compiles, persistent-cache loads included) and
  ``xla.cache_hits`` (executables found in the persistent compile cache).

DESIGN.md §15 lists every span and counter name.
"""
from __future__ import annotations

import threading
from typing import Dict

import jax
from jax import monitoring

SPAN_PREFIX = "repro."

# jax.monitoring event -> counter name
_JAX_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "xla.traces",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "xla.lowerings",
    "/jax/core/compile/backend_compile_duration": "xla.compiles",
    "/jax/compilation_cache/cache_hits": "xla.cache_hits",
}

_lock = threading.Lock()
_counts: Dict[str, int] = {}


def span(name: str, **attrs) -> jax.profiler.TraceAnnotation:
    """Context manager: a host span ``repro.<name>`` carrying ``attrs``."""
    return jax.profiler.TraceAnnotation(SPAN_PREFIX + name, **attrs)


def count(name: str, n: int = 1) -> None:
    with _lock:
        _counts[name] = _counts.get(name, 0) + int(n)


def snapshot() -> Dict[str, int]:
    """Every counter's value so far in this process."""
    with _lock:
        return dict(_counts)


def _on_jax_event(event: str, *_args, **_kwargs) -> None:
    name = _JAX_EVENTS.get(event)
    if name is not None:
        count(name)


monitoring.register_event_listener(_on_jax_event)
monitoring.register_event_duration_secs_listener(_on_jax_event)
