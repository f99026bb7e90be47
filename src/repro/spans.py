"""Host spans on the device trace's clock.

``span(name, **args)`` is a ``jax.profiler.TraceAnnotation``: while the
profiler traces (``jax.profiler.start_trace``) it writes a host event,
with ``args`` as its stats, into the same ``.xplane.pb`` as the device's
programs, so a span and a device op share one clock.  While it does not,
a span costs about a microsecond.  There is no switch.  Args that
are known only inside the span go in with ``set_metadata``::

    with span("vp.level.host", level=3, retry=0) as sp:
        ...
        sp.set_metadata(mode="push", budget=1 << 20)

Span names are stable strings: readers key on them.

* ``vp.wave`` (slots, budget), ``vp.init``, ``vp.sync`` (level, retry),
  ``vp.level.host`` (level, retry, mode, budget), ``vp.rows`` (slots):
  the level loop, ``VertexProgramRunner._run_packed``;
* ``dynbatch.submit`` (req), ``dynbatch.cut`` (wave, batch, preempted),
  ``dynbatch.prepare``, ``dynbatch.execute``, ``dynbatch.finish`` (wave):
  the serving layer, ``launch.dynbatch``;
* ``host.gc`` (generation, collected): Python's cyclic collector, through
  ``gc.callbacks`` (:func:`gc_spans`).
"""
from __future__ import annotations

import gc

from jax.profiler import TraceAnnotation


def span(name: str, **args) -> TraceAnnotation:
    """A host span named ``name`` with ``args`` as its stats."""
    return TraceAnnotation(name, **args)


_gc_open: list[TraceAnnotation] = []


def _on_gc(phase: str, info: dict) -> None:
    # a collection starts and stops on one thread, and never two at once
    if phase == "start":
        if TraceAnnotation.is_enabled():
            ann = TraceAnnotation("host.gc", generation=info["generation"])
            ann.__enter__()
            _gc_open.append(ann)
    elif _gc_open:
        ann = _gc_open.pop()
        ann.set_metadata(collected=info["collected"])
        ann.__exit__(None, None, None)


def gc_spans() -> None:
    """Record each run of Python's collector as a ``host.gc`` span.
    Idempotent."""
    if _on_gc not in gc.callbacks:
        gc.callbacks.append(_on_gc)
