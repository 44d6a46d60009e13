"""Host spans and counters on the profiler's clock.

Every span is a ``jax.profiler.TraceAnnotation`` named ``repro.<name>``. An
operator who runs ``jax.profiler`` on a live controller or simulator
(``jax.profiler.trace(dir)``, or ``start_trace``/``stop_trace``) gets them in
the same trace as the device's programs, on the same clock, so each device
program can be put to the host call that issued it and each idle stretch of
the device to what the host was doing.

Counters are integer (or short string) stats on the span that did the work:
``span("p1.solve", rows=8)``. A stat known only at the end is attached with
``set_metadata(...)`` on the span before it closes. A stat never reads a
device array: that would wait for the device.

``retraced(name)`` marks, from inside a traced function body, that JAX traced
the function again (a new shape, or an eager call that traces anew every
time): a zero-length span ``repro.retrace.<name>``. It runs at trace time
only, so a call that hits JAX's cache records nothing.

With no profiler active nothing is recorded, and a span costs about a
microsecond of host time.
"""
from __future__ import annotations

from jax.profiler import TraceAnnotation

PREFIX = "repro."


def span(name: str, **stats) -> TraceAnnotation:
    """A host span ``repro.<name>`` carrying ``stats``; use it in ``with``."""
    return TraceAnnotation(PREFIX + name, **stats)


def retraced(name: str, **stats) -> None:
    """Record a zero-length span ``repro.retrace.<name>``; call it from the
    Python body of a jitted function or a Pallas kernel."""
    with TraceAnnotation(PREFIX + "retrace." + name, **stats):
        pass


def shape(*dims) -> str:
    """A shape as one stat value, ``"8x4"`` (a stat value may hold no comma)."""
    return "x".join(str(int(d)) for d in dims)
