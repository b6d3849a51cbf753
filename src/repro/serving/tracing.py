"""Host spans of the serving step loop, written into the profiler's trace.

``span(name, **args)`` marks one host phase of :meth:`Engine.step
<repro.serving.engine.Engine.step>` (``step.schedule``, ``step.admit``,
``step.launch``, ``step.sync``, ``step.observe``, ``step.migrate``,
``step.finish``). With tracing enabled it is a
:class:`jax.profiler.TraceAnnotation`: the profiler keeps the span, on the
clock its device operations are on, and writes it out when the trace
stops. Disabled (the default), it is one shared no-op span, so the step
loop pays a call and an empty ``with`` per phase.

The switch is process-wide because the profiler session it feeds is: the
process that starts a trace turns spans on beside it and off after it.
Spans read no clock of their own, so the engine's virtual timeline and
records are the same with tracing on or off.
"""

from __future__ import annotations

import jax

__all__ = ["enable", "span"]


class _Off:
    """The span of disabled tracing: enters, exits and records nothing."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set_metadata(self, **args) -> None:
        pass


_OFF = _Off()
_on = False


def enable(flag: bool = True) -> None:
    """Turn the step-loop spans on (``True``) or off for this process."""
    global _on
    _on = bool(flag)


def span(name: str, **args):
    """A profiler annotation ``name`` with ``args`` while tracing is
    enabled, otherwise the shared no-op span. Either one, entered, takes
    more args through ``set_metadata`` (values known only at its end)."""
    if not _on:
        return _OFF
    return jax.profiler.TraceAnnotation(name, **args)
