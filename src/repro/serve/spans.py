"""Host spans of the serving tier in the profiler's trace.

``span("kv.tick")`` opens the span ``repro.kv.tick``. Spans record only
while a profiler session is open (``jax.profiler.trace``); with none open
one costs under a microsecond, so the serving path keeps them on.
"""

import jax

PREFIX = "repro."


def span(name: str) -> jax.profiler.TraceAnnotation:
    return jax.profiler.TraceAnnotation(PREFIX + name)
