"""Host spans of the served path, on the profiler's clock.

``span(name, **args)`` opens a ``jax.profiler.TraceAnnotation``: it is
recorded, with its args, only while a profiler trace runs (on the host
plane, beside the device's programs), and costs about a microsecond
otherwise.  Names start with ``lsh.``; spans of one query batch share the
arg ``batch``.  Args that are None are left out.
"""
from __future__ import annotations

import jax


def span(name: str, **args) -> jax.profiler.TraceAnnotation:
    return jax.profiler.TraceAnnotation(
        name, **{k: v for k, v in args.items() if v is not None})
