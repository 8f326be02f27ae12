"""Stable names for the serving path on a profiler trace.

Device: each executable the serving path launches is built by `phase_jit`,
so its XLA module reads ``jit_essr_<phase>`` in a trace whatever the Python
function behind it is called. A phase may launch several executables of one
name; naming never merges or splits what a frame launches.

Host: `frame_span` opens a frame's top-level span (``essr.serve``, or
``essr.launch`` / ``essr.finalize`` under fused dispatch) and makes the
frame's launch index the ``frame`` stat of every `span` opened inside it, in
whichever module, so all spans of one frame share one identifier. Spans are
`jax.profiler.TraceAnnotation`s: on the profiler's clock with the device
ops, and close to free when no profiler runs. They belong in host code
only, never in a traced body. The names are listed in docs/api.md
("Tracing").
"""
from __future__ import annotations

import contextlib
import contextvars
import functools
from typing import Callable, Iterator

import jax

#: Launch index of the frame whose `frame_span` is open (-1: none).
_FRAME = contextvars.ContextVar("essr_frame", default=-1)


def phase_jit(name: str, **jit_kwargs) -> Callable[[Callable], Callable]:
    """Decorator: ``jax.jit(fun, **jit_kwargs)`` whose executable is named
    ``jit_<name>`` (JAX names a module after the function's ``__name__``).
    Nested inside another jit it is inlined like any jitted call."""
    def wrap(fun: Callable) -> Callable:
        @functools.wraps(fun)
        def body(*args, **kwargs):
            return fun(*args, **kwargs)
        body.__name__ = body.__qualname__ = name
        return jax.jit(body, **jit_kwargs)
    return wrap


def lane_phase(width: int) -> str:
    """Phase of one subnet lane: the bilinear floor (width 0) or a conv
    subnet, named from its width (``essr_c27``, ``essr_c54``)."""
    return "essr_bilinear" if width == 0 else f"essr_c{width}"


def span(name: str, **stats) -> jax.profiler.TraceAnnotation:
    """A host span inside the open frame, tagged with its ``frame`` stat."""
    return jax.profiler.TraceAnnotation(name, frame=_FRAME.get(), **stats)


@contextlib.contextmanager
def frame_span(name: str, index: int) -> Iterator[None]:
    """The top-level span of frame ``index``; spans opened inside it carry
    ``frame=index``."""
    token = _FRAME.set(index)
    try:
        with jax.profiler.TraceAnnotation(name, frame=index):
            yield
    finally:
        _FRAME.reset(token)
