"""Program spans on the JAX profiler's clock.

``span(name, **args)`` is a context manager. While a profiler runs
(``jax.profiler.start_trace`` / ``jax.profiler.trace``) it is a
``jax.profiler.TraceAnnotation``: a host event the profiler keeps in
memory and writes with the device planes at ``stop_trace``, on the clock
of the device operations. Otherwise it is :data:`OFF`, one shared object
that does nothing, so a span costs one check and builds no metadata.

Metadata known only inside the span goes in through ``set_metadata``;
guard work that builds it with ``if sp is not OFF``. The profiler splits
metadata at commas, so a list of ids is one space-separated string
(:func:`ids`).
"""
from __future__ import annotations

from jax.profiler import TraceAnnotation


class _Off:
    """The span while no profiler runs."""
    __slots__ = ()

    def __enter__(self) -> "_Off":
        return self

    def __exit__(self, *exc) -> None:
        return None

    def set_metadata(self, **args) -> None:
        return None


OFF = _Off()


def span(name: str, **args):
    """A profiler span named ``name`` with ``args`` as its metadata, or
    :data:`OFF` when no profiler runs."""
    if not TraceAnnotation.is_enabled():
        return OFF
    return TraceAnnotation(name, **args)


def ids(values) -> str:
    """``[1, 2, 7]`` -> ``"1 2 7"``: ids as one metadata value."""
    return " ".join(map(str, values))
