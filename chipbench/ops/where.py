"""where: a filtered pull, ``FeatureFrontend.submit(where=predicate,
klass="batch")`` then its result, with the predicate the configuration's
loader makes of the query and its parameters (``where(query, params)``).

A trainer's loader consumes the whole answer, so the op reads all of it:
the row count, float64 sums of every feature column, and the rows at the
positions the configuration's reference names
(``sample_positions(params, count)``), which are kept with their
features. Only those are kept, so a window's answers stay small on the
host whatever their size; the reference's ``compare_answers`` checks
them.

The first question a deployment is asked (in the loop's warm-up) first
checks that the system can be warmed at all: it serves one parameter set
of the reference's ``every_params(query)``, then ``PROBES`` more, whose
match counts differ, and raises if the asking thread (where a request's
scan, count and compaction are submitted) lowered any program to XLA
while serving those. A window serves a new count with every request, so
a system whose where path takes a shape from the match count would
compile inside it, and cannot run this deployment: the run stops in its
set-up, with an error.
"""
import threading

import jax
import numpy as np

KLASS = "batch"
PROBES = 3
LOWERING = "/jax/core/compile/jaxpr_to_mlir_module_duration"


class _Lowerings:
    """Programs lowered to XLA by one thread: every jit cache miss,
    whether or not the persistent cache then holds the executable."""

    def __init__(self):
        self.n = 0
        self.thread = threading.get_ident()

    def __call__(self, event: str, secs: float, **_) -> None:
        if event == LOWERING and threading.get_ident() == self.thread:
            self.n += 1


_lowered: _Lowerings | None = None
_checked: set[int] = set()


def _serve(dep, query: str, params: dict) -> dict:
    pred = dep.program.where(query, params)
    feats = dep.frontend.result(dep.frontend.submit(where=pred, klass=KLASS))
    count = int(feats.shape[0])
    pos = dep.ref.sample_positions(params, count)
    return {"count": count, "pos": pos, "rows": feats[pos],
            "sums": feats.sum(axis=0, dtype=np.float64)}


def _check_counts_compile_nothing(dep, query: str) -> None:
    global _lowered
    if _lowered is None:
        _lowered = _Lowerings()
        jax.monitoring.register_event_duration_secs_listener(_lowered)
    _lowered.thread = threading.get_ident()
    _checked.add(id(dep.service))
    every = dep.ref.every_params(query)
    first, *others = every[::len(every) // (PROBES + 1)][:PROBES + 1]
    counts = {_serve(dep, query, first)["count"]}
    before = _lowered.n
    for p in others:
        counts.add(_serve(dep, query, p)["count"])
    if _lowered.n != before:
        raise RuntimeError(
            f"where requests of {len(counts)} match counts lowered "
            f"{_lowered.n - before} programs after the first: this system "
            "compiles its where path per match count, so each request of "
            "the measured window would compile; this deployment needs a "
            "where path whose shapes do not follow the count")


def ask(dep, query: str, params: dict) -> dict:
    if id(dep.service) not in _checked:
        _check_counts_compile_nothing(dep, query)
    return _serve(dep, query, params)


def compare(ref, raw, answers: list) -> dict:
    return ref.compare_answers(raw, answers)


def control(ref, raw, params: list) -> dict:
    return ref.compare_answers(raw, [(p, None) for p in params],
                               control=True)
