"""Pallas TPU kernels for the paper's compute hot-spots (DESIGN.md §2).

The paper's hot path is featurization data movement, not FLOPs, so every
kernel here is a bandwidth-shaped kernel around the dictionary:

- ``bitunpack``  — b-bit packed code words -> int32 codes (DAX-scan analogue)
- ``adv_gather`` — codes -> ADV feature rows, dictionary pinned in VMEM;
  includes the fused packed path (``adv_gather_packed``: unpack -> clamp ->
  multi-hot gather in one pass, int32 codes never materialized)
- ``onehot_wide``— fused one-hot(codes) @ W wide-layer (one-hot never
  materialized in HBM; MXU-shaped accumulation over categorical columns)
- ``hist``       — count-metadata build (per-block histograms, paper §6.2)

Each subpackage: ``kernel.py`` (pl.pallas_call + BlockSpec), ``ops.py``
(jit'd public wrapper), ``ref.py`` (pure-jnp oracle). Every entry point
takes ``interpret=None``, resolved by :func:`resolve_interpret`: the Pallas
interpreter runs only on the CPU backend, so a TPU always runs the compiled
kernel. :func:`compile_refusal` asks the compiler whether a kernel lowers
for a device without running it.
"""
from __future__ import annotations

import jax


def resolve_interpret(interpret: bool | None) -> bool:
    """``interpret`` as given, or — for ``None`` — True exactly when JAX's
    default backend is the CPU (where Pallas can only interpret)."""
    if interpret is None:
        return jax.default_backend() == "cpu"
    return interpret


class KernelRefused(RuntimeError):
    """The compiler refused one or more kernels for the serving device.

    ``refused`` maps each kernel's name to the compiler's own message."""

    def __init__(self, refused: dict[str, str]):
        self.refused = dict(refused)
        super().__init__("; ".join(f"{name} does not compile for this "
                                   f"device: {why}"
                                   for name, why in self.refused.items()))


def compile_refusal(fn, *args) -> str | None:
    """Lower and compile ``jax.jit(fn)`` for ``args`` (``ShapeDtypeStruct``s
    whose shardings name the target device); nothing runs. Returns None
    when the compiler accepts it, else its error as ``"Type: message"``."""
    try:
        jax.jit(fn).lower(*args).compile()
    except Exception as e:   # any refusal (Mosaic, XLA, unimplemented lowering)
        return f"{type(e).__name__}: {e}"
    return None
