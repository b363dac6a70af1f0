"""Fused one-hot wide layer (paper §6.1.3 + Wide&Deep context).

Computes  out[n, :] = sum_c  W[c, codes[c, n], :]  for C categorical columns —
the wide part of a Wide&Deep model — without ever materializing the (N, ΣK)
one-hot design matrix in HBM. Each grid step turns one (BN,) code block of one
column into a VREG-resident one-hot tile and feeds the MXU, accumulating into
the same (BN, F) output tile across columns and K-blocks.

Grid: (N/BN, C, K/BK), output revisited over (c, k).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import resolve_interpret


def _onehot_wide_kernel(codes_ref, w_ref, out_ref, *, bk: int):
    c = pl.program_id(1)
    k = pl.program_id(2)

    @pl.when((c == 0) & (k == 0))
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    codes = codes_ref[...]                        # (1, BN) int32, column c
    w = w_ref[...]                                # (1, BK, F)
    bn = codes.shape[1]
    local = codes.reshape(bn, 1) - k * bk
    col = jax.lax.broadcasted_iota(jnp.int32, (bn, bk), 1)
    onehot = (local == col).astype(w.dtype)
    out_ref[...] += jnp.dot(onehot, w[0],
                            preferred_element_type=out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bn", "bk", "interpret"))
def onehot_wide_pallas(codes: jnp.ndarray, w: jnp.ndarray,
                       bn: int = 256, bk: int = 512,
                       interpret: bool | None = None) -> jnp.ndarray:
    """codes (C, N) int32; w (C, K, F) float -> out (N, F).

    Preconditions (ops.py): N % bn == 0, K % bk == 0.
    """
    c, n = codes.shape
    _, k_rows, f = w.shape
    grid = (n // bn, c, k_rows // bk)
    return pl.pallas_call(
        functools.partial(_onehot_wide_kernel, bk=bk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bn), lambda i, c, k: (c, i)),
            pl.BlockSpec((1, bk, f), lambda i, c, k: (c, k, 0)),
        ],
        out_specs=pl.BlockSpec((bn, f), lambda i, c, k: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, f), w.dtype),
        interpret=resolve_interpret(interpret),
    )(codes, w)
