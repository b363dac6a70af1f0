"""Histogram / count-metadata build Pallas kernel (paper §6.2).

Builds the per-dictionary-entry counts from a code stream: the operation a
columnar DB runs at load time so that later stats queries never scan rows.

Grid: (K/BK, N/BN) — N innermost so each (1, BK) count tile stays resident in
VMEM while code blocks stream past it; per block the partial histogram is a
compare-against-iota matrix reduced over the code axis (VPU work, no MXU).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import resolve_interpret


def _hist_kernel(codes_ref, out_ref, *, bk: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    codes = codes_ref[...]                       # (1, BN) int32
    k0 = pl.program_id(0) * bk
    bn = codes.shape[1]
    rows = jax.lax.broadcasted_iota(jnp.int32, (bk, bn), 0) + k0
    hits = (rows == codes).astype(jnp.int32)     # (BK, BN)
    out_ref[...] += hits.sum(axis=1, keepdims=True).reshape(1, bk)


@functools.partial(jax.jit, static_argnames=("k", "bn", "bk", "interpret"))
def hist_pallas(codes: jnp.ndarray, k: int, bn: int = 1024, bk: int = 512,
                interpret: bool | None = None) -> jnp.ndarray:
    """codes (N,) int32 in [0, k) -> counts (k,) int32.

    Preconditions (ops.py): N % bn == 0, k % bk == 0.
    """
    n = codes.shape[0]
    grid = (k // bk, n // bn)
    return pl.pallas_call(
        functools.partial(_hist_kernel, bk=bk),
        grid=grid,
        in_specs=[pl.BlockSpec((1, bn), lambda i, j: (0, j))],
        out_specs=pl.BlockSpec((1, bk), lambda i, j: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, k), jnp.int32),
        interpret=resolve_interpret(interpret),
    )(codes.reshape(1, n)).reshape(k)


def _masked_hist_kernel(codes_ref, mask_ref, out_ref, *, bk: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    codes = codes_ref[...]                       # (1, BN) int32
    mask = mask_ref[...]                         # (1, BN) int32
    k0 = pl.program_id(0) * bk
    bn = codes.shape[1]
    rows = jax.lax.broadcasted_iota(jnp.int32, (bk, bn), 0) + k0
    hits = ((rows == codes) & (mask > 0)).astype(jnp.int32)   # (BK, BN)
    out_ref[...] += hits.sum(axis=1, keepdims=True).reshape(1, bk)


@functools.partial(jax.jit, static_argnames=("k", "bn", "bk", "interpret"))
def masked_hist_pallas(codes: jnp.ndarray, mask: jnp.ndarray, k: int,
                       bn: int = 1024, bk: int = 512,
                       interpret: bool | None = None) -> jnp.ndarray:
    """codes (N,) int32 in [0, k), mask (N,) int32 -> counts (k,) int32 of
    the codes whose mask lane is nonzero — the predicate-pushdown aggregate
    core: the count tile stays resident while code AND selection-bitmap
    blocks stream past it together.

    Preconditions (ops.py): N % bn == 0, k % bk == 0.
    """
    n = codes.shape[0]
    grid = (k // bk, n // bn)
    return pl.pallas_call(
        functools.partial(_masked_hist_kernel, bk=bk),
        grid=grid,
        in_specs=[pl.BlockSpec((1, bn), lambda i, j: (0, j)),
                  pl.BlockSpec((1, bn), lambda i, j: (0, j))],
        out_specs=pl.BlockSpec((1, bk), lambda i, j: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, k), jnp.int32),
        interpret=resolve_interpret(interpret),
    )(codes.reshape(1, n), mask.reshape(1, n)).reshape(k)
