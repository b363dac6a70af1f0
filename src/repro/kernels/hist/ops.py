"""Public wrapper for the count-metadata histogram kernel."""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels.hist.kernel import hist_pallas, masked_hist_pallas


def _pad_to(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def hist(codes: jnp.ndarray, k: int, bn: int = 1024, bk: int = 512,
         interpret: bool | None = None) -> jnp.ndarray:
    """Count occurrences of each code in [0, k)."""
    flat = codes.reshape(-1).astype(jnp.int32)
    n = flat.shape[0]
    n_pad = _pad_to(max(n, 1), bn)
    k_pad = _pad_to(k, bk)
    flat_p = jnp.pad(flat, (0, n_pad - n), constant_values=-1)  # no lane hit
    out = hist_pallas(flat_p, k_pad, bn=bn, bk=bk, interpret=interpret)
    return out[:k]


def masked_hist(codes: jnp.ndarray, mask: jnp.ndarray, k: int,
                bn: int = 1024, bk: int = 512,
                interpret: bool | None = None) -> jnp.ndarray:
    """Count occurrences of each code in [0, k) where ``mask`` is set —
    the histogram a predicate-pushdown aggregate runs over a selection
    bitmap instead of the whole column."""
    flat = codes.reshape(-1).astype(jnp.int32)
    m = mask.reshape(-1).astype(jnp.int32)
    n = flat.shape[0]
    n_pad = _pad_to(max(n, 1), bn)
    k_pad = _pad_to(k, bk)
    flat_p = jnp.pad(flat, (0, n_pad - n), constant_values=-1)  # no lane hit
    m_p = jnp.pad(m, (0, n_pad - n))                            # mask=0 pad
    out = masked_hist_pallas(flat_p, m_p, k_pad, bn=bn, bk=bk,
                             interpret=interpret)
    return out[:k]
