"""Public wrapper for the fused one-hot wide layer."""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels.onehot_wide.kernel import onehot_wide_pallas
from repro.kernels.onehot_wide.ref import onehot_wide_ref


def _pad_to(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def onehot_wide(codes: jnp.ndarray, w: jnp.ndarray,
                bn: int = 256, bk: int = 512,
                interpret: bool | None = None) -> jnp.ndarray:
    """codes (C, N) int32, w (C, K, F) -> (N, F) wide-layer output."""
    c, n = codes.shape
    _, k, f = w.shape
    n_pad = _pad_to(max(n, 1), bn)
    k_pad = _pad_to(k, bk)
    f_pad = _pad_to(f, 128)
    # pad codes with an out-of-range index so padded rows hit no one-hot lane
    codes_p = jnp.pad(codes.astype(jnp.int32), ((0, 0), (0, n_pad - n)),
                      constant_values=-1)
    w_p = jnp.pad(w, ((0, 0), (0, k_pad - k), (0, f_pad - f)))
    out = onehot_wide_pallas(codes_p, w_p, bn=bn, bk=bk, interpret=interpret)
    return out[:n, :f]
