"""Bit-unpack Pallas kernel: packed uint32 words -> int32 codes (paper §5.1).

TPU adaptation of the DAX/SIMD packed scan (DESIGN.md §2): TPU vector units
have no cross-lane funnel shift, so gather-free unpacking requires the field
width to divide the 32-bit word. ops.py therefore rounds dictionary widths up
to the next divisor of 32 ({1,2,4,8,16,32}) for device shipping — trading a
bounded ≤2x packing loss (e.g. 6->8 bits) for a fully lane-parallel unpack:

    out.reshape(BW, S)[w, s] = (words[w] >> (s*b)) & mask,  S = 32/b

Each grid step unpacks one (1, BW) word tile into an (S, BW) subfield-major
code tile, all in VREGs; the word-major interleave runs outside the kernel
(ops.py; Mosaic refuses the in-kernel (BW, S) -> (1, BW*S) shape cast).
Host storage (columnar/bitpack.py) keeps exact widths.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import resolve_interpret

DIVISOR_WIDTHS = (1, 2, 4, 8, 16, 32)


def tpu_width(bits: int) -> int:
    """Round a dictionary bit-width up to the next divisor of 32."""
    for w in DIVISOR_WIDTHS:
        if bits <= w:
            return w
    raise ValueError(f"bits {bits} > 32")


def _bitunpack_kernel(words_ref, out_ref, *, bits: int):
    words = words_ref[...]                       # (1, BW) uint32
    bw = words.shape[1]
    s = 32 // bits
    # (S, BW): subfield s of word w
    shifts = jax.lax.broadcasted_iota(jnp.uint32, (s, bw), 0) * jnp.uint32(bits)
    fields = (words.astype(jnp.uint32) >> shifts)
    if bits < 32:
        fields = fields & jnp.uint32((1 << bits) - 1)
    out_ref[...] = fields.astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("bits", "bw", "interpret"))
def bitunpack_pallas(words: jnp.ndarray, bits: int, bw: int = 512,
                     interpret: bool | None = None) -> jnp.ndarray:
    """words (W,) uint32 packed at ``bits`` (must divide 32, W % bw == 0)
    -> (32/bits, W) int32 subfield-major codes: ``[j, w]`` is the code of
    row ``w * 32/bits + j``."""
    if 32 % bits:
        raise ValueError(f"device path needs bits | 32, got {bits} "
                         "(use tpu_width + ops.repack)")
    w = words.shape[0]
    s = 32 // bits
    grid = (w // bw,)
    return pl.pallas_call(
        functools.partial(_bitunpack_kernel, bits=bits),
        grid=grid,
        in_specs=[pl.BlockSpec((1, bw), lambda i: (0, i))],
        out_specs=pl.BlockSpec((s, bw), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((s, w), jnp.int32),
        interpret=resolve_interpret(interpret),
    )(words.reshape(1, w))
