"""Public wrapper: host-side repack to TPU-friendly width + device unpack."""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

from repro.columnar.bitpack import pack_bits, packed_nbytes
from repro.kernels.bitunpack.kernel import bitunpack_pallas, tpu_width


def _pad_to(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def repack_for_device(codes: np.ndarray, bits: int) -> tuple[np.ndarray, int]:
    """Host: pack codes at the TPU-aligned width. Returns (words, device_bits)."""
    db = tpu_width(bits)
    return pack_bits(np.asarray(codes), db), db


# Rows per step of a device-wide unpack. XLA:TPU tiles an array's last two
# dims by (8, 128), so the (words, 32/db) field array of a whole-column
# unpack pads its minor dim to 128 lanes: 16 GiB at 128M rows. Unpacking
# block by block bounds every such intermediate to this many rows. Blocks
# hand their per-row results over as (rows / 128, 128) arrays: the TPU
# compiler takes ~20 s over a loop that carries long 1-D arrays, and under
# a second over the same loop in lane-major 2-D form.
ROW_BLOCK = 1 << 18
LANES = 128


def unpack_rows(flat_words: jnp.ndarray, off: int, db: int, start,
                size: int) -> jnp.ndarray:
    """(size / 128, 128) int32 codes of rows [start, start + size), in row
    order, of the column packed at ``db`` bits from word ``off`` of
    ``flat_words``; ``start`` and ``size`` are multiples of 128, ``start``
    may be traced. Words past the stream's end read as 0."""
    s = 32 // db
    if isinstance(start, int):
        w = flat_words[off + start // s:off + (start + size) // s]
        w = jnp.pad(w, (0, size // s - w.shape[0]))
    else:
        w = jax.lax.dynamic_slice(flat_words, (off + start // s,),
                                  (size // s,))
    shifts = jnp.arange(s, dtype=jnp.uint32) * jnp.uint32(db)
    fields = w[:, None] >> shifts[None, :]
    if db < 32:
        fields = fields & jnp.uint32((1 << db) - 1)
    codes = fields.reshape(size).astype(jnp.int32)
    return codes.reshape(size // LANES, LANES)


def rows_of(x: jnp.ndarray, start, size: int) -> jnp.ndarray:
    """Rows [start, start + size) of a per-row array as (size / 128, 128),
    zero past its end."""
    if isinstance(start, int):
        part = x[start:start + size]
        part = jnp.pad(part, (0, size - part.shape[0]))
    else:
        part = jax.lax.dynamic_slice(x, (start,), (size,))
    return part.reshape(size // LANES, LANES)


def map_row_blocks(fn, n: int, block: int = ROW_BLOCK):
    """Apply ``fn(start, size)`` over rows [0, n) rounded up to 128, block
    by block: the whole blocks under one ``lax.map`` (traced ``start``),
    the rest as one static call. Returns ``(stacked whole-block results or
    None, tail result or None)``."""
    n_full = n // block
    full = None
    if n_full:
        full = jax.lax.map(lambda i: fn(i * block, block),
                           jnp.arange(n_full, dtype=jnp.int32))
    tail_size = _pad_to(max(n, 1), LANES) - n_full * block
    tail = fn(n_full * block, tail_size) if tail_size > 0 else None
    return full, tail


def concat_row_blocks(full, tail, n: int) -> jnp.ndarray:
    """The first ``n`` per-row results of :func:`map_row_blocks`, 1-D."""
    parts = [p.reshape(-1) for p in (full, tail) if p is not None]
    out = parts[0] if len(parts) == 1 else jnp.concatenate(parts)
    return out[:n]


def bitunpack(words: jnp.ndarray, device_bits: int, n: int,
              bw: int = 512, interpret: bool | None = None) -> jnp.ndarray:
    """Unpack ``n`` codes from device-width packed words.

    ``words`` may be over-provisioned (more words than ``n`` codes need —
    e.g. a whole-IMCU buffer queried for a prefix): the excess is sliced off
    before block padding. The kernel emits subfield-major fields; the
    word-major interleave runs block by block (:func:`map_row_blocks`).
    """
    s = 32 // device_bits
    w_needed = (n + s - 1) // s
    w_pad = _pad_to(max(w_needed, 1), bw)
    words = jnp.asarray(words, jnp.uint32)[:w_needed]
    words_p = jnp.pad(words, (0, w_pad - words.shape[0]))
    fields = bitunpack_pallas(words_p, device_bits, bw=bw,
                              interpret=interpret)

    def interleave(start, size):
        part = jax.lax.dynamic_slice(fields, (0, start // s), (s, size // s))
        return part.T.reshape(size // LANES, LANES)

    return concat_row_blocks(*map_row_blocks(interleave, n), n)


def device_overhead(bits: int, n: int) -> float:
    """Bytes-overhead factor of the TPU-aligned width vs exact packing."""
    return packed_nbytes(n, tpu_width(bits)) / packed_nbytes(n, bits)
