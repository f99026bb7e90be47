"""Packed-bitmap frontier state (paper Algorithm 2).

ScalaBFS tracks vertex status with three bitmaps — ``current_frontier``,
``next_frontier``, ``visited`` — one bit per vertex, held in double-pump
BRAM on the FPGA.  The TPU analogue is a packed ``uint32`` word array that
lives in VMEM inside kernels and in device HBM between iterations.

All functions are pure-jnp and jit-safe; the Pallas kernel in
``repro.kernels.bitmap_update`` implements the fused P3 update against the
same semantics (``repro.kernels.ref`` ties them together).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

WORD_BITS = 32


def num_words(num_bits: int) -> int:
    return (num_bits + WORD_BITS - 1) // WORD_BITS


def zeros(num_bits: int) -> jax.Array:
    return jnp.zeros((num_words(num_bits),), dtype=jnp.uint32)


def from_indices(idx: jax.Array, num_bits: int) -> jax.Array:
    """Bitmap with bits ``idx`` set.  Out-of-range indices are ignored."""
    idx = jnp.asarray(idx)
    valid = (idx >= 0) & (idx < num_bits)
    word = jnp.where(valid, idx // WORD_BITS, num_words(num_bits))
    bit = (jnp.uint32(1) << (idx % WORD_BITS).astype(jnp.uint32))
    bit = jnp.where(valid, bit, 0).astype(jnp.uint32)
    out = jnp.zeros((num_words(num_bits) + 1,), dtype=jnp.uint32)
    out = _scatter_or(out, word, bit)
    return out[:-1]


def _scatter_or(words: jax.Array, word_idx: jax.Array, bits: jax.Array) -> jax.Array:
    """Scatter bitwise-OR: words[word_idx] |= bits (duplicates allowed)."""
    # Decompose into the 32 bit-planes: for plane b, set word w if any
    # scattered element targets (w, b).  at[].max on uint32 of a single bit
    # value is an OR for that bit, but two different bits in the same word
    # would take max instead of OR.  Per-plane scatter-max is exact.
    out = words
    for b in range(WORD_BITS):
        plane = bits & jnp.uint32(1 << b)
        out = out.at[word_idx].max(plane)  # max == OR for single-bit planes
    return out


def from_indices_dense(idx: jax.Array, num_bits: int) -> jax.Array:
    """Bitmap from indices via a dense boolean intermediate (fast path)."""
    dense = jnp.zeros((num_words(num_bits) * WORD_BITS,), dtype=jnp.bool_)
    valid = (idx >= 0) & (idx < num_bits)
    dense = dense.at[jnp.where(valid, idx, num_bits)].max(valid,
                                                          mode="drop")
    return pack(dense)


def pack(mask: jax.Array) -> jax.Array:
    """bool[num_bits] -> uint32[num_words] (little-endian bit order)."""
    nb = mask.shape[0]
    pad = (-nb) % WORD_BITS
    m = jnp.pad(mask, (0, pad)).reshape(-1, WORD_BITS).astype(jnp.uint32)
    shifts = jnp.arange(WORD_BITS, dtype=jnp.uint32)
    return jnp.sum(m << shifts, axis=1, dtype=jnp.uint32)


def unpack(words: jax.Array, num_bits: int | None = None) -> jax.Array:
    """uint32[num_words] -> bool[num_bits]."""
    shifts = jnp.arange(WORD_BITS, dtype=jnp.uint32)
    bits = (words[:, None] >> shifts[None, :]) & jnp.uint32(1)
    flat = bits.reshape(-1).astype(jnp.bool_)
    return flat if num_bits is None else flat[:num_bits]


def test_bits(words: jax.Array, idx: jax.Array) -> jax.Array:
    """Gathered bit test: returns bool per index."""
    w = words[idx // WORD_BITS]
    return ((w >> (idx % WORD_BITS).astype(jnp.uint32)) & 1).astype(jnp.bool_)


def popcount(words: jax.Array) -> jax.Array:
    return jnp.sum(jax.lax.population_count(words).astype(jnp.int32))


def np_unpack(words: np.ndarray, num_bits: int) -> np.ndarray:
    b = np.unpackbits(words.view(np.uint8), bitorder="little")
    return b[:num_bits].astype(bool)


# ---------------------------------------------------------------------------
# Batched (multi-source) bit-planes: one bit per BFS source, packed along the
# LAST axis.  A `[n, B]` boolean plane-set packs to uint32[n, ceil(B/32)]:
# element v holds the source-mask of vertex v, so a whole 32/64-root batch
# rides on every CSR edge read (MS-BFS sharing; Then et al., VLDB'14).
# ---------------------------------------------------------------------------

def pack_rows(mask: jax.Array) -> jax.Array:
    """bool[..., B] -> uint32[..., num_words(B)] (little-endian bit order)."""
    nb = mask.shape[-1]
    pad = (-nb) % WORD_BITS
    widths = [(0, 0)] * (mask.ndim - 1) + [(0, pad)]
    m = jnp.pad(mask, widths).reshape(
        *mask.shape[:-1], -1, WORD_BITS).astype(jnp.uint32)
    shifts = jnp.arange(WORD_BITS, dtype=jnp.uint32)
    return jnp.sum(m << shifts, axis=-1, dtype=jnp.uint32)


def unpack_rows(words: jax.Array, num_bits: int | None = None) -> jax.Array:
    """uint32[..., nw] -> bool[..., num_bits]."""
    shifts = jnp.arange(WORD_BITS, dtype=jnp.uint32)
    bits = (words[..., None] >> shifts) & jnp.uint32(1)
    flat = bits.reshape(*words.shape[:-1], -1).astype(jnp.bool_)
    return flat if num_bits is None else flat[..., :num_bits]


def plane_mask(num_bits: int) -> jax.Array:
    """uint32[num_words] with the first ``num_bits`` bits set — masks the
    pad bits of the last source word (needed before complementing)."""
    bits = jnp.arange(num_words(num_bits) * WORD_BITS) < num_bits
    return pack(bits)


def pad_plane_slots(roots: np.ndarray, fill: int | None = None,
                    word_bits: int = WORD_BITS) -> tuple[np.ndarray, int]:
    """Pad a 1-D slot array so its length fills whole uint32 plane words.

    Dynamic-batching waves rarely arrive as an exact multiple of 32.  Each
    slot is an independent bit-plane and duplicate roots are legal, so the
    pad slots repeat ``fill`` (default: the first root); the packed word
    count — and therefore every jitted MS-BFS step shape — stays constant
    across wave sizes, keeping the compilation cache hot.

    Pad-slot work must stay INERT: a duplicate plane never changes the
    union frontier (its bits ride word lanes that are already set), so the
    per-level edge traffic is unchanged, and callers must both slice
    results with :func:`slice_plane_rows` AND account TEPS over the real
    requests only (``launch.dynbatch`` recounts traversed edges from the
    sliced rows for exactly this reason).  ``fill`` may name a different
    (e.g. known-isolated) vertex; it must be a non-negative integer —
    bounds against |V| are the engine's ``validate_roots`` job.  Returns
    ``(padded_roots, original_length)``.
    """
    roots = np.asarray(roots)
    if roots.ndim != 1 or roots.size == 0:
        raise ValueError(f"roots must be 1-D and non-empty, got shape "
                         f"{roots.shape}")
    if fill is not None:
        if isinstance(fill, bool) or not isinstance(fill, (int, np.integer)):
            raise TypeError(f"fill must be an integer vertex id, got "
                            f"{type(fill).__name__} ({fill!r})")
        if fill < 0:
            raise ValueError(f"fill must be non-negative, got {fill}")
    b = int(roots.size)
    pad = (-b) % word_bits
    if pad == 0:
        return roots, b
    fill_v = roots[0] if fill is None else fill
    return np.concatenate(
        [roots, np.full(pad, fill_v, dtype=roots.dtype)]), b


def slice_plane_rows(rows, b: int):
    """Drop the pad slots of :func:`pad_plane_slots` from a per-slot result
    (levels ``[B_padded, n]`` -> ``[b, n]``, or any leading-axis array)."""
    return rows[:b]


def _scatter_or_rows(words: jax.Array, row_idx: jax.Array,
                     msg: jax.Array) -> jax.Array:
    """Packed scatter-OR: ``words[row_idx[e]] |= msg[e]`` for every e.

    The jnp fallback for the fused P2->P3 Pallas propagate kernel
    (``repro.kernels.msbfs_propagate``), with identical semantics: duplicate
    target rows OR together and out-of-range rows are dropped.  ``at[].max``
    is only an OR for single-bit values, so the words are decomposed into
    bit planes first — vectorized over the 4 byte lanes of each uint32, so
    the whole scatter is ONE gather-free call of uint8 single-bit planes
    (8 planes per lane) instead of 32 sequential word-sized scatters.

    words: uint32[r, nw]   accumulator (existing bits are kept)
    row_idx: int32[m]      target row per message (OOR -> dropped)
    msg: uint32[m, nw]     packed source-mask words to OR in
    """
    r, nw = words.shape
    m = msg.shape[0]
    # negative indices would WRAP (numpy semantics), not drop — rewrite
    # them to r so mode="drop" discards them like any other OOR row
    row_idx = jnp.where(row_idx < 0, r, row_idx)
    shifts = jnp.uint8(1) << jnp.arange(8, dtype=jnp.uint8)
    def to_planes(w):
        b8 = jax.lax.bitcast_convert_type(w, jnp.uint8)      # [.., nw, 4]
        return (b8[..., None] & shifts).reshape(*w.shape[:-1], nw * 32)
    acc = to_planes(words).at[row_idx].max(to_planes(msg), mode="drop")
    bytes_ = acc.reshape(r, nw, 4, 8).sum(-1).astype(jnp.uint8)
    return jax.lax.bitcast_convert_type(bytes_, jnp.uint32).reshape(r, nw)


def segment_or_rows(msg: jax.Array, seg: jax.Array) -> jax.Array:
    """Inclusive segmented OR-scan over rows of packed words.

    ``msg`` is uint32[E, nw] (one packed source-mask per edge), ``seg`` is
    int[E], the segment of each row: rows of one segment are contiguous
    and carry equal ids.  Returns scan[E, nw] where scan[e] = OR of msg
    over e's segment up to e — read the last slot of each segment for the
    per-segment OR.  This is how the pull direction reduces each vertex's
    in-list without any scatter: CSC edges are already grouped by child,
    so the child of each edge (``LocalGraph.in_child``) is the segment id.

    Hillis–Steele doubling: ceil(log2 E) passes, each ORing in the row
    ``2^k`` back when it lies in the same segment.  Every pass is a static
    shift, which the TPU compiler handles in seconds at any E, whereas
    ``lax.associative_scan`` costs it compile memory and time in
    proportion to E (about 4 GB and 40 s per million rows on v5e).
    """
    e = msg.shape[0]
    x = msg
    shift = 1
    while shift < e:
        same = seg[shift:] == seg[:-shift]
        prev = jnp.where(same[:, None], x[:-shift], jnp.uint32(0))
        x = jnp.concatenate([x[:shift], x[shift:] | prev])
        shift *= 2
    return x


def any_rows(words: jax.Array) -> jax.Array:
    """bool[...]: does row v have any source bit set?"""
    return jnp.any(words != 0, axis=-1)


def popcount_rows(words: jax.Array) -> jax.Array:
    """int32[...]: per-row popcount over the packed source words."""
    return jnp.sum(jax.lax.population_count(words).astype(jnp.int32),
                   axis=-1)
