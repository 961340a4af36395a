"""Bit-packing of SYMOG mantissas for serving.

After SYMOG training, every quantizable weight is an integer mantissa
m ∈ [-(2^{N-1}-1), 2^{N-1}-1] times a power-of-two scale 2^{-f}.  For
N ∈ {2, 4} we pack 4 (resp. 2) mantissas per int8 byte along the last
axis — on TPU this cuts HBM→VMEM weight traffic 4×/2× vs int8 and 8×/4×
vs bf16, which is the bandwidth-side realization of the paper's
"bit shift replaces multiplication" claim (see DESIGN.md §2).

Layout (split planes): the word axis is cut into chunks of ``PLANE_WORDS``
= 128 words (the last chunk may be shorter, w words).  Within a chunk of w
words, bit field i (bits [i·N, (i+1)·N), two's complement) of word j holds
value ``chunk_start + i·w + j`` — each field is a contiguous plane of w
values.  Unpacking a chunk is therefore ``per`` shift/mask passes plus a
lane-axis concatenate of whole planes: a full chunk is exactly one 128-lane
TPU vreg row, so the Pallas kernels unpack with no minor-dim interleave
reshape (DESIGN.md §2).  Rows of at most 128 words are one chunk, which is
the split-halves int4 order of the paged KV pools (§11).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Tuple

import jax
import jax.numpy as jnp


@dataclasses.dataclass
class Packed:
    """A packed fixed-point tensor: int8 words + static metadata.

    ``shape`` (the original unpacked shape) is DERIVED from the word array,
    not stored: pack() requires exact divisibility, so the last dim is just
    words·(8/n_bits).  That keeps Packed closed under lax.scan / vmap leaf
    slicing — a stacked layer group scans Packed params like any float
    leaf (see repro.models.quantized.scan_ready)."""

    data: jax.Array  # int8, shape[..., last/per_byte]
    n_bits: int
    f: jax.Array  # int32 scalar or per-leading-dim array (layers/experts)

    @property
    def shape(self) -> Tuple[int, ...]:
        per = 8 // self.n_bits
        return tuple(self.data.shape[:-1]) + (self.data.shape[-1] * per,)

    def tree_flatten(self):
        return (self.data, self.f), (self.n_bits,)

    @classmethod
    def tree_unflatten(cls, aux, children):
        data, f = children
        (n_bits,) = aux
        return cls(data=data, n_bits=n_bits, f=f)


jax.tree_util.register_pytree_node(
    Packed, Packed.tree_flatten, Packed.tree_unflatten
)


PLANE_WORDS = 128  # words per packing chunk: one TPU lane width


def values_per_byte(n_bits: int) -> int:
    if n_bits not in (2, 4, 8):
        raise ValueError(f"packing supports n_bits in (2,4,8), got {n_bits}")
    return 8 // n_bits


def _chunks(words: int):
    """(start, width) of each packing chunk along a ``words``-long axis."""
    return [(c, min(PLANE_WORDS, words - c)) for c in range(0, words, PLANE_WORDS)]


def pack_int(m: jax.Array, n_bits: int) -> jax.Array:
    """Pack integer mantissas (any int dtype, values fit N-bit signed) into
    int8 words along the last axis, split-planes order (module docstring).
    Last dim must be divisible by 8//n_bits."""
    per = values_per_byte(n_bits)
    if n_bits == 8:
        return m.astype(jnp.int8)
    *lead, last = m.shape
    if last % per != 0:
        raise ValueError(f"last dim {last} not divisible by {per}")
    mask = (1 << n_bits) - 1
    g = m.astype(jnp.int32) & mask
    out = []
    for c, w in _chunks(last // per):
        planes = g[..., c * per : (c + w) * per].reshape(*lead, per, w)
        shifts = (jnp.arange(per, dtype=jnp.int32) * n_bits)[:, None]
        out.append(jnp.sum(planes << shifts, axis=-2))
    word = jnp.concatenate(out, axis=-1)
    # int32 word fits in a byte (per*n_bits == 8); reinterpret via uint8.
    return word.astype(jnp.uint8).view(jnp.int8)


def unpack_int(packed: jax.Array, n_bits: int, last_dim: int) -> jax.Array:
    """Inverse of pack_int: int8 words -> int8 mantissas (sign-extended)."""
    per = values_per_byte(n_bits)
    if n_bits == 8:
        return packed.astype(jnp.int8)
    mask = (1 << n_bits) - 1
    sign = 1 << (n_bits - 1)
    w_all = packed.view(jnp.uint8).astype(jnp.int32)
    *lead, words = w_all.shape
    shifts = (jnp.arange(per, dtype=jnp.int32) * n_bits)[:, None]
    out = []
    for c, w in _chunks(words):
        fields = (w_all[..., None, c : c + w] >> shifts) & mask  # (..., per, w)
        out.append(fields.reshape(*lead, per * w))
    vals = jnp.concatenate(out, axis=-1)
    vals = (vals ^ sign) - sign  # sign-extend N-bit two's complement
    assert vals.shape[-1] == last_dim, (vals.shape, last_dim)
    return vals.astype(jnp.int8)


def unpack_planes(words: jax.Array, n_bits: int, dtype=jnp.int32) -> jax.Array:
    """``unpack_int`` written as the Pallas kernels run it: (rows, w) int8
    words -> (rows, w·per) mantissas by lane slices and a lane concatenate
    of whole planes — no reshape of the minor dims, which Mosaic refuses.
    Each field is shifted to the top of an int32 and arithmetic-shifted
    down, which sign-extends it."""
    per = values_per_byte(n_bits)
    x = words.astype(jnp.int32)
    planes = []
    for c, w in _chunks(x.shape[-1]):
        chunk = x[..., c : c + w]
        for i in range(per):
            planes.append(((chunk << (32 - (i + 1) * n_bits)) >> (32 - n_bits)).astype(dtype))
    return jnp.concatenate(planes, axis=-1)


@functools.partial(jax.jit, static_argnames=("n_bits",))
def _pack_words(weight: jax.Array, f, n_bits: int) -> jax.Array:
    from repro.core.quantizer import quantize_int, delta_from_f

    delta = delta_from_f(f)
    # broadcast per-expert f over trailing dims
    while jnp.ndim(delta) < jnp.ndim(weight):
        delta = delta[..., None]
    return pack_int(quantize_int(weight, delta, n_bits), n_bits)


def pack(weight: jax.Array, f, n_bits: int) -> Packed:
    """Quantize an already-converged SYMOG weight and pack its mantissas
    (one fused program per leaf: the int32 temporaries of a full-size
    layer stack never coexist in device memory)."""
    return Packed(
        data=_pack_words(weight, f, n_bits),
        n_bits=n_bits,
        f=jnp.asarray(f, jnp.int32),
    )


def unpack(p: Packed, dtype=jnp.float32) -> jax.Array:
    """Dequantize to ``dtype``: m · 2^{-f} (exact: exponent-only scale)."""
    m = unpack_int(p.data, p.n_bits, p.shape[-1]).astype(dtype)
    scale = jnp.exp2(-p.f.astype(dtype))
    while jnp.ndim(scale) < jnp.ndim(m):
        scale = scale[..., None]
    return m * scale
