"""jit'd public wrappers for the Pallas kernels (padding, dtypes, reshapes).

``interpret=None`` auto-selects: real TPU lowering on TPU backends,
interpreter (Python/CPU execution of the kernel body) elsewhere — the
validation mode this container uses.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.kernels import bitset as _bitset
from repro.kernels import flashattn as _fa
from repro.kernels import matreduce as _mr
from repro.kernels import sddmm as _sd


def _auto_interpret(interpret):
    if interpret is not None:
        return interpret
    return jax.default_backend() != "tpu"


def _pad2(x, bm, bn):
    M, N = x.shape
    pm, pn = (-M) % bm, (-N) % bn
    if pm or pn:
        x = jnp.pad(x, ((0, pm), (0, pn)))
    return x


def sddmm(lhs, rhs, mask, *, bm=128, bn=128, bk=128, interpret=None):
    M, N = mask.shape
    interpret = _auto_interpret(interpret)
    lhs_p = _pad2(lhs, bm, bk)
    rhs_p = _pad2(rhs, bn, bk)
    mask_p = _pad2(mask, bm, bn)
    out = _sd.sddmm(lhs_p, rhs_p, mask_p, bm=min(bm, lhs_p.shape[0]),
                    bn=min(bn, rhs_p.shape[0]), bk=min(bk, lhs_p.shape[1]),
                    interpret=interpret)
    return out[:M, :N]


def masked_matmul_reduce(lhs, rhs, mask, *, bm=128, bn=128, bk=128,
                         interpret=None):
    interpret = _auto_interpret(interpret)
    lhs_p = _pad2(lhs, bm, bk)
    rhs_p = _pad2(rhs, bn, bk)
    mask_p = _pad2(mask, bm, bn)
    return _mr.matreduce(lhs_p, rhs_p, mask_p, bm=min(bm, lhs_p.shape[0]),
                         bn=min(bn, rhs_p.shape[0]),
                         bk=min(bk, lhs_p.shape[1]), interpret=interpret)


def triangle_count(adj, *, interpret=None):
    """Σ A ⊙ (A@A) / 6 with the product tile kept in VMEM."""
    a = jnp.asarray(adj, jnp.float32)
    return masked_matmul_reduce(a, a, a, interpret=interpret) / 6.0


def _tile(interpret: bool, tile=None) -> int:
    """Join-kernel tile: 128 compiled for TPU (the lane width; VMEM-
    sized), 1024 interpreted, where per-grid-step dispatch dominates and
    VMEM is no constraint — fewer, larger tiles keep the CPU validation
    path faster than the XLA dense-mask join.  The certified chunk
    (``block``) is applied inside the tile, so it never sets the lane
    width."""
    return tile if tile is not None else (1024 if interpret else 128)


def _count_call(op: str, cut: int, interpret: bool):
    obs.counter("kernel.calls", op=op, cut=cut,
                mode="interpret" if interpret else "compiled")


def cutjoin_reduce(factors, *, distinct=True, block=None, tile=None,
                   interpret=None, offsets=None) -> float:
    """The decomposition join Σ_{e_c} Π_i M_i(e_c) as a fused kernel.

    ``factors`` is a sequence of equal-shape cut tensors: (n,) vectors for
    |cut| = 1 (``distinct`` is moot — one vertex is always injective) or
    (n, n) matrices for |cut| = 2, where ``distinct`` applies the
    off-diagonal injectivity mask in-kernel from tile indices.  Arbitrary
    ``n`` works (zero-padding to the tile multiple).  ``block`` bounds
    the cells per f32 partial — take it from ``cutjoin_exact_block`` so
    integer counts stay exact; partials are summed in f64.  ``offsets``
    gives the factors' global start index per cut axis when the caller
    holds only a slice (the mesh tier — see ``distributed/cutjoin.py``).
    """
    interpret = _auto_interpret(interpret)
    tile = _tile(interpret, tile)
    _count_call("cutjoin_reduce",
                2 if getattr(factors[0], "ndim", 2) == 2 else 1, interpret)
    return _mr.prod_reduce(factors, distinct=distinct, block=block or tile,
                           tile=tile, interpret=interpret, offsets=offsets)


def cutjoin_reduce_keep(factors, *, keep=0, distinct=True, block=None,
                        tile=None, interpret=None,
                        offsets=None) -> np.ndarray:
    """Keep-axis decomposition join: out[x] = Σ_{y≠x} Π_i M_i(x, y) over
    (n, n) cut tensors — the anchored partial-embedding vector of a
    |cut| = 2 plan (``keep`` picks which cut axis survives).  Same
    padding, masking, and chunked f32/f64 exactness story as
    ``cutjoin_reduce``; ``cutjoin_exact_block`` certifies the same chunk
    size for both.
    """
    interpret = _auto_interpret(interpret)
    tile = _tile(interpret, tile)
    _count_call("cutjoin_reduce_keep", 2, interpret)
    return _mr.prod_reduce_keep(factors, keep=keep, distinct=distinct,
                                block=block or tile, tile=tile,
                                interpret=interpret, offsets=offsets)


def cutjoin_reduce3(factors, axes, *, n, distinct=True, block=None,
                    tile=None, interpret=None, offsets=None) -> float:
    """The |cut| = 3 decomposition join Σ_{e_c pairwise distinct} Π_i
    M_i(e_c) as a tiled tri-join kernel.

    ``factors[i]`` spans only the cut axes ``axes[i]`` (a sorted subset
    of (0, 1, 2)): (n,) vectors, (n, n) pair tensors, or full (n, n, n)
    tensors.  Axis-subset factors broadcast per tile inside the kernel
    — they are never expanded to 3-D — and the pairwise-distinct mask
    is derived from tile iotas, so nothing O(n³) is materialised beyond
    whatever genuinely 3-D factors the caller already holds.  ``block``
    bounds the cells per f32 partial; take it from
    ``cutjoin_exact_block`` so integer counts stay exact.
    """
    interpret = _auto_interpret(interpret)
    tile = _tile(interpret, tile)
    _count_call("cutjoin_reduce3", 3, interpret)
    return _mr.tri_reduce(factors, axes, n=n, distinct=distinct,
                          block=block or tile, tile=tile,
                          interpret=interpret, offsets=offsets)


def cutjoin_reduce3_keep(factors, axes, *, keep, n, distinct=True,
                         block=None, tile=None, interpret=None,
                         offsets=None) -> np.ndarray:
    """Keep-axis |cut| = 3 join: out[w] = Σ over the two non-kept cut
    axes (pairwise-distinct triples only) of Π_i M_i — the anchored
    partial-embedding vector of a 3-cut plan.  Same axis-subset
    broadcasting, in-kernel mask, and chunked f32/f64 exactness story
    as ``cutjoin_reduce3``."""
    interpret = _auto_interpret(interpret)
    tile = _tile(interpret, tile)
    _count_call("cutjoin_reduce3_keep", 3, interpret)
    return _mr.tri_reduce_keep(factors, axes, keep=keep, n=n,
                               distinct=distinct, block=block or tile,
                               tile=tile, interpret=interpret,
                               offsets=offsets)


def runtime_block(block: int, *, interpret=None) -> int:
    """Clamp a statically certified ``exact_block`` chunk to the running
    backend's tile (the same 1024-interpret / 128-TPU cap
    ``cutjoin_exact_block`` applies).  Certificates are computed against
    the interpret-mode maximum (``analysis.verify.precertify``); a
    smaller chunk is always at least as exact, so clamping preserves the
    guarantee."""
    return min(int(block), _tile(_auto_interpret(interpret)))


def cutjoin_exact_block(factors, *, interpret=None, maxes=None):
    """Chunk size for which ``cutjoin_reduce`` / ``cutjoin_reduce3`` is
    exact on the given integer-valued factors, or None when no f32
    chunking can guarantee it (callers should use an f64 path).
    ``maxes`` passes cached per-factor max magnitudes so serving plans
    skip the device→host factor scan (see ``matreduce.exact_block``).
    """
    cap = _tile(_auto_interpret(interpret))
    block = _mr.exact_block(factors, max_block=cap, maxes=maxes)
    obs.counter("kernel.exact_block",
                outcome="granted" if block is not None else "refused")
    return block


def common_neighbors(adj_bool: np.ndarray, edges: np.ndarray, *,
                     interpret=None):
    """Per-edge common-neighbour counts via the bitset kernel."""
    packed = _bitset.pack_bitsets(adj_bool)
    rows_a = jnp.asarray(packed[edges[:, 0]])
    rows_b = jnp.asarray(packed[edges[:, 1]])
    E = rows_a.shape[0]
    block = min(256, max(8, E))
    pad = (-E) % block
    if pad:
        z = jnp.zeros((pad, rows_a.shape[1]), rows_a.dtype)
        rows_a = jnp.concatenate([rows_a, z])
        rows_b = jnp.concatenate([rows_b, z])
    out = _bitset.bitset_intersect(rows_a, rows_b, block=block,
                                   interpret=_auto_interpret(interpret))
    return out[:E]


def flash_attention(q, k, v, *, causal=True, bq=128, bk=128,
                    interpret=None):
    """(B, S, H, D) attention via the Pallas kernel."""
    B, Sq, H, D = q.shape
    Skv = k.shape[1]
    interpret = _auto_interpret(interpret)
    qf = q.transpose(0, 2, 1, 3).reshape(B * H, Sq, D)
    kf = k.transpose(0, 2, 1, 3).reshape(B * H, Skv, D)
    vf = v.transpose(0, 2, 1, 3).reshape(B * H, Skv, D)
    out = _fa.flash_attention(qf, kf, vf, causal=causal,
                              bq=min(bq, Sq), bk=min(bk, Skv),
                              interpret=interpret)
    return out.reshape(B, H, Sq, D).transpose(0, 2, 1, 3)
