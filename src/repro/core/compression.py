"""Offline model compression (paper Fig. 1, left side).

``compress(w, spec)`` sparsifies (per-group top-|w|), then hands the packed
nonzero values to the format's codec (`core/codecs.py`) for quantization and
packing into the DECA storage triplet {codes, mask, scales}. Runs in numpy
on the host — compression is offline in the paper; only *decompression* is
on the inference critical path.

All format-specific code (bf8/mxfp4/int8/int4/nf4/bf16 number handling)
lives on the registered `Codec` objects; this module owns only the
format-agnostic sparsification and the `CompressedTensor` container. The
individual quantizers are re-exported from the registry for back-compat.
"""
from __future__ import annotations

import dataclasses
import os
from concurrent.futures import ThreadPoolExecutor
from typing import Optional, Tuple

import jax
import numpy as np

from repro.core.codecs import (  # noqa: F401  (back-compat re-exports)
    FP4_GRID,
    NF4_LUT,
    dequantize_bf8,
    dequantize_fp4,
    get_codec,
    quantize_bf8,
    quantize_fp4,
    _bf16_bits_to_f32,
    _f32_to_bf16_bits,
)
from repro.core.formats import CompressionSpec


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class CompressedTensor:
    """Packed compressed weight of logical shape (K, N).

    codes : (ng, k_cap*bits/8, N) uint8   packed quantized nonzeros
            (bf16 codes are stored as 2 bytes little-endian)
    mask  : (ng, N) uint32 or None        per-group bitmask (bit i = row g*G+i)
    scales: (ng, N) uint8|uint16 or None  E8M0 (mxfp4) / bf16-bits (int8/4, nf4)
    """

    codes: jax.Array
    mask: Optional[jax.Array]
    scales: Optional[jax.Array]
    spec: CompressionSpec
    shape: Tuple[int, int]  # logical (K, N)

    def tree_flatten(self):
        return (self.codes, self.mask, self.scales), (self.spec, self.shape)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, *aux)

    @property
    def nbytes(self) -> int:
        total = self.codes.size  # uint8
        if self.mask is not None:
            total += self.mask.size * 4
        if self.scales is not None:
            total += self.scales.size * self.scales.dtype.itemsize
        return int(total)


# ---------------------------------------------------------------------------
# compression pipeline
# ---------------------------------------------------------------------------

def _sparsify_groups(wg: np.ndarray, k_cap: int) -> Tuple[np.ndarray, np.ndarray]:
    """Per-group top-|w| pruning.

    wg: (ng, G, N). Returns (values (ng, k_cap, N) packed-dense along axis 1,
    mask (ng, N) uint32 with bit i set iff element i is kept).
    """
    ng, G, N = wg.shape
    order = np.argsort(-np.abs(wg), axis=1, kind="stable")  # (ng, G, N)
    keep_rank = np.empty_like(order)
    np.put_along_axis(keep_rank, order, np.arange(G)[None, :, None], axis=1)
    keep = keep_rank < k_cap  # (ng, G, N) bool
    bits = keep.astype(np.uint32) << np.arange(G, dtype=np.uint32)[None, :, None]
    mask = bits.sum(axis=1, dtype=np.uint32)  # (ng, N)
    # pack kept values contiguously (in original order), pad with 0
    vals = np.zeros((ng, k_cap, N), dtype=wg.dtype)
    pos = np.cumsum(keep, axis=1) - 1  # destination slot for kept elems
    gi, _, ni = np.meshgrid(np.arange(ng), np.arange(G), np.arange(N), indexing="ij")
    sel = keep
    vals[gi[sel], pos[sel], ni[sel]] = wg[sel]
    return vals, mask


# below this many elements a matrix compresses in the calling thread
_PARALLEL_MIN = 1 << 22


def compress(w: np.ndarray, spec: CompressionSpec) -> CompressedTensor:
    """Compress a 2D weight (K, N) along K. K must be a multiple of group.

    Every stage is column-local (groups run along K), so a large matrix is
    compressed as column slabs on a thread pool (numpy releases the GIL in
    its array loops) and the planes are joined: bit-identical to one pass.
    """
    w = np.asarray(w, dtype=np.float32)
    if w.ndim != 2:
        raise ValueError(f"compress expects 2D weights, got {w.shape}")
    K, N = w.shape
    G = spec.group
    if K % G != 0:
        raise ValueError(f"K={K} not a multiple of group={G}")
    slabs = min(os.cpu_count() or 1, N // 128) if w.size >= _PARALLEL_MIN else 1
    if slabs <= 1:
        return _compress_cols(w, spec)
    cuts = np.linspace(0, N, slabs + 1).astype(int)
    with ThreadPoolExecutor(slabs) as pool:
        parts = list(pool.map(
            lambda a, b: _compress_cols(w[:, a:b], spec), cuts[:-1], cuts[1:]
        ))

    def join(planes):
        return None if planes[0] is None else np.concatenate(planes, axis=-1)

    return CompressedTensor(
        codes=join([p.codes for p in parts]),
        mask=join([p.mask for p in parts]),
        scales=join([p.scales for p in parts]),
        spec=spec,
        shape=(K, N),
    )


def _compress_cols(w: np.ndarray, spec: CompressionSpec) -> CompressedTensor:
    K, N = w.shape
    G = spec.group
    ng = K // G
    wg = w.reshape(ng, G, N)

    mask = None
    if spec.is_sparse:
        vals, mask = _sparsify_groups(wg, spec.k_cap)  # (ng, k_cap, N)
    else:
        vals = wg  # k_cap == G

    codes, scales = get_codec(spec.quant).encode(vals)

    return CompressedTensor(
        codes=np.ascontiguousarray(codes),
        mask=mask,
        scales=scales,
        spec=spec,
        shape=(K, N),
    )
