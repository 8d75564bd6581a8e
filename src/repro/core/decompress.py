"""Online decompression API: compressed-weight model serving.

`compress_tree(params, spec)` walks a model's param pytree and replaces
every eligible FC weight with a `CompressedTensor` (offline step, numpy).
`mm(x, w)` is the matmul used by all model layers: for a plain array it is
`x @ w`; for a CompressedTensor it routes through the DECA decompress-GeMM
(kernels/ops.py) — dequantize + de-sparsify + scale fused with the matrix
multiply, exactly the paper's accelerator on the serving critical path.

Stacked weights (scan-over-layers (L, K, N) or MoE (E, K, N)) are compressed
per 2D slice with stacked storage; lax.scan / indexing slices the
CompressedTensor pytree back to 2D slices naturally.
"""
from __future__ import annotations

import contextlib
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.compression import CompressedTensor, compress
from repro.core.formats import CompressionSpec
from repro.kernels import ops

# 'auto' (ops.decompress_gemm picks from the backend and M; attention runs
# the ref walk) | 'ref' (portable XLA) | 'pallas' (TPU kernels; interpreted
# on CPU)
_IMPL = "auto"


@contextlib.contextmanager
def use_impl(impl: str):
    global _IMPL
    prev, _IMPL = _IMPL, impl
    try:
        yield
    finally:
        _IMPL = prev


def current_impl() -> str:
    """The active kernel impl ('auto' | 'ref' | 'pallas') — also
    consulted by the fused paged-attention routing in models/layers.py
    (which runs 'auto' as 'ref'), so `use_impl` switches every DECA kernel
    on the serving path at once."""
    return _IMPL


def mm(x: jax.Array, w: Any) -> jax.Array:
    """x (..., K) @ w (K, N) with transparent DECA decompression."""
    if isinstance(w, CompressedTensor):
        return ops.decompress_gemm(x, w, impl=_IMPL, out_dtype=x.dtype)
    return x @ w


# ---------------------------------------------------------------------------
# offline tree compression
# ---------------------------------------------------------------------------

# leaves eligible for weight compression: all FC weights; embeddings stay
# dense (gather, not GeMM — paper §3.1 compresses only GeMM weights), and
# norms/biases are not GeMM operands at all
_SKIP = ("embed", "pos_embed", "router", "conv_w", "a_log", "a_param", "norm")


def _eligible(name: str, arr: np.ndarray, spec: CompressionSpec) -> bool:
    if any(s in name for s in _SKIP):
        return False
    if arr.ndim < 2 or arr.size < 4096:
        return False
    k = arr.shape[-2]
    return k % spec.group == 0


def _compress_leaf(arr: np.ndarray, spec: CompressionSpec) -> CompressedTensor:
    if arr.ndim == 2:
        return compress(arr, spec)
    lead = arr.shape[:-2]
    flat = arr.reshape((-1,) + arr.shape[-2:])
    cts = [compress(np.asarray(flat[i], np.float32), spec) for i in range(flat.shape[0])]
    codes = np.stack([c.codes for c in cts]).reshape(lead + cts[0].codes.shape)
    mask = (
        np.stack([c.mask for c in cts]).reshape(lead + cts[0].mask.shape)
        if cts[0].mask is not None
        else None
    )
    scales = (
        np.stack([c.scales for c in cts]).reshape(lead + cts[0].scales.shape)
        if cts[0].scales is not None
        else None
    )
    return CompressedTensor(
        codes=codes, mask=mask, scales=scales, spec=spec, shape=cts[0].shape
    )


def compress_tree(params: Any, spec: CompressionSpec) -> Any:
    """Offline: compress every eligible FC weight leaf in a param pytree."""

    def one(path, leaf):
        name = "/".join(p.key if hasattr(p, "key") else str(p) for p in path)
        arr = np.asarray(jax.device_get(leaf), dtype=np.float32)
        if not _eligible(name, arr, spec):
            return leaf
        return _compress_leaf(arr, spec)

    return jax.tree_util.tree_map_with_path(one, params)


def _decompress_leaf(ct: CompressedTensor) -> np.ndarray:
    """Dense f32 weights back out of a (possibly stacked) CompressedTensor.

    Stacked leaves store per-2D-slice planes under lead dims (see
    `_compress_leaf`); 2D codes are always (ng, packed_k, N), so the lead
    dims are whatever `codes` carries beyond rank 3."""
    from repro.kernels import ref

    codes = np.asarray(ct.codes)
    lead = codes.shape[: codes.ndim - 3]
    if not lead:
        return np.asarray(ref.decompress(ct, out_dtype=jnp.float32))

    def plane(a):
        if a is None:
            return None
        a = np.asarray(a)
        return a.reshape((-1,) + a.shape[len(lead):])

    fc, fm, fs = plane(ct.codes), plane(ct.mask), plane(ct.scales)
    slices = [
        np.asarray(ref.decompress(
            CompressedTensor(
                codes=fc[i],
                mask=None if fm is None else fm[i],
                scales=None if fs is None else fs[i],
                spec=ct.spec, shape=ct.shape,
            ),
            out_dtype=jnp.float32,
        ))
        for i in range(fc.shape[0])
    ]
    return np.stack(slices).reshape(lead + ct.shape)


def make_draft_tree(params: Any, draft_spec: CompressionSpec) -> Any:
    """Self-speculation draft weights: re-encode the weight tree at a
    cheaper codec — no second checkpoint, no training (DESIGN.md §16).

    Every `CompressedTensor` leaf is decompressed (so the draft quantizes
    the *same* numbers the target serves, target-codec error included) and
    re-compressed at `draft_spec`; eligible dense FC leaves compress
    directly. Everything else — embeddings, norms, ineligible weights — is
    shared with the target tree by reference: the draft model costs only
    its re-encoded FC planes, typically ~4x fewer bytes than bf16 at a
    4-bit draft codec, which is the whole point (draft decode is
    weight-bandwidth bound)."""

    def one(path, leaf):
        if isinstance(leaf, CompressedTensor):
            if leaf.shape[-2] % draft_spec.group:
                return leaf  # draft group doesn't divide K: share the target
            return _compress_leaf(_decompress_leaf(leaf), draft_spec)
        name = "/".join(p.key if hasattr(p, "key") else str(p) for p in path)
        arr = np.asarray(jax.device_get(leaf), dtype=np.float32)
        if not _eligible(name, arr, draft_spec):
            return leaf
        return _compress_leaf(arr, draft_spec)

    return jax.tree_util.tree_map_with_path(
        one, params, is_leaf=lambda x: isinstance(x, CompressedTensor)
    )


def compressed_bytes(params: Any) -> int:
    """Total stored bytes of a (possibly partially) compressed tree."""
    total = 0
    for leaf in jax.tree_util.tree_leaves(
        params, is_leaf=lambda x: isinstance(x, CompressedTensor)
    ):
        if isinstance(leaf, CompressedTensor):
            total += leaf.nbytes
        else:
            total += np.asarray(leaf).nbytes
    return total
