"""Pallas TPU kernel: fused decompress + GeMM (DECA + TMUL cooperation).

The paper overlaps DECA's decompression with the core's AMX matmul through
double buffering and the TEPL out-of-order invocation (paper §5). On TPU the
same overlap is achieved *structurally*: this kernel decompresses a weight
block in VMEM with VPU ops and immediately feeds it to the MXU, while the
Pallas grid pipeline prefetches the next compressed block from HBM. The
decompressed tile never exists in HBM — the analog of the paper's
"+TOut Regs" integration (§9.3), where the core reads decompressed tiles
straight from the accelerator's output registers instead of via L2.

Grid = (M/bm, N/bn, K/bk), k innermost and marked "arbitrary" (the m/n axes
are "parallel"): partial sums live in a VMEM f32 scratch accumulator and the
output block is written exactly once at the last k step — the output ref is
never revisited across k, so its HBM traffic is one store per tile instead
of a load+store per k step.

Two grid shapes for the two serving regimes (DESIGN.md §12):
  decompress_gemm_pallas   prefill/GeMM regime — M tiles over MXU rows;
  decompress_gemv_pallas   decode/GeMV regime — M is a handful of
                           continuous-batching slots, kept whole; the grid
                           walks (N/bn, K/bk) and the kernel is MEM-bound
                           on the compressed weight stream.

Block geometry comes from `kernels.autotune`: largest-divisor selection
(no decrement-by-1 shrink loops) against roofline-mapped targets.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.compression import CompressedTensor
from repro.kernels.autotune import pick_blocks, select_block
from repro.kernels.deca_decompress import decompress_block
from repro.kernels.mode import resolve_interpret


def _unpack_refs(spec, refs):
    """(x, codes[, mask][, scales], out, acc) -> named operands."""
    if spec.is_sparse and spec.has_scale:
        x_ref, codes_ref, mask_ref, scales_ref, out_ref, acc_ref = refs
        mask, scales = mask_ref[...], scales_ref[...]
    elif spec.is_sparse:
        x_ref, codes_ref, mask_ref, out_ref, acc_ref = refs
        mask, scales = mask_ref[...], None
    elif spec.has_scale:
        x_ref, codes_ref, scales_ref, out_ref, acc_ref = refs
        mask, scales = None, scales_ref[...]
    else:
        x_ref, codes_ref, out_ref, acc_ref = refs
        mask, scales = None, None
    return x_ref, codes_ref, mask, scales, out_ref, acc_ref


def _gemm_kernel(spec, nk, k_axis, *refs):
    x_ref, codes_ref, mask, scales, out_ref, acc_ref = _unpack_refs(spec, refs)
    k = pl.program_id(k_axis)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # DECA stage: VPU decompression of the (bk, bn) weight block in VMEM.
    w = decompress_block(codes_ref[...], mask, scales, spec).astype(jnp.bfloat16)
    # TMUL stage: MXU matmul on the freshly decompressed tile, accumulated
    # in VMEM scratch (the "+TOut Regs" analog) — not in the output ref.
    acc_ref[...] += jnp.dot(
        x_ref[...].astype(jnp.bfloat16), w, preferred_element_type=jnp.float32
    )

    @pl.when(k == nk - 1)
    def _flush():
        out_ref[...] = acc_ref[...].astype(out_ref.dtype)


def _compressed_specs(spec, gb, ck, block_n, index_map_codes, index_map_gn):
    """BlockSpecs + operand order for the {codes, mask, scales} triplet."""
    in_specs = [pl.BlockSpec((gb, ck, block_n), index_map_codes)]
    if spec.is_sparse:
        in_specs.append(pl.BlockSpec((gb, block_n), index_map_gn))
    if spec.has_scale:
        in_specs.append(pl.BlockSpec((gb, block_n), index_map_gn))
    return in_specs


def _ct_operands(ct):
    ops = [ct.codes]
    if ct.spec.is_sparse:
        ops.append(ct.mask)
    if ct.spec.has_scale:
        ops.append(ct.scales)
    return ops


@functools.partial(
    jax.jit,
    static_argnames=("block_m", "block_n", "block_k", "out_dtype", "interpret"),
)
def decompress_gemm_pallas(
    x: jax.Array,
    ct: CompressedTensor,
    *,
    block_m: Optional[int] = None,
    block_n: Optional[int] = None,
    block_k: Optional[int] = None,
    out_dtype=jnp.float32,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """x (M, K) @ decompress(ct) (K, N) -> (M, N), decompression fused.

    Block targets default to the roofline-picked triple (autotune.py);
    explicit values are treated as targets and resolved to the largest
    divisor of the dimension (lane/group-aligned when possible)."""
    spec = ct.spec
    K, N = ct.shape
    M = x.shape[0]
    if x.shape[1] != K:
        raise ValueError(f"x K dim {x.shape[1]} != weight K {K}")
    G = spec.group
    if K % G:
        raise ValueError(
            f"decompress_gemm_pallas: K={K} is not a multiple of the "
            f"compression group {G} (K % G == {K % G}); CompressedTensor "
            "shape is invalid"
        )

    auto_m, auto_n, auto_k = pick_blocks(M, N, K, spec)
    block_m = select_block(M, block_m, name="block_m") if block_m else auto_m
    block_n = (
        select_block(N, block_n, warn_lanes=True, name="block_n")
        if block_n
        else auto_n
    )
    block_k = (
        select_block(K, block_k, multiple=G, minimum=G, name="block_k")
        if block_k
        else auto_k
    )
    gb = block_k // G
    ck = ct.codes.shape[1]
    nk = K // block_k

    grid = (M // block_m, pl.cdiv(N, block_n), nk)
    in_specs = [
        pl.BlockSpec((block_m, block_k), lambda i, j, k: (i, k)),
        *_compressed_specs(
            spec, gb, ck, block_n,
            lambda i, j, k: (k, 0, j), lambda i, j, k: (k, j),
        ),
    ]

    out = pl.pallas_call(
        functools.partial(_gemm_kernel, spec, nk, 2),
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), out_dtype),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=resolve_interpret(interpret),
    )(x, *_ct_operands(ct))
    return out


@functools.partial(
    jax.jit,
    static_argnames=("block_n", "block_k", "out_dtype", "interpret"),
)
def decompress_gemv_pallas(
    x: jax.Array,
    ct: CompressedTensor,
    *,
    block_n: Optional[int] = None,
    block_k: Optional[int] = None,
    out_dtype=jnp.float32,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Decode-shaped fused GeMV: x (M, K) @ W (K, N) with M kept whole.

    The decode step's M is the continuous-batching slot count (a few rows,
    far below the 8-sublane granularity), so tiling M buys nothing: the
    grid walks (N/bn, K/bk) with k innermost, the x row-block rides along
    every program, and the kernel streams the compressed weight exactly
    once — the MEM-bound GeMV regime of DESIGN.md §12. Accumulation stays
    in VMEM scratch; the (M, bn) output block stores once at the last k."""
    spec = ct.spec
    K, N = ct.shape
    M = x.shape[0]
    if x.shape[1] != K:
        raise ValueError(f"x K dim {x.shape[1]} != weight K {K}")
    G = spec.group
    if K % G:
        raise ValueError(
            f"decompress_gemv_pallas: K={K} not a multiple of group {G}"
        )

    _, auto_n, auto_k = pick_blocks(M, N, K, spec)
    block_n = (
        select_block(N, block_n, warn_lanes=True, name="block_n")
        if block_n
        else auto_n
    )
    block_k = (
        select_block(K, block_k, multiple=G, minimum=G, name="block_k")
        if block_k
        else auto_k
    )
    gb = block_k // G
    ck = ct.codes.shape[1]
    nk = K // block_k

    grid = (pl.cdiv(N, block_n), nk)
    in_specs = [
        pl.BlockSpec((M, block_k), lambda j, k: (0, k)),
        *_compressed_specs(
            spec, gb, ck, block_n,
            lambda j, k: (k, 0, j), lambda j, k: (k, j),
        ),
    ]

    out = pl.pallas_call(
        functools.partial(_gemm_kernel, spec, nk, 1),
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((M, block_n), lambda j, k: (0, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), out_dtype),
        scratch_shapes=[pltpu.VMEM((M, block_n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=resolve_interpret(interpret),
    )(x, *_ct_operands(ct))
    return out
