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
                           walks (N/bn, K/bk), decodes each block in its
                           stored layout in sub-blocks, and is set by the
                           VPU's decode of the compressed weight stream.

Block geometry comes from `kernels.autotune`: largest-divisor selection
(no decrement-by-1 shrink loops) against roofline-mapped targets for the
GeMM, `pick_gemv_blocks` for the GeMV.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.compression import CompressedTensor
from repro.kernels.autotune import (
    gemv_vmem_bytes, pick_blocks, pick_gemv_blocks, select_block,
)
from repro.kernels.deca_decompress import decompress_block, expand_slots
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


def _gemm_kernel(spec, nk, *refs):
    x_ref, codes_ref, mask, scales, out_ref, acc_ref = _unpack_refs(spec, refs)
    k = pl.program_id(2)

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
        functools.partial(_gemm_kernel, spec, nk),
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


def _gemv_kernel(spec, nk, sg, sub_n, *refs):
    """One (block_k, block_n) step of the fused decode GEMV.

    The planes arrive as stored: codes (gb*ck, bn) uint8, mask and scales
    (gb, bn). A `fori_loop` decodes `sg` groups at a time, in `sub_n`-lane
    tiles, and contracts each decoded tile on the MXU straight away; x
    arrives split into the columns each value plane multiplies (see
    `decompress_gemv_pallas`), so nibble codes are never interleaved."""
    codec = spec.codec
    refs = list(refs)
    x_ref, codes_ref = refs.pop(0), refs.pop(0)
    mask_ref = refs.pop(0) if spec.is_sparse else None
    scales_ref = refs.pop(0) if spec.has_scale else None
    out_ref = refs.pop(0)
    sc_ref = refs.pop(0) if spec.has_scale else None
    acc_ref = refs.pop(0) if nk > 1 else None

    nx, nsub, m, _ = x_ref.shape
    bn = out_ref.shape[1]
    ck = codes_ref.shape[0] // (nsub * sg)
    rows = sg * ck
    nt = bn // sub_n
    if sc_ref is not None:
        # scales decode once a step, into f32 rows any sub-block can slice
        sc_ref[...] = codec.decode_scales(scales_ref[...])

    def sub_block(i, acc):
        r0 = pl.multiple_of(i * rows, rows)
        g0 = pl.multiple_of(i * sg, sg)
        xs = [x_ref[p, i].astype(jnp.bfloat16) for p in range(nx)]
        out = []
        for t in range(nt):
            lanes = pl.ds(t * sub_n, sub_n)
            codes = codes_ref[pl.ds(r0, rows), lanes].astype(jnp.int32)
            planes = codec.decode_planes(codes.reshape(sg, ck, sub_n))
            if sc_ref is not None:
                s = sc_ref[pl.ds(g0, sg), lanes][:, None, :]
                planes = [v * s for v in planes]
            if mask_ref is not None:
                planes = [expand_slots(planes, mask_ref[pl.ds(g0, sg), lanes], spec)]
            a = acc[t]
            for xp, v in zip(xs, planes):
                w = v.reshape(-1, sub_n).astype(jnp.bfloat16)
                a = a + jnp.dot(xp, w, preferred_element_type=jnp.float32)
            out.append(a)
        return tuple(out)

    zero = tuple(jnp.zeros((m, sub_n), jnp.float32) for _ in range(nt))
    acc = jax.lax.fori_loop(0, nsub, sub_block, zero)
    acc = acc[0] if nt == 1 else jnp.concatenate(acc, axis=1)
    if acc_ref is None:
        out_ref[...] = acc.astype(out_ref.dtype)
        return
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = acc

    @pl.when(k > 0)
    def _add():
        acc_ref[...] += acc

    @pl.when(k == nk - 1)
    def _flush():
        out_ref[...] = acc_ref[...].astype(out_ref.dtype)


def gemv_x_planes(x: jax.Array, spec, sub_k: int) -> jax.Array:
    """x (M, K) -> (P, K / sub_k, M, sub_k / P): for each value plane p of
    a dense nibble codec (P = 2) the K columns 2j + p its stored rows
    multiply, cut into one slab per decode sub-block; P = 1 for every
    other codec and for sparse ones, whose expansion restores K order."""
    m, k = x.shape
    p = 1 if spec.is_sparse else spec.codec.n_planes
    xb = x.astype(jnp.bfloat16).reshape(m, k // sub_k, sub_k // p, p)
    return xb.transpose(3, 1, 0, 2)


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
    """Decode-shaped fused GEMV: x (M, K) @ W (K, N) with M kept whole.

    The decode step's M is the continuous-batching slot count, so the grid
    walks (N/bn, K/bk) with k innermost and streams each compressed plane
    byte from HBM into VMEM once. Each step decodes its block in the
    layout it is stored in, one sub-block at a time, and contracts it on
    the MXU; no decoded tile reaches HBM. Partial sums over K stay in an
    f32 VMEM accumulator; the (M, bn) output block stores once.

    The sum over K runs in blocks, so the result differs from
    `kernels.ref.decompress_gemm`'s one dot by f32 rounding only (the
    products are exact; tests/test_decode_chunk.py argues the bound).
    Geometry defaults to `autotune.pick_gemv_blocks`, whose sub-blocks an
    explicit block_k must hold whole; an explicit block_n is decoded in
    one lane tile."""
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
    auto_n, auto_k, sg, sub_n = pick_gemv_blocks(M, N, K, spec)
    if block_n not in (None, auto_n):
        sub_n = block_n
    block_n = block_n or auto_n
    block_k = block_k or auto_k
    if K % block_k or block_k % (sg * G):
        raise ValueError(
            f"GEMV block_k={block_k} does not tile K={K} in sub-blocks "
            f"of {sg * G} rows"
        )
    gb = block_k // G
    ck = ct.codes.shape[1]
    nk = K // block_k
    xp = gemv_x_planes(x, spec, sg * G)
    nx, _, _, r = xp.shape

    in_specs = [
        pl.BlockSpec((nx, gb // sg, M, r), lambda j, k: (0, k, 0, 0)),
        pl.BlockSpec((gb * ck, block_n), lambda j, k: (k, j)),
    ]
    operands = [xp, ct.codes.reshape(-1, N)]
    if spec.is_sparse:
        in_specs.append(pl.BlockSpec((gb, block_n), lambda j, k: (k, j)))
        operands.append(ct.mask)
    scratch = []
    if spec.has_scale:
        in_specs.append(pl.BlockSpec((gb, block_n), lambda j, k: (k, j)))
        operands.append(ct.scales)
        scratch.append(pltpu.VMEM((gb, block_n), jnp.float32))
    if nk > 1:
        scratch.append(pltpu.VMEM((M, block_n), jnp.float32))
    vmem = gemv_vmem_bytes(M, block_n, block_k, sg, sub_n, spec)

    return pl.pallas_call(
        functools.partial(_gemv_kernel, spec, nk, sg, sub_n),
        grid=(pl.cdiv(N, block_n), nk),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((M, block_n), lambda j, k: (0, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), out_dtype),
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=max(32 << 20, 2 * vmem),
        ),
        interpret=resolve_interpret(interpret),
    )(*operands)
