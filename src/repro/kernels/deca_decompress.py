"""Pallas TPU kernel: DECA tile decompression (paper Fig. 11).

Maps the DECA PE onto a TPU core:

  DECA stage                      TPU kernel equivalent
  ----------                      ---------------------
  Loader (LDQ + prefetcher)       Pallas grid pipeline: HBM->VMEM DMA of the
                                  next block overlaps compute (double-buffered
                                  automatically — the TEPL/double-buffer analog)
  Dequantization (LUT array)      ALU decode on the VPU: E5M2/E2M1 -> BF16 via
                                  integer shift/mask/select (no per-lane LUT
                                  SRAM on TPU; see DESIGN.md §2)
  Expansion (prefix-sum + XBAR)   popcount of the masked-below bitmask + a
                                  select per packed slot
  Scaling (BF16 multipliers)      per-group broadcast multiply
  TOut registers                  VMEM output block

Block geometry: a program decompresses a (block_k, block_n) dense output
region from (block_k/G) groups. ``block_n`` should be a multiple of 128
(lanes) and ``block_k`` a multiple of the group size (32) on real hardware.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.codecs import get_codec
from repro.core.compression import CompressedTensor
from repro.core.formats import CompressionSpec
from repro.kernels.autotune import select_block
from repro.kernels.mode import resolve_interpret


# ---------------------------------------------------------------------------
# in-kernel decode: the registered codec's jnp decode (pure VPU ops —
# shifts, masks, selects — the same implementation kernels/ref.py uses)
# ---------------------------------------------------------------------------

def decode_values(codes: jax.Array, spec: CompressionSpec) -> jax.Array:
    """(ng, packed, n) uint8 block -> (ng, k_cap, n) f32 values (in-kernel)."""
    return get_codec(spec.quant).decode_values(codes)


def decode_scales(scales: jax.Array, spec: CompressionSpec) -> jax.Array:
    return get_codec(spec.quant).decode_scales(scales)


def decompress_block(
    codes: jax.Array,
    mask: Optional[jax.Array],
    scales: Optional[jax.Array],
    spec: CompressionSpec,
) -> jax.Array:
    """Decompress one VMEM block -> (ng*G, n) f32 dense tile.

    This is the full DECA pipeline body; shared by the standalone and the
    fused GeMM kernels, and format-agnostic: the codec registry supplies
    the dequantization, so a newly registered format runs here unchanged.
    """
    codec = get_codec(spec.quant)
    vals = codec.decode_values(codes)  # (ng, k_cap, n)
    if scales is not None:
        vals = vals * codec.decode_scales(scales)[:, None, :]
    ng, _, n = vals.shape
    if mask is None:
        return vals.reshape(ng * spec.group, n)
    slot = slots(mask, spec)
    # crossbar analog: a select per packed slot (a per-column gather along
    # sublanes, which Mosaic cannot lower)
    dense = jnp.zeros((ng, spec.group, n), vals.dtype)
    for j in range(spec.k_cap):
        dense = jnp.where(slot == j, vals[:, j : j + 1, :], dense)
    return dense.reshape(ng * spec.group, n)


def slots(mask: jax.Array, spec: CompressionSpec) -> jax.Array:
    """POPCNT analog: (ng, n) bitmask -> (ng, G, n) int32 slot of each
    element. Element i of a group is the slot-th kept value, where slot =
    popcount(mask & (2^i - 1)); unkept elements get slot k_cap."""
    m = mask.astype(jnp.uint32)[:, None, :]  # (ng, 1, n)
    i = jax.lax.broadcasted_iota(jnp.uint32, (1, spec.group, 1), 1)
    kept = ((m >> i) & 1) == 1
    return jnp.where(
        kept,
        jax.lax.population_count(m & ((jnp.uint32(1) << i) - 1)),
        spec.k_cap,
    ).astype(jnp.int32)


def expand_slots(planes, mask: jax.Array, spec: CompressionSpec) -> jax.Array:
    """The fused GEMV's bitmask expansion: packed slot values -> (ng, G, n)
    dense groups.

    `planes` holds the slots in P interleaved planes, slot s at row s // P
    of plane s % P (P = 1: `decode_values` order; P = 2: a nibble codec's
    `decode_planes`), so nibble codes expand without being interleaved.
    A group keeps exactly k_cap elements (core/formats.py), so element i
    holds a slot in [i - (G - k_cap), i]: each 8-row block selects among
    those slots only, a quarter fewer selects than `decompress_block`'s
    loop at 50% density."""
    slot = slots(mask, spec)
    ng, n = mask.shape
    p = len(planes)
    g, kc = spec.group, spec.k_cap
    blocks = []
    for r0 in range(0, g, 8):
        rows = slice(r0, min(r0 + 8, g))
        block = jnp.zeros((ng, rows.stop - r0, n), planes[0].dtype)
        for j in range(max(0, r0 - (g - kc)), min(rows.stop, kc)):
            r = j // p
            block = jnp.where(
                slot[:, rows] == j, planes[j % p][:, r : r + 1, :], block
            )
        blocks.append(block)
    return jnp.concatenate(blocks, axis=1)


# ---------------------------------------------------------------------------
# standalone decompression kernel
# ---------------------------------------------------------------------------

def _decompress_kernel(spec, out_dtype, *refs):
    if spec.is_sparse and spec.has_scale:
        codes_ref, mask_ref, scales_ref, out_ref = refs
        mask, scales = mask_ref[...], scales_ref[...]
    elif spec.is_sparse:
        codes_ref, mask_ref, out_ref = refs
        mask, scales = mask_ref[...], None
    elif spec.has_scale:
        codes_ref, scales_ref, out_ref = refs
        mask, scales = None, scales_ref[...]
    else:
        codes_ref, out_ref = refs
        mask, scales = None, None
    dense = decompress_block(codes_ref[...], mask, scales, spec)
    out_ref[...] = dense.astype(out_dtype)


@functools.partial(
    jax.jit, static_argnames=("block_k", "block_n", "out_dtype", "interpret")
)
def decompress_pallas(
    ct: CompressedTensor,
    *,
    block_k: int = 512,
    block_n: int = 256,
    out_dtype=jnp.bfloat16,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Decompress a CompressedTensor to a dense (K, N) array via Pallas."""
    spec = ct.spec
    K, N = ct.shape
    G = spec.group
    if K % G:
        # compression produces whole groups only; a non-group K cannot be
        # tiled into whole-group blocks at all
        raise ValueError(
            f"decompress_pallas: K={K} is not a multiple of the compression "
            f"group {G} (K % G == {K % G}); CompressedTensor shape is invalid"
        )
    # largest-divisor selection (autotune.py): O(sqrt) at trace time and
    # warns on non-lane-aligned block_n instead of silently shrinking to it
    block_k = select_block(K, block_k, multiple=G, minimum=G, name="block_k")
    block_n = select_block(N, block_n, warn_lanes=True, name="block_n")
    gb = block_k // G  # groups per block
    ck = ct.codes.shape[1]  # packed bytes per group

    grid = (K // block_k, N // block_n)
    in_specs = [
        pl.BlockSpec((gb, ck, block_n), lambda i, j: (i, 0, j)),
    ]
    operands = [ct.codes]
    if spec.is_sparse:
        in_specs.append(pl.BlockSpec((gb, block_n), lambda i, j: (i, j)))
        operands.append(ct.mask)
    if spec.has_scale:
        in_specs.append(pl.BlockSpec((gb, block_n), lambda i, j: (i, j)))
        operands.append(ct.scales)

    return pl.pallas_call(
        functools.partial(_decompress_kernel, spec, out_dtype),
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((block_k, block_n), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((K, N), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
        ),
        interpret=resolve_interpret(interpret),
    )(*operands)
