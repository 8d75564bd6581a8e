"""Block-geometry selection for the DECA Pallas kernels, grounded on the
§2 roofline mapping (DESIGN.md §2/§12/§13).

Three layers:

  select_block(n, target, multiple)
      Largest divisor of `n` that is <= `target` (and a multiple of
      `multiple` when one exists). Replaces the old `while n % b: b -= 1`
      shrink loops, which were O(n) at trace time and silently produced
      non-lane-aligned blocks for odd n; divisor enumeration is O(sqrt n)
      and warns when the result falls below the 128-lane width.

  pick_blocks(m, n, k, spec)
      Roofline-driven (block_m, block_n, block_k) for the fused
      decompress-GeMM. The Roof-Surface terms (core/roofsurface.py) say
      what each dimension buys:
        * block_n rides the VPU lanes (128) and MXU columns — the VEC term
          `VOS * AI_XV` degrades by block_n/128 when under-filled;
        * block_k amortizes the per-block f32 accumulator traffic and must
          hold whole compression groups (G) so the bitmask prefix-sum stays
          block-local;
        * block_m fills MXU rows — irrelevant in the decode GeMV regime
          (M = a few slots), where the kernel is MEM-bound on the
          compressed-weight stream and block_m is simply M.
      The block triple is shrunk (k first, then n — k only costs
      accumulator reuse, n costs lanes) until the VMEM working set fits the
      budget (double-buffered inputs + dense tile + f32 scratch).

  pick_page_block(mb, block_size, hkv, dh, quant)
      Pages per grid step of the fused paged-attention page walk
      (kernels/paged_attention.py and the ref while-loop in kernels/ref.py,
      DESIGN.md §13). Larger page blocks amortize the online-softmax
      rescale and the per-step loop machinery; the cap is the VMEM working
      set (double-buffered K/V codes + scale planes + position plane for
      the block, plus the query and f32 accumulator). Always a divisor of
      `mb`, and at most mb // 2 when mb splits at all — a single whole-walk
      block would re-materialize the gathered KV view the fused path
      exists to avoid.
"""
from __future__ import annotations

import math
import warnings
from typing import Optional, Tuple

from repro.core.formats import CompressionSpec

LANES = 128          # TPU vector lane width; MXU column count
SUBLANES = 8         # f32/bf16 sublane count; MXU row granularity
VMEM_BUDGET = 8 * 1024 * 1024  # half of the ~16 MB/core VMEM, headroom left


def divisors(n: int):
    """All divisors of n, ascending (O(sqrt n))."""
    small, large = [], []
    for d in range(1, int(math.isqrt(n)) + 1):
        if n % d == 0:
            small.append(d)
            if d != n // d:
                large.append(n // d)
    return small + large[::-1]


def select_block(
    n: int,
    target: int,
    *,
    multiple: int = 1,
    minimum: int = 1,
    warn_lanes: bool = False,
    name: str = "block",
) -> int:
    """Largest divisor of `n` <= `target`, preferring multiples of
    `multiple`. `minimum` raises a too-small target first — block_k callers
    pass the compression group G so an undersized explicit block still
    holds whole groups (the old `max(G, ...)` clamp). Falls back to the
    largest plain divisor <= target (>= 1 by construction). With
    `warn_lanes`, warns when the choice is not lane-aligned (a multiple of
    128) although the dimension could have supported one — the silent
    failure mode of the old decrement-by-1 shrink loops on odd n; dims
    below 128 have no aligned option and stay silent."""
    if n <= 0:
        raise ValueError(f"{name}: dimension must be positive, got {n}")
    target = max(1, min(max(target, minimum), n))
    best, best_aligned = 1, 0
    for d in divisors(n):
        if d > target:
            break
        best = d
        if d % multiple == 0:
            best_aligned = d
    out = best_aligned if best_aligned else best
    if warn_lanes and out % LANES and n >= LANES:
        warnings.warn(
            f"{name}={out} (dim {n}, target {target}) is not a multiple of "
            f"the 128-lane width; expect padding waste on real TPU",
            stacklevel=2,
        )
    return out


def _gemm_vmem_bytes(
    bm: int, bn: int, bk: int, spec: CompressionSpec, x_bytes: int = 4
) -> int:
    """VMEM working set of one fused-GeMM program instance.

    Double-buffered streamed inputs (x tile + codes/mask/scales block), one
    dense (bk, bn) f32 tile from the decompressor, the f32 scratch
    accumulator, and the output block."""
    gb = max(1, bk // spec.group)
    codes = gb * math.ceil(spec.k_cap * spec.bits / 8) * bn
    mask = gb * bn * 4 if spec.is_sparse else 0
    scales = gb * bn * 2 if spec.has_scale else 0
    stream = (bm * bk * x_bytes) + codes + mask + scales
    dense_tile = bk * bn * 4          # f32 values before the bf16 cast
    acc = bm * bn * 4                 # f32 scratch accumulator
    out = bm * bn * 4
    return 2 * stream + dense_tile + acc + out


def pick_blocks(
    m: int,
    n: int,
    k: int,
    spec: CompressionSpec,
    *,
    vmem_budget: int = VMEM_BUDGET,
    target_m: int = 128,
    target_n: int = 256,
    target_k: int = 512,
) -> Tuple[int, int, int]:
    """Roofline-mapped (block_m, block_n, block_k) for decompress-GeMM.

    Decode regime (m < 8 sublanes): the kernel is MEM-bound on the
    compressed stream — block_m is all of M, block_n gets the larger
    lane-aligned target so each fetched group feeds wide VPU decompression.
    Prefill/GeMM regime: classic MXU tiling with 128-row blocks.
    Shrinks k (accumulator reuse) before n (lane fill) until the working
    set fits the VMEM budget."""
    if m < SUBLANES:
        bm, tn = m, max(target_n, 2 * LANES)
    else:
        bm, tn = select_block(m, target_m, multiple=SUBLANES, name="block_m"), target_n
    bn = lane_block(n, tn)
    bk = select_block(k, target_k, multiple=spec.group, name="block_k")
    while _gemm_vmem_bytes(bm, bn, bk, spec) > vmem_budget:
        if bk > spec.group:
            bk = select_block(k, bk // 2, multiple=spec.group, name="block_k")
        elif bn > 1:
            bn = lane_block(n, bn // 2)
        else:  # pragma: no cover - tiny shapes always fit
            break
    return bm, bn, bk


def lane_block(n: int, target: int) -> int:
    """block_n for an output dim `n`: the largest lane-aligned divisor <=
    `target`. A dim wider than a lane row with no such divisor (a 50272
    vocab) gets a lane-aligned block and a ragged last block instead — the
    kernels grid N with `pl.cdiv`, and since every column decodes and
    contracts on its own, the last block's out-of-range columns touch only
    output columns that are never stored."""
    if n > LANES and n % LANES:
        return max(LANES, target // LANES * LANES)
    return select_block(n, target, multiple=LANES, name="block_n")


# ---------------------------------------------------------------------------
# paged-attention page-block grid (DESIGN.md §13)
# ---------------------------------------------------------------------------

def kv_page_bytes(block_size: int, hkv: int, dh: int, quant: str = "none") -> int:
    """HBM bytes one KV page costs the decode read stream. The per-token
    formula (K + V code planes, codec scale planes, position plane) is the
    roofline's — one accounting for pricing and VMEM sizing alike."""
    from repro.core.roofsurface import kv_bytes_per_token

    return int(block_size * kv_bytes_per_token(quant, hkv, dh))


def pick_page_block(
    mb: int,
    block_size: int,
    hkv: int,
    dh: int,
    quant: str = "none",
    *,
    hq: Optional[int] = None,
    vmem_budget: int = VMEM_BUDGET,
    target: int = 8,
) -> int:
    """Pages per step of the fused paged-attention page walk.

    The walk is MEM-bound on the KV stream (the attention analog of the
    decode GeMV regime): each block's bytes are fetched exactly once, so
    the block size only trades online-softmax rescale overhead against
    VMEM residency. Returns the largest divisor of `mb` that is <= `target`
    and fits the budget — capped at mb // 2 whenever mb splits, so the
    walk never degenerates into one whole-table block (which would
    re-materialize the gathered KV view)."""
    if mb <= 1:
        return 1
    cap = max(1, mb // 2)
    ppb = select_block(mb, min(target, cap), name="pages_per_block")
    overhead = 3 * (hq or hkv) * dh * 4  # query + f32 accumulator + exp block
    while (
        ppb > 1
        and 2 * ppb * kv_page_bytes(block_size, hkv, dh, quant) + overhead
        > vmem_budget
    ):
        ppb = select_block(mb, ppb // 2, name="pages_per_block")
    return ppb
