"""Pallas TPU kernel: fused paged-attention decode (DESIGN.md §13).

The decode-attention analog of the fused decompress-GeMM: the quantized
K/V pool pages are the compressed stream, and the DECA stages run inside
the kernel against each page before it ever exists in dense form:

  DECA stage                      kernel equivalent
  ----------                      -----------------
  Loader (LDQ + prefetcher)       `PrefetchScalarGridSpec`: the block
                                  tables are scalar-prefetched, so the
                                  HBM->VMEM DMA of page block i+1 (indexed
                                  *through the table*) overlaps the
                                  online-softmax update on block i
  Dequantization (LUT array)      the codec registry's `kv_decode` on the
                                  VPU (shift/mask/select — the same jnp
                                  body `kernels/ref.py` uses)
  Scaling                         per-(slot, head) bf16 scale multiply
  TOut registers                  VMEM f32 (m, l, acc) online-softmax
                                  scratch; the output block stores once at
                                  the last page block

Grid = (slot, page-block), page-blocks innermost: each step folds
`pages_per_block` pages (each fetched via its own table-indexed BlockSpec)
into the flash-style accumulator. The walk is *length-bounded*: a page
past its slot's used page count (`kv_lens`, scalar-prefetched) is skipped
with `pl.when` — no decode, no softmax work — and its table entry is the
null page, so consecutive skipped steps re-target page 0 and the pipeline
issues no new copies for them. The dense (B, MB*bsize, Hkv, Dh) KV view of
`paged_gather_kv` is never materialized.

Validated against `ref.paged_decode_attention` (the jnp oracle the model
graphs run) in tests/test_paged_attention.py; interpret mode on CPU, with
the compile switch shared by all kernel entry points (`kernels.mode`,
REPRO_PALLAS_INTERPRET).
"""
from __future__ import annotations

import functools
import math
from typing import Dict, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.mode import resolve_interpret
from repro.kernels.ref import (
    kv_decode_page,
    paged_softmax_update,
    resolve_page_walk,
)


def _paged_attn_kernel(
    quant, bs, hkv, g, dh, ppb, npb, causal, window, softcap, has_scale,
    *refs,
):
    tables_ref, lens_ref, qpos_ref = refs[:3]
    q_ref = refs[3]
    per = 5 if has_scale else 3
    page_refs = refs[4 : 4 + ppb * per]
    out_ref, m_s, l_s, acc_s = refs[4 + ppb * per :]
    b = pl.program_id(0)
    pb = pl.program_id(1)

    @pl.when(pb == 0)
    def _init():
        m_s[...] = jnp.full_like(m_s, -1e30)
        l_s[...] = jnp.zeros_like(l_s)
        acc_s[...] = jnp.zeros_like(acc_s)

    scale = 1.0 / math.sqrt(dh)
    n_pages = (lens_ref[b] + bs - 1) // bs
    # windowed attention also bounds from below: pages wholly behind this
    # slot's window hold only masked keys (and are typically window-freed)
    lo_page = jnp.maximum(qpos_ref[b] - window + 1, 0) // bs if window > 0 else 0
    q = q_ref[...].reshape(hkv, g, dh)
    q_pos = qpos_ref[b]

    def fold_page(j):
        base = j * per
        kp_ref, vp_ref, pp_ref = page_refs[base : base + 3]
        ks = page_refs[base + 3][0] if has_scale else None
        vs = page_refs[base + 4][0] if has_scale else None
        k = kv_decode_page(kp_ref[0], ks, quant)  # (bs, Hkv, Dh)
        v = kv_decode_page(vp_ref[0], vs, quant)
        m, l, acc = paged_softmax_update(
            q, k, v, pp_ref[0, 0], q_pos, m_s[...], l_s[...], acc_s[...],
            scale=scale, causal=causal, window=window, softcap=softcap,
        )
        m_s[...], l_s[...], acc_s[...] = m, l, acc

    for j in range(ppb):
        # the length bound: pages outside [first visible, used count) are
        # skipped — no decode, no softmax work, and (their table entry
        # being the null page in both the past-length and window-freed
        # cases) no fresh DMA either
        page_no = pb * ppb + j
        pl.when((page_no >= lo_page) & (page_no < n_pages))(
            functools.partial(fold_page, j)
        )

    @pl.when(pb == npb - 1)
    def _flush():
        l = l_s[...]
        out = jnp.where(
            l[..., None] > 0, acc_s[...] / jnp.maximum(l, 1e-30)[..., None], 0.0
        )
        out_ref[...] = out.reshape(1, hkv * g, dh).astype(out_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=(
        "quant", "causal", "window", "softcap", "pages_per_block", "interpret",
    ),
)
def paged_attention_pallas(
    q: jax.Array,                  # (B, Hq, Dh) one query token per slot
    pools: Dict[str, jax.Array],   # kp/vp/ppos (+ks/vs for scaled codecs)
    block_tables: jax.Array,       # (B, MB) int32 device page ids
    kv_lens: jax.Array,            # (B,) int32 valid KV tokens per slot
    q_pos: jax.Array,              # (B,) int32 query positions
    *,
    quant: str = "none",
    causal: bool = True,
    window: int = 0,
    softcap: float = 0.0,
    pages_per_block: Optional[int] = None,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Fused paged-attention decode over the quantized KV pool.

    `pages_per_block` defaults to the autotuned page-block grid
    (`autotune.pick_page_block`); each of the block's pages rides its own
    table-indexed BlockSpec, so the grid pipeline prefetches scattered
    pages exactly like the GeMM kernels prefetch compressed weight blocks.
    """
    kp = pools["kp"]
    bs, hkv = kp.shape[1], kp.shape[2]
    w = kp.shape[3]
    b, hq, dh = q.shape
    g = hq // hkv
    ppb, npb, tables = resolve_page_walk(
        block_tables, bs, hkv, dh, quant, hq, pages_per_block
    )
    has_scale = "ks" in pools
    # positions ride as (pages, 1, bs): a (1, bs) block of the 2-D plane
    # breaks Mosaic's (8, 128) block tiling, a (1, 1, bs) block of this
    # view spans its last two dims whole
    ppos = pools["ppos"].reshape(pools["ppos"].shape[0], 1, bs)

    def page_spec(shape_tail, j):
        zeros = (0,) * len(shape_tail)
        return pl.BlockSpec(
            (1,) + shape_tail,
            lambda bb, pb, tbl, ln, qp, j=j, z=zeros: (tbl[bb, pb * ppb + j],) + z,
        )

    in_specs = [
        pl.BlockSpec((1, hq, dh), lambda bb, pb, tbl, ln, qp: (bb, 0, 0)),
    ]
    operands = [q]
    for j in range(ppb):
        in_specs += [
            page_spec((bs, hkv, w), j),
            page_spec((bs, hkv, w), j),
            page_spec((1, bs), j),
        ]
        operands += [pools["kp"], pools["vp"], ppos]
        if has_scale:
            in_specs += [page_spec((bs, hkv), j), page_spec((bs, hkv), j)]
            operands += [pools["ks"], pools["vs"]]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b, npb),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, hq, dh), lambda bb, pb, tbl, ln, qp: (bb, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((hkv, g), jnp.float32),
            pltpu.VMEM((hkv, g), jnp.float32),
            pltpu.VMEM((hkv, g, dh), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(
            _paged_attn_kernel, quant, bs, hkv, g, dh, ppb, npb,
            causal, window, softcap, has_scale,
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hq, dh), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=resolve_interpret(interpret),
    )(
        jnp.asarray(tables, jnp.int32),
        jnp.asarray(kv_lens, jnp.int32),
        jnp.asarray(q_pos, jnp.int32),
        *operands,
    )
    return out.astype(q.dtype)
