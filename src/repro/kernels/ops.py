"""Public jit'd entry points for DECA decompression ops and the fused
paged-attention decode.

Dispatches between the Pallas kernels (TPU target; interpret-mode on CPU)
and the pure-jnp reference path. The reference path is what the distributed
model graphs use (it lowers to plain XLA HLO everywhere, including the
512-device dry-run); the Pallas path is the TPU hot-spot implementation,
validated against the reference in tests/.

Regime split (DESIGN.md §12): below `GEMV_MAX_M` rows the matmul is the
decode GeMV regime — bandwidth-bound on the weight stream — and both impls
route to the decode-shaped variants (`ref.decompress_gemv` /
`decompress_gemv_pallas`) that never materialize the dense (K, N) weight.
With no implementation asked for (`impl="auto"`, what the serving path
passes by default), a GeMV on a TPU runs the fused Pallas kernel and
everything else runs the jnp oracle: the choice follows the backend and
M only. The fused kernel sums over K in blocks, so it matches the oracle
to f32 rounding, not bit for bit (tests/test_decode_chunk.py argues the
bound); an explicit `impl="ref"` keeps the oracle on any backend.

`paged_attention` (DESIGN.md §13) is the same split on the decode
*attention* path: both impls walk the quantized KV pool page by page,
dequantize-on-read via the codec registry, and never materialize the
gathered (B, MB*bsize, Hkv, Dh) KV view.

Compile mode is one switch for all four kernel entry points (decompress,
gemm, gemv, paged attention), resolved by `kernels.mode.resolve_interpret`:
compiled Mosaic on a TPU, interpreted on every other backend, and
`REPRO_PALLAS_INTERPRET=1`/`=0` forces interpret/compiled anywhere. The
resolved mode is passed explicitly so it keys each entry point's jit cache.

Each entry point's dispatch runs inside a `jax.named_scope` that names the
kernel, not its implementation (`deca.gemv`, `deca.gemm`,
`deca.paged_attention`, `deca.decompress`): the scope lands in the HLO
metadata of every op either impl emits, so a device trace attributes the
kernel's time by the same name before and after a kernel swap
(DESIGN.md §14).
"""
from __future__ import annotations

from typing import Dict, Optional

import jax
import jax.numpy as jnp

from repro.core.compression import CompressedTensor
from repro.kernels import ref
from repro.kernels.deca_decompress import decompress_pallas
from repro.kernels.deca_gemm import decompress_gemm_pallas, decompress_gemv_pallas
from repro.kernels.mode import on_tpu, resolve_interpret
from repro.kernels.paged_attention import paged_attention_pallas
from repro.obs.metrics import TRACE_COUNTS

# Rows at or below which the decode-shaped GeMV path is used. The decode
# step's M is the continuous-batching slot count (<= ~32); prefill and
# training matmuls sit far above the threshold and keep the GeMM tiling.
GEMV_MAX_M = 32

# Routing switch for the fused paged-attention decode path; False restores
# the PR 4 gather-read hot path (the benchmark baseline and golden
# reference — see benchmarks/bench_serving.py).
PAGED_ATTENTION_FUSED = True

# Device-trace names of the kernels (see the module docstring).
SCOPE_GEMV = "deca.gemv"
SCOPE_GEMM = "deca.gemm"
SCOPE_PAGED_ATTENTION = "deca.paged_attention"
SCOPE_DECOMPRESS = "deca.decompress"

def decompress(
    ct: CompressedTensor,
    *,
    impl: str = "ref",
    out_dtype=jnp.bfloat16,
    **block_kwargs,
) -> jax.Array:
    """Decompress to a dense (K, N) array. impl: 'ref' | 'pallas'."""
    with jax.named_scope(SCOPE_DECOMPRESS):
        if impl == "ref":
            return ref.decompress(ct, out_dtype=out_dtype)
        if impl == "pallas":
            return decompress_pallas(
                ct, out_dtype=out_dtype, interpret=resolve_interpret(),
                **block_kwargs,
            )
    raise ValueError(impl)


def decompress_gemm(
    x: jax.Array,
    ct: CompressedTensor,
    *,
    impl: str = "auto",
    out_dtype=jnp.float32,
    **block_kwargs,
) -> jax.Array:
    """Fused-semantics compressed GeMM: x (..., K) @ W (K, N).

    Leading dims of x are flattened to M. impl: 'auto' (the fused Pallas
    GeMV on a TPU at M <= GEMV_MAX_M, the jnp oracle otherwise) | 'ref' |
    'pallas' | 'gemv' (explicit decode-shaped oracle; 'ref'/'pallas'
    route to their GeMV when M <= GEMV_MAX_M). Each GeMV call site counts
    once per trace in `TRACE_COUNTS` under `kernels.gemv_sites.fused` or
    `.ref`.
    """
    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])
    m = x2.shape[0]
    # the GeMV variants tile fewer dims than the GeMM grid; drop the block
    # kwargs they don't take so the same call works on either side of the
    # M-threshold (block_m / block_k are meaningless with M kept whole /
    # the full-K contraction)
    gemv_ref_kw = {k: v for k, v in block_kwargs.items() if k == "block_n"}
    gemv_pl_kw = {
        k: v for k, v in block_kwargs.items() if k in ("block_n", "block_k")
    }
    if impl not in ("auto", "gemv", "ref", "pallas"):
        raise ValueError(impl)
    gemv = impl == "gemv" or m <= GEMV_MAX_M
    if impl == "auto":
        impl = "pallas" if gemv and on_tpu() else "ref"
    if gemv:
        route = "fused" if impl == "pallas" else "ref"
        TRACE_COUNTS.counter(f"kernels.gemv_sites.{route}", "sites").inc()
    with jax.named_scope(SCOPE_GEMV if gemv else SCOPE_GEMM):
        if impl == "pallas" and gemv:
            out = decompress_gemv_pallas(
                x2, ct, out_dtype=out_dtype, interpret=resolve_interpret(),
                **gemv_pl_kw,
            )
        elif impl == "pallas":
            out = decompress_gemm_pallas(
                x2, ct, out_dtype=out_dtype, interpret=resolve_interpret(),
                **block_kwargs,
            )
        elif gemv:
            out = ref.decompress_gemv(x2, ct, out_dtype=out_dtype, **gemv_ref_kw)
        else:
            out = ref.decompress_gemm(x2, ct, out_dtype=out_dtype)
        return out.reshape(*lead, out.shape[-1])


def paged_attention(
    q: jax.Array,                 # (B, Hq, Dh) one query token per slot
    pools: Dict[str, jax.Array],  # kp/vp/ppos (+ks/vs for scaled codecs)
    block_tables: jax.Array,      # (B, MB) int32 device page ids
    kv_lens: jax.Array,           # (B,) int32 valid KV tokens per slot
    q_pos: jax.Array,             # (B,) int32 query positions
    *,
    quant: str = "none",
    causal: bool = True,
    window: int = 0,
    softcap: float = 0.0,
    impl: str = "ref",
    pages_per_block: Optional[int] = None,
) -> jax.Array:
    """Fused paged-attention decode (DESIGN.md §13): dequantize-on-read
    inside the page walk, online softmax, length-bounded by `kv_lens` —
    the gathered dense KV view is never materialized. impl: 'ref' (the
    length-bounded while-loop oracle the model graphs run; 'auto' is
    'ref') | 'pallas'."""
    with jax.named_scope(SCOPE_PAGED_ATTENTION):
        if impl in ("ref", "auto"):
            return ref.paged_decode_attention(
                q, pools, block_tables, kv_lens, q_pos,
                quant=quant, causal=causal, window=window, softcap=softcap,
                pages_per_block=pages_per_block,
            )
        if impl == "pallas":
            return paged_attention_pallas(
                q, pools, block_tables, kv_lens, q_pos,
                quant=quant, causal=causal, window=window, softcap=softcap,
                pages_per_block=pages_per_block, interpret=resolve_interpret(),
            )
    raise ValueError(impl)
