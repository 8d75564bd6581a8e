"""Compressed gradient all-reduce with persistent error feedback.

`make_compressed_allreduce(mesh, grads_like)` returns an
`allreduce(grads, err) -> (avg_grads, new_err)` that quantizes the
error-compensated gradient (g + err) per-group with any KV-capable codec
from the registry (`repro.core.codecs`) — int8 (symmetric, per-group scale)
and BF8 (E5M2, the paper's quantization substrate reused for collectives)
are the canonical choices; mxfp4/int4/nf4 work the same way — sums the
dequantized payload across every mesh axis with `psum`, and keeps the local
quantization residual as the next step's error feedback. The residual
guarantees the *transmitted* sequence telescopes: sum_t sent_t = sum_t g_t
- err_T, so quantization bias does not accumulate over training
(Karimireddy et al., "Error Feedback Fixes SignSGD").

The reduction runs inside shard_map with replicated specs: each device
holds its own local gradient replica (SPMD data parallelism), quantization
is purely local, and only the psum crosses the interconnect — on a real
ring that is where the 4x (int8) byte saving lands.
"""
from __future__ import annotations

from typing import Any, Callable, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.core import codecs

# canonical methods; any name in codecs.kv_codec_names() is accepted
METHODS = ("int8", "bf8")


# ---------------------------------------------------------------------------
# per-leaf quantize / dequantize (local, no communication)
# ---------------------------------------------------------------------------

def _codec_roundtrip(x: jax.Array, codec: codecs.Codec, group: int) -> jax.Array:
    """x -> dequantize(quantize(x)): what the wire would carry. Grouped
    along a flat view; scaled codecs get one scale per `group` elements."""
    flat = x.reshape(-1).astype(jnp.float32)
    pad = (-flat.size) % group
    g = jnp.pad(flat, (0, pad)).reshape(-1, group)
    codes, scales = codec.kv_encode(g)
    deq = codec.kv_decode(codes, scales).astype(jnp.float32).reshape(-1)
    return deq[: flat.size].reshape(x.shape)


def make_compressed_allreduce(
    mesh: Mesh,
    grads_like: Any,
    *,
    method: str = "int8",
    group: int = 128,
) -> Tuple[Callable, Callable]:
    """Build the compressed gradient all-reduce for `mesh`.

    `method` names any KV-capable registered codec (see
    `repro.core.codecs.kv_codec_names()`); unknown or non-quantizing
    formats raise ValueError.

    Returns (allreduce, init_err):
      init_err(grads)       -> zero f32 residual tree
      allreduce(grads, err) -> (avg_grads, new_err); avg_grads is the mean
                               over all mesh devices of the quantized
                               payloads, new_err the local residual.
    """
    codec = codecs.get_codec(method)  # ValueError on unknown formats
    if not codec.kv_capable:
        raise ValueError(
            f"method {method!r} has no runtime quantizer; choose one of "
            f"{codecs.kv_codec_names()}"
        )
    axes = tuple(mesh.axis_names)
    n_dev = int(np.prod([mesh.shape[a] for a in axes]))

    def init_err(grads: Any) -> Any:
        return jax.tree.map(lambda g: jnp.zeros(g.shape, jnp.float32), grads)

    def _leaf(g: jax.Array, e: jax.Array):
        compensated = g.astype(jnp.float32) + e
        sent = _codec_roundtrip(compensated, codec, group)
        avg = jax.lax.psum(sent, axes) / n_dev
        return avg, compensated - sent

    def _body(grads: Any, err: Any):
        flat_g, treedef = jax.tree_util.tree_flatten(grads)
        flat_e = treedef.flatten_up_to(err)
        avgs, errs = [], []
        for g, e in zip(flat_g, flat_e):
            a, ne = _leaf(g, e)
            avgs.append(a)
            errs.append(ne)
        return (
            jax.tree_util.tree_unflatten(treedef, avgs),
            jax.tree_util.tree_unflatten(treedef, errs),
        )

    # replicated in/out: every device carries its full local gradient; the
    # psum inside is the only cross-device traffic
    allreduce = jax.jit(
        jax.shard_map(
            _body,
            mesh=mesh,
            in_specs=(P(), P()),
            out_specs=(P(), P()),
            check_vma=False,
        )
    )
    return allreduce, init_err
