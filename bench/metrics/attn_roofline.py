"""attn_roofline: the least time the paged-attention walk needs for the
traced decode launches, over the device time of its scope
`deca.paged_attention` in `jit_chunk_step`.

Each scan step reads, for each active slot, the KV of the context its
attention covers: `serve.decode` `kv_tokens` in all, times
`flops.kv_bytes_per_token`, at the HBM bandwidth. Reads nothing unless
the harness kept the trace's kernel scopes (`kernels.reduce`)."""
import flops
import kernels


def read(r):
    got = kernels.traced(r, "jit_chunk_step", "deca.paged_attention",
                         "serve.decode")
    if got is None:
        return None
    t, share, work = got
    b = float(work["kv_tokens"] * flops.kv_bytes_per_token(r.model))
    return {"value": 100.0 * b / r.peak["hbm_bytes_per_s"] / t,
            "device_s": t, "module_share": share,
            "kv_tokens": work["kv_tokens"], "bytes": b}
