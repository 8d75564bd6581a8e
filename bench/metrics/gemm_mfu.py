"""gemm_mfu: the FLOPs of the prefill's decompress-GEMMs over (device time
of their scope `deca.gemm` in `jit_paged_prefill` x peak bf16 FLOP/s).

Every token-step launched (`serve.prefill` `rows` x `span`, padding
included, since the GEMM computes it; the scheduler's share of it is
`prefill_pad_share`) does 2 FLOPs per FC weight (`flops.linear_params`).
Reads nothing unless the harness kept the trace's kernel scopes
(`kernels.reduce`)."""
import flops
import kernels


def read(r):
    got = kernels.traced(r, "jit_paged_prefill", "deca.gemm",
                         "serve.prefill")
    if got is None:
        return None
    t, share, work = got
    f = float(work["token_steps"] * 2 * flops.linear_params(r.model))
    return {"value": 100.0 * f / (t * r.peak["bf16_flops"]),
            "device_s": t, "module_share": share,
            "token_steps": work["token_steps"], "flops": f}
