"""decode_mfu: dense-equivalent model FLOPs of the traced decode tokens
over (device time of `jit_chunk_step` x peak bf16 FLOP/s)."""
import flops

MODULE = "jit_chunk_step"


def read(r):
    if r.trace is None:
        return None
    t = r.trace["module_ns"].get(MODULE, 0.0) / 1e9
    w = r.traced_work()
    if t <= 0 or w["decode_tokens"] == 0:
        return None
    f = flops.decode_flops(r.model, w["decode_tokens"], w["kv_sum"])
    return 100.0 * f / (t * r.peak["bf16_flops"])
