"""decode_roofline: the least time the chip needs for the traced decode
steps, over the device time of `jit_chunk_step` in the trace.

The least time is max(FLOPs / peak bf16, bytes / HBM bandwidth): each live
decode step reads every stored weight byte and the bf16 tied head once,
and each live slot its own KV (`flops.py`). Which bound applies is printed
beside it."""
import flops

MODULE = "jit_chunk_step"


def read(r):
    if r.trace is None:
        return None
    t = r.trace["module_ns"].get(MODULE, 0.0) / 1e9
    w = r.traced_work()
    if t <= 0 or w["decode_steps"] == 0:
        return None
    f = flops.decode_flops(r.model, w["decode_tokens"], w["kv_sum"])
    b = flops.decode_bytes(r.model, r.weights, w["decode_steps"], w["kv_sum"])
    compute, memory = f / r.peak["bf16_flops"], b / r.peak["hbm_bytes_per_s"]
    return {"value": 100.0 * max(compute, memory) / t,
            "bound": "memory" if memory >= compute else "compute",
            "device_s": t, "steps": w["decode_steps"],
            "tokens": w["decode_tokens"], "bytes": b, "flops": f}
