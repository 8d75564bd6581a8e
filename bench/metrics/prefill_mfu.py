"""prefill_mfu: model FLOPs of the real prompt tokens prefilled in the
traced steps (padding does not count; the head for each prompt's last
token only) over (device time of `jit_paged_prefill` x peak bf16 FLOP/s)."""
import flops

MODULE = "jit_paged_prefill"


def read(r):
    if r.trace is None:
        return None
    t = r.trace["module_ns"].get(MODULE, 0.0) / 1e9
    prompts = r.traced_work()["prefill_prompts"]
    if t <= 0 or not prompts:
        return None
    f = sum(flops.prefill_flops(r.model, p) for p in prompts)
    return {"value": 100.0 * f / (t * r.peak["bf16_flops"]),
            "prompts": len(prompts), "device_s": t}
