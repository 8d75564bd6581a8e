"""gemv_roofline: the least time the decompress-GEMV needs for the traced
decode launches, over the device time of its scope `deca.gemv` in
`jit_chunk_step`.

Every scan step launched (`serve.decode` `scan_steps`, a chunk's dead tail
included, since the device runs it) reads each stored weight plane once
(`flops.plane_bytes`) at the HBM bandwidth. Reads nothing unless the
harness kept the trace's kernel scopes (`kernels.reduce`)."""
import flops
import kernels


def read(r):
    got = kernels.traced(r, "jit_chunk_step", "deca.gemv", "serve.decode")
    if got is None:
        return None
    t, share, work = got
    b = float(work["scan_steps"] * flops.plane_bytes(r.model, r.weights))
    return {"value": 100.0 * b / r.peak["hbm_bytes_per_s"] / t,
            "device_s": t, "module_share": share,
            "scan_steps": work["scan_steps"], "bytes": b}
