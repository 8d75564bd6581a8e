"""Device time by the program's kernel scopes, and the work its spans say
each launch gave the device, from the profile of a traced window.

`devtrace.reduce` stops at whole programs (`XLA Modules`). Here each
program's time is split by the `jax.named_scope`s the program puts on its
kernels and model-step parts (DESIGN.md §14), and the `serve.decode` /
`serve.prefill` spans' arguments give the work those kernels did, so a
kernel's roofline share divides work the program stated by time the
device measured. A TPU op event names no scope: the scope comes from the
HLO that the profiler keeps for every loaded program in the profile's
metadata plane, read here from the protobuf wire format.

Call `reduce(path, devtrace_reduced)` on a traced run's `.xplane.pb`
while it exists, with `devtrace.reduce`'s result for the same profile,
which gives the covered steps and each program's device time (and whose
`idle_by_host_ns` already names the `serve.*` span or the runtime call
inside one around each device gap). The per-kernel readers in `metrics/`
read the result through `traced`, under the reduced trace's `kernels`
key.
"""
from __future__ import annotations

import bisect
import collections

from devtrace import DEVICE_PLANE, WINDOW, module_name, union

# `jax.named_scope` names the program gives its kernels (kernels/ops.py) and
# the rest of a model step's work (models/).
SCOPES = ("deca.gemv", "deca.gemm", "deca.paged_attention", "deca.decompress",
          "model.head", "kv.write", "model.attention")
# The program's spans that carry the work of their launch as arguments.
WORK = {"serve.decode": ("scan_steps", "slots", "kv_tokens"),
        "serve.prefill": ("rows", "span", "real_tokens")}
METADATA_PLANE = "/host:metadata"
HLO_PROTO_STAT = "Hlo Proto"


def _varint(buf, i):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf):
    """(field number, value) of one protobuf message in wire format: an int
    for varint and fixed fields, a memoryview for length-delimited ones."""
    buf = memoryview(buf)
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        field, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            value, i = int.from_bytes(buf[i:i + size], "little"), i + size
        else:
            raise ValueError(f"protobuf wire type {wire} at byte {i}")
        yield field, value


def _packed(buf) -> list:
    out, i = [], 0
    while i < len(buf):
        v, i = _varint(buf, i)
        out.append(v)
    return out


def _text(buf) -> str:
    return bytes(buf).decode("utf-8", "replace")


def hlo_protos(raw) -> dict:
    """{program name as its `XLA Modules` events give it, e.g.
    `jit_chunk_step(123)`: its serialized HloProto}, from the metadata plane
    where the profiler keeps the HLO of every program it saw loaded.
    XSpace.planes = 1; XPlane: name = 2, event_metadata = 4 (map entry: key
    1, value 2), stat_metadata = 5; XEventMetadata: name = 2, stats = 5;
    XStatMetadata: name = 2; XStat: metadata_id = 1, bytes_value = 6."""
    out: dict = {}
    for field, plane in _fields(raw):
        if field != 1:
            continue
        parts = list(_fields(plane))
        if [_text(v) for f, v in parts if f == 2] != [METADATA_PLANE]:
            continue
        stat_names = {}
        for f, entry in parts:
            if f == 5:
                kv = dict(_fields(entry))
                names = [_text(v) for g, v in _fields(kv[2]) if g == 2]
                stat_names[kv[1]] = names[0] if names else ""
        for f, entry in parts:
            if f != 4:
                continue
            meta = list(_fields(dict(_fields(entry))[2]))
            name = next((_text(v) for g, v in meta if g == 2), None)
            for g, stat in meta:
                if g != 5:
                    continue
                st = dict(_fields(stat))
                if stat_names.get(st.get(1)) == HLO_PROTO_STAT and 6 in st:
                    out[name] = st[6]
    return out


def op_scopes(proto) -> dict:
    """{HLO op name: the SCOPES on its `op_name` metadata path}. An op with
    none of them on its path (metadata left empty, as on ops the compiler
    adds) takes those of the op that calls its computation, so a copy
    inside a scoped loop body counts with the loop. HloProto.hlo_module =
    1; HloModuleProto.computations = 3, callers before callees when read
    back to front; HloComputationProto: instructions = 2, id = 5;
    HloInstructionProto: name = 1, metadata = 7 (OpMetadata.op_name = 2),
    called_computation_ids = 38."""
    wanted = set(SCOPES)
    comps = []
    for field, module in _fields(proto):
        if field != 1:
            continue
        for f, comp in _fields(module):
            if f != 3:
                continue
            cid, ops = None, []
            for g, v in _fields(comp):
                if g == 5:
                    cid = v
                elif g == 2:
                    name, path, calls = None, "", []
                    for h, x in _fields(v):
                        if h == 1:
                            name = _text(x)
                        elif h == 7:
                            path = next((_text(y) for k, y in _fields(x)
                                         if k == 2), "")
                        elif h == 38:  # packed, or one varint
                            calls += (_packed(x) if isinstance(x, memoryview)
                                      else [x])
                    own = tuple(s for s in path.split("/") if s in wanted)
                    ops.append((name, own, calls))
            comps.append((cid, ops))
    inherited: dict = {}
    out: dict = {}
    for cid, ops in reversed(comps):
        outer = inherited.get(cid, ())
        for name, own, calls in ops:
            scopes = own or outer
            out[name] = scopes
            for callee in calls:
                inherited.setdefault(callee, scopes)
    return out


def _covered_steps(reduced) -> list:
    return sorted((st["start"], st["end"]) for st in reduced["steps"]
                  if st["covered"])


def _in(steps, starts, t) -> bool:
    i = bisect.bisect_right(starts, t) - 1
    return i >= 0 and t <= steps[i][1]


def _span_line(pd):
    """The events of the host thread that opened the traced window."""
    for plane in pd.planes:
        if plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                evs = list(line.events)
                if any(ev.name == WINDOW for ev in evs):
                    return evs
    return []


def reduce(path, reduced) -> dict:
    """Per program (`XLA Modules` name without its id) of the covered
    `bench.step`s (`devtrace.reduce`'s result, passed as `reduced`): its
    device time from `reduced`, and per kernel scope the union of the
    intervals of the `XLA Ops` events whose op lies under the scope, so
    nested loops count once. `other_ns` is the program's time under no
    scope. Also the summed work arguments of the program's `serve.decode`
    and `serve.prefill` spans in the same steps. Times are averaged over
    the device planes. Empty where the program names nothing (a program
    without scopes or spans)."""
    import mmap

    from jax.profiler import ProfileData

    with open(path, "rb") as fh, \
            mmap.mmap(fh.fileno(), 0, access=mmap.ACCESS_READ) as mm:
        protos = {k: bytes(v) for k, v in hlo_protos(memoryview(mm)).items()}
    pd = ProfileData.from_file(str(path))
    steps = _covered_steps(reduced)
    starts = [s for s, _ in steps]
    planes = [p for p in pd.planes if DEVICE_PLANE.match(p.name)]
    device: dict = collections.Counter()
    for st in reduced["steps"]:
        if st["covered"]:
            device.update(st["module_ns"])
    scoped: dict = collections.defaultdict(list)
    resolved: dict = {}
    for plane in planes:
        lines = {line.name: line for line in plane.lines}
        if "XLA Modules" not in lines or "XLA Ops" not in lines:
            continue
        mods = sorted((ev.start_ns, ev.end_ns, ev.name)
                      for ev in lines["XLA Modules"].events
                      if _in(steps, starts, (ev.start_ns + ev.end_ns) / 2))
        mstarts = [m[0] for m in mods]
        for ev in lines["XLA Ops"].events:
            s, e = ev.start_ns, ev.end_ns
            i = bisect.bisect_right(mstarts, s) - 1
            if i < 0 or s > mods[i][1]:
                continue
            prog = mods[i][2]
            if prog not in resolved:
                proto = protos.get(prog)
                resolved[prog] = op_scopes(proto) if proto else {}
            op = ev.name.partition(" = ")[0].lstrip("%")
            for scope in resolved[prog].get(op, ()):
                scoped[module_name(prog), scope].append((s, e))
            if resolved[prog].get(op):
                scoped[module_name(prog), None].append((s, e))
    n = max(1, len(planes))
    modules: dict = {}
    for prog, ns in device.items():
        times = {scope: sum(e - s for s, e in union(scoped[prog, scope])) / n
                 for scope in SCOPES if scoped.get((prog, scope))}
        any_scope = sum(e - s for s, e in union(scoped.get((prog, None), [])))
        modules[prog] = {"device_ns": ns, "scope_ns": times,
                         "other_ns": max(0.0, ns - any_scope / n)}
    work: dict = {}
    for ev in _span_line(pd):
        if ev.name not in WORK or not _in(steps, starts, ev.start_ns):
            continue
        stats = dict(ev.stats)
        if not all(k in stats for k in WORK[ev.name]):
            continue
        got = work.setdefault(ev.name, collections.Counter())
        got["launches"] += 1
        for k in WORK[ev.name]:
            got[k] += int(stats[k])
        if ev.name == "serve.prefill":
            got["token_steps"] += int(stats["rows"]) * int(stats["span"])
    if not any(m["scope_ns"] for m in modules.values()) and not work:
        return {}
    return {"steps": len(steps), "modules": modules,
            "work": {k: dict(v) for k, v in work.items()}}


def traced(r, module, scope, span):
    """(device seconds of `scope` in `module`, that time's share of the
    module's in %, the summed arguments of the `span` spans) for a metric
    reader, from `reduce`'s result kept as `r.trace["kernels"]`. None where
    the run kept none, or names no such scope or span."""
    k = (r.trace or {}).get("kernels")
    mod = k["modules"].get(module) if k else None
    work = k["work"].get(span) if k else None
    ns = mod["scope_ns"].get(scope, 0.0) if mod else 0.0
    if ns <= 0 or not work:
        return None
    return ns / 1e9, 100.0 * ns / mod["device_ns"], work
