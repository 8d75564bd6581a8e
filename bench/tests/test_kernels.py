"""The kernel-scope reduction on synthetic traces with known answers: the
HLO the profiler keeps (built here in the protobuf wire format), nested op
events, host spans with work arguments, and a stretch of dropped device
buffers."""
import collections
import importlib.util
import types

import pytest
from jax.profiler import ProfileData

import devtrace
import flops
import kernels
import peaks
from conftest import BENCH, SMOKE_MODELS
from test_devtrace import _plane

def _varint(n):
    out = b""
    while True:
        b, n = n & 0x7F, n >> 7
        if n:
            out += bytes([b | 0x80])
        else:
            return out + bytes([b])


def _msg(*fields):
    """Protobuf wire bytes of (field, value): int -> varint, bytes/str ->
    length-delimited, list of ints -> packed."""
    out = b""
    for f, v in fields:
        if isinstance(v, int):
            out += _varint(f << 3) + _varint(v)
            continue
        if isinstance(v, list):
            v = b"".join(_varint(x) for x in v)
        if isinstance(v, str):
            v = v.encode()
        out += _varint(f << 3 | 2) + _varint(len(v)) + v
    return out


def _op(name, op_name=None, calls=()):
    fields = [(1, name)]
    if op_name is not None:
        fields.append((7, _msg((2, op_name))))
    if calls:
        fields.append((38, list(calls)))
    return _msg(*fields)


def _hlo(*comps):
    """An HloProto: computations (id, [instruction bytes]), callees first."""
    return _msg((1, _msg(*[
        (3, _msg(*[(2, ins) for ins in ops], (5, cid))) for cid, ops in comps
    ])))


CHUNK_HLO = _hlo(
    # the GEMV tile loop's body: a scoped fusion and a copy the compiler
    # added, with no metadata, which takes the loop's scope
    (2, [_op("fusion.3", "jit(chunk_step)/while/body/deca.gemv/while/body/mul"),
         _op("copy.4")]),
    # the layer scan's body: the tile loop, the attention walk, the KV write
    (3, [_op("while.2", "jit(chunk_step)/while/body/deca.gemv/while", [2]),
         _op("fusion.5", "jit(chunk_step)/while/body/deca.paged_attention/dot"),
         _op("fusion.7", "jit(chunk_step)/while/body/kv.write/scatter")]),
    (1, [_op("while.1", "jit(chunk_step)/while", [3]),
         _op("fusion.6", "jit(chunk_step)/model.head/dot_general")]),
)
PREFILL_HLO = _hlo((1, [
    _op("fusion.1", "jit(paged_prefill)/while/body/deca.gemm/dot_general"),
    _op("fusion.2", "jit(paged_prefill)/while/body/model.attention/exp"),
]))


def _stat_plane(pid, name, programs):
    """The metadata plane: one event metadata per program, its HLO as a
    bytes stat named like the profiler's."""
    def esc(b):
        return "".join(f"\\{c:03o}" for c in b)
    metas = "".join(
        f'  event_metadata {{ key: {i} value {{ id: {i} name: "{n}" '
        f'stats {{ metadata_id: 1 bytes_value: "{esc(p)}" }} }} }}\n'
        for i, (n, p) in enumerate(programs.items(), 1)
    )
    stat = '  stat_metadata { key: 1 value { id: 1 name: "Hlo Proto" } }\n'
    return f'planes {{\n  id: {pid}\n  name: "{name}"\n{metas}{stat}}}\n'


def _host_plane(events, args):
    """A host plane whose one line holds `events` (name, start, duration);
    `args[name]` (a list, one dict per event of that name, in order) gives
    int stats."""
    names = sorted({n for n, _, _ in events})
    ids = {n: i for i, n in enumerate(names, 1)}
    keys = sorted({k for per in args.values() for a in per for k in a})
    kid = {k: i for i, k in enumerate(keys, 1)}
    seen = collections.Counter()
    evs = ""
    for n, s, d in events:
        stats = ""
        if n in args:
            a = args[n][seen[n]]
            seen[n] += 1
            stats = "".join(f" stats {{ metadata_id: {kid[k]} int64_value: {v} }}"
                            for k, v in a.items())
        evs += (f"    events {{ metadata_id: {ids[n]} offset_ps: {s * 1000} "
                f"duration_ps: {d * 1000}{stats} }}\n")
    meta = "".join(f'  event_metadata {{ key: {i} value {{ id: {i} name: "{n}" }} }}\n'
                   for n, i in ids.items())
    smeta = "".join(f'  stat_metadata {{ key: {i} value {{ id: {i} name: "{k}" }} }}\n'
                    for k, i in kid.items())
    return (f'planes {{\n  id: 1\n  name: "/host:CPU"\n'
            f'  lines {{ id: 1 name: "python" timestamp_ns: 0\n{evs}  }}\n'
            f"{meta}{smeta}}}\n")


def kernel_trace(tmp_path, chips=1):
    """Two `bench.step`s: the first runs a prefill and a decode chunk whose
    ops nest (a tile loop inside the layer scan); the second runs a chunk
    inside a stretch whose device buffers were dropped, so neither its time
    nor its span arguments count. Each of `chips` device planes runs the
    same ops at the same times, as chips of one mesh do."""
    decode = {"scan_steps": 8, "slots": 2, "kv_tokens": 100}
    host = _host_plane([
        ("bench.traced", 0, 2000),
        ("bench.step", 100, 800), ("serve.step", 100, 800),
        ("serve.prefill", 110, 90), ("serve.prefill.launch", 112, 8),
        ("serve.prefill.sync", 120, 75),
        ("serve.decode", 200, 700), ("serve.decode.pack", 200, 20),
        ("serve.decode.launch", 220, 100), ("serve.decode.sync", 320, 400),
        ("serve.decode.replay", 720, 180),
        ("bench.step", 1000, 900), ("serve.decode", 1000, 890),
    ], {"serve.prefill": [{"rows": 2, "span": 16, "real_tokens": 20}],
        "serve.decode": [decode, dict(decode, scan_steps=4)]})
    meta = _stat_plane(3, "/host:metadata", {
        "jit_chunk_step(7)": CHUNK_HLO, "jit_paged_prefill(9)": PREFILL_HLO,
    })
    dev = "".join(_plane(4 + i, f"/device:TPU:{i}", {
        "XLA Modules": [(1, 120, 70), (2, 330, 380), (2, 1100, 400)],
        "XLA Ops": [
            (10, 120, 50), (11, 170, 20),                     # prefill
            (12, 330, 380), (13, 340, 100), (14, 350, 20),    # scan, loop
            (15, 400, 30), (16, 460, 100), (17, 600, 50),
            (18, 660, 10),
            (12, 1100, 400), (13, 1110, 100),                 # dropped
        ],
        "XLA TraceMe": [(19, 1050, 550)],
    }, {1: "jit_paged_prefill(9)", 2: "jit_chunk_step(7)",
        10: "%fusion.1 = bf16[2,16,64] fusion(x)",
        11: "%fusion.2 = bf16[2,16,64] fusion(y)",
        12: "%while.1 = (s32[]) while(a)", 13: "%while.2 = (s32[]) while(b)",
        14: "%fusion.3 = f32[4,128] fusion(c)", 15: "%copy.4 = f32[4] copy(d)",
        16: "%fusion.5 = f32[4] fusion(e)", 17: "%fusion.6 = f32[4] fusion(f)",
        18: "%fusion.7 = f32[4] fusion(g)", 19: "Trace Buffers Dropped"})
        for i in range(chips))
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(host + meta + dev))
    return path


def test_scope_times_count_nesting_once(tmp_path):
    path = kernel_trace(tmp_path)
    reduced = devtrace.reduce(ProfileData.from_file(str(path)))
    assert [s["covered"] for s in reduced["steps"]] == [True, False]
    k = kernels.reduce(path, reduced)
    assert k["steps"] == 1
    chunk = k["modules"]["jit_chunk_step"]
    # the loop (340..440) holds the fusion and the copy: counted once
    assert chunk["scope_ns"] == {"deca.gemv": 100, "deca.paged_attention": 100,
                                 "model.head": 50, "kv.write": 10}
    assert chunk["device_ns"] == 380
    assert chunk["other_ns"] == 380 - 260  # the scan's own time
    assert k["modules"]["jit_paged_prefill"] == {
        "device_ns": 70, "scope_ns": {"deca.gemm": 50, "model.attention": 20},
        "other_ns": 0}


def test_scope_times_are_per_chip(tmp_path):
    """Two chips running the same ops at once: every time reads as one
    chip's, so a kernel's share of the two chips' peaks is half its share
    of one chip's."""
    got = {}
    for chips in (1, 2):
        path = kernel_trace(tmp_path, chips)
        reduced = devtrace.reduce(ProfileData.from_file(str(path)))
        assert reduced["devices"] == chips
        got[chips] = kernels.reduce(path, reduced)
    assert got[2] == got[1]
    one = _readings({"module_ns": {}, "kernels": got[1]})
    two = _readings({"module_ns": {}, "kernels": got[2]})
    two.peak = peaks.deployment(one.peak, 2)
    for name in ("gemv_roofline", "attn_roofline"):
        share = _reader(name)(one)["value"]
        assert share > 0
        assert _reader(name)(two)["value"] == pytest.approx(share / 2,
                                                            rel=1e-12)


def test_sum_the_work_of_covered_steps_only(tmp_path):
    path = kernel_trace(tmp_path)
    k = kernels.reduce(path, devtrace.reduce(ProfileData.from_file(str(path))))
    assert k["work"] == {
        "serve.decode": {"launches": 1, "scan_steps": 8, "slots": 2,
                         "kv_tokens": 100},
        "serve.prefill": {"launches": 1, "rows": 2, "span": 16,
                          "real_tokens": 20, "token_steps": 32},
    }


def test_device_time_comes_from_devtrace(tmp_path):
    path = kernel_trace(tmp_path)
    reduced = devtrace.reduce(ProfileData.from_file(str(path)))
    k = kernels.reduce(path, reduced)
    covered = [st["module_ns"] for st in reduced["steps"] if st["covered"]]
    assert {p: m["device_ns"] for p, m in k["modules"].items()} == covered[0]
    assert "idle_by_span_ns" not in k


def test_read_nothing_from_a_program_that_names_nothing(tmp_path):
    # no metadata plane and no program spans: a program without scopes
    host = _plane(1, "/host:CPU", {
        "python": [(1, 100, 1000), (2, 100, 900)],
    }, {1: "bench.traced", 2: "bench.step"})
    dev = _plane(2, "/device:TPU:0", {
        "XLA Modules": [(1, 150, 300)], "XLA Ops": [(3, 150, 200)],
    }, {1: "jit_chunk_step(7)", 3: "fusion.1"})
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(host + dev))
    reduced = devtrace.reduce(ProfileData.from_file(str(path)))
    assert kernels.reduce(path, reduced) == {}


def test_op_scopes_inherit_through_called_computations():
    scopes = kernels.op_scopes(CHUNK_HLO)
    assert scopes["copy.4"] == ("deca.gemv",)
    assert scopes["while.1"] == ()
    assert scopes["fusion.6"] == ("model.head",)


def _reader(name):
    spec = importlib.util.spec_from_file_location(
        name, BENCH / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _readings(trace):
    return types.SimpleNamespace(
        trace=trace, model=SMOKE_MODELS["opt"], weights="mxfp4_100",
        peak=peaks.PEAKS["TPU v5 lite"])


KERNELS = {
    "steps": 2,
    "modules": {
        "jit_chunk_step": {"device_ns": 4e9, "other_ns": 0.4e9, "scope_ns": {
            "deca.gemv": 3e9, "deca.paged_attention": 0.5e9,
            "model.head": 0.1e9}},
        "jit_paged_prefill": {"device_ns": 1e9, "other_ns": 0.1e9,
                              "scope_ns": {"deca.gemm": 0.8e9,
                                           "model.attention": 0.1e9}},
    },
    "work": {
        "serve.decode": {"launches": 2, "scan_steps": 16, "slots": 8,
                         "kv_tokens": 50000},
        "serve.prefill": {"launches": 1, "rows": 2, "span": 1024,
                          "real_tokens": 1900, "token_steps": 2048},
    },
}


@pytest.mark.parametrize("name,expect", [
    ("gemv_roofline", lambda m, pk: 100 * 16 * flops.plane_bytes(
        m, "mxfp4_100") / pk["hbm_bytes_per_s"] / 3.0),
    ("attn_roofline", lambda m, pk: 100 * 50000 * flops.kv_bytes_per_token(m)
     / pk["hbm_bytes_per_s"] / 0.5),
    ("gemm_mfu", lambda m, pk: 100 * 2048 * 2 * flops.linear_params(m)
     / (0.8 * pk["bf16_flops"])),
])
def test_kernel_readers_divide_stated_work_by_scope_time(name, expect):
    r = _readings({"module_ns": {}, "kernels": KERNELS})
    got = _reader(name)(r)
    assert got["value"] == pytest.approx(expect(r.model, r.peak), rel=1e-12)
    module = "jit_paged_prefill" if name == "gemm_mfu" else "jit_chunk_step"
    mod = KERNELS["modules"][module]
    assert got["module_share"] == pytest.approx(
        100 * got["device_s"] * 1e9 / mod["device_ns"])


@pytest.mark.parametrize("name", ["gemv_roofline", "attn_roofline",
                                  "gemm_mfu"])
@pytest.mark.parametrize("trace", [
    None,                                   # an untraced run
    {"module_ns": {"jit_chunk_step": 1e9}},  # a harness that keeps no scopes
    {"module_ns": {}, "kernels": {}},        # a program that names nothing
    {"module_ns": {}, "kernels": dict(KERNELS, work={})},
])
def test_kernel_readers_read_nothing_without_scopes(name, trace):
    assert _reader(name)(_readings(trace)) is None


def test_shares_read_against_the_deployments_peaks():
    """The same readings on 1 and on 4 chips: every share of a roofline or
    a peak reads 4 times lower on 4 (the trace's times are per chip, the
    work the whole model's), every other metric alike."""
    import json

    import numpy as np

    import run
    from conftest import smoke_cell

    window = types.SimpleNamespace(
        t0=0.0, t1=2.0,
        records=[{"rid": 1, "prompt": np.zeros(30, np.int32), "count": 13}],
        steps=[(0.0, 1.0, True, [(1, 0, 5)]), (1.0, 2.0, True, [(1, 5, 13)])])
    trace = {"window_ns": 2e9, "busy_ns": 1.5e9, "kernels": KERNELS,
             "module_ns": {"jit_chunk_step": 3e9, "jit_paged_prefill": 1e9},
             "steps": [{"covered": True}, {"covered": True}]}
    stats = {"decode_steps": 12, "active_slot_steps": 40,
             "prefill_token_steps": 64, "prefill_real_tokens": 30}
    names = [m["name"] for m in json.loads(
        (BENCH.parent / "BENCHMARK.json").read_text())["per_layer"]]
    read = {}
    for chips in (1, 4):
        cell = dict(smoke_cell("opt"), chips=chips)
        r = run.Readings(cell, window, 1.0, stats, trace,
                         peaks.PEAKS["TPU v5 lite"])
        got = {n: run.reader(n)(r) for n in names}
        read[chips] = {n: v["value"] if isinstance(v, dict) else v
                       for n, v in got.items()}
    # a metric this trace does not feed reads nothing at either count
    names = [n for n in names if read[1][n] is not None]
    assert names and all(read[4][n] is not None for n in names)
    shares = [n for n in names if "roofline" in n or "mfu" in n]
    assert shares and all(read[1][n] > 0 for n in names)
    for n in names:
        assert read[1][n] == (4 * read[4][n] if n in shares else read[4][n]), n
