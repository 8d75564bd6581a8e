"""The trace reduction on a synthetic trace with known answers."""
from jax.profiler import ProfileData

import devtrace


def _plane(pid, name, lines, names):
    meta = "".join(
        f'  event_metadata {{ key: {i} value {{ id: {i} name: "{n}" }} }}\n'
        for i, n in names.items()
    )
    body = ""
    for lid, (lname, events) in enumerate(lines.items(), 1):
        evs = "".join(
            f"    events {{ metadata_id: {m} offset_ps: {s * 1000} "
            f"duration_ps: {d * 1000} }}\n" for m, s, d in events
        )
        body += f'  lines {{ id: {lid} name: "{lname}" timestamp_ns: 0\n{evs}  }}\n'
    return f'planes {{\n  id: {pid}\n  name: "{name}"\n{body}{meta}}}\n'


def trace():
    # host: window 100..1100 ns; a step 100..1000 and, inside it, a
    # dispatch of the decode chunk 600..700
    host = _plane(1, "/host:CPU", {
        "python": [(1, 100, 1000), (2, 100, 900), (3, 600, 100)],
    }, {1: "bench.traced", 2: "bench.step", 3: "PjitFunction(chunk_step)"})
    # device: modules chunk 150..450, prefill 800..1050 (ops overlap), and
    # an op before the window that must be clipped away
    dev = _plane(2, "/device:TPU:0", {
        "XLA Modules": [(1, 150, 300), (2, 800, 250)],
        "XLA Ops": [(3, 150, 200), (4, 300, 150), (5, 800, 250), (5, 0, 50)],
    }, {1: "jit_chunk_step(7)", 2: "jit_paged_prefill(9)", 3: "fusion.1",
        4: "dot.2", 5: "fusion.3"})
    return ProfileData.from_text_proto(host + dev)


def test_busy_is_the_union_of_op_intervals():
    r = devtrace.reduce(trace())
    assert r["window_ns"] == 1000
    assert r["busy_ns"] == 300 + 250  # 150..450 and 800..1050
    assert r["devices"] == 1


def test_module_time_by_name_without_id():
    r = devtrace.reduce(trace())
    assert r["module_ns"] == {"jit_chunk_step": 300, "jit_paged_prefill": 250}
    assert r["op_ns"]["fusion.3"] == 250  # the pre-window op is clipped


def test_idle_gaps_attributed_to_innermost_host_event():
    r = devtrace.reduce(trace())
    idle = r["idle_by_host_ns"]
    # gaps: 100..150 (step), 450..800 (midpoint 625: the dispatch),
    # 1050..1100 (after the step: no host event but the window)
    assert idle == {"bench.step": 50, "PjitFunction(chunk_step)": 350,
                    "no host event": 50}
    assert sum(idle.values()) + r["busy_ns"] == r["window_ns"]


def test_top_lists_seconds_largest_first():
    assert devtrace.top({"a": 1e9, "b": 3e9, "c": 2e9}, 2) == [["b", 3.0], ["c", 2.0]]


def test_union_merges_overlaps():
    assert devtrace.union([(5, 7), (1, 3), (2, 4), (7, 8)]) == [[1, 4], [5, 8]]


def test_dropped_device_buffers_leave_the_window_and_their_steps():
    host = _plane(1, "/host:CPU", {
        "python": [(1, 0, 1000), (2, 0, 400), (2, 500, 400)],
    }, {1: "bench.traced", 2: "bench.step"})
    dev = _plane(2, "/device:TPU:0", {
        "XLA Modules": [(1, 100, 200), (1, 600, 200)],
        "XLA Ops": [(3, 100, 200), (3, 600, 200)],
        "XLA TraceMe": [(4, 550, 300)],
    }, {1: "jit_chunk_step(7)", 3: "fusion.1", 4: "Trace Buffers Dropped"})
    r = devtrace.reduce(ProfileData.from_text_proto(host + dev))
    assert r["window_ns"] == 700 and r["dropped_ns"] == 300
    assert r["busy_ns"] == 200  # the op inside the dropped stretch is left out
    assert [s["covered"] for s in r["steps"]] == [True, False]
    assert r["module_ns"] == {"jit_chunk_step": 200}


def test_op_names_keep_the_head_of_the_hlo_text():
    assert devtrace.op_name("%while.1 = (s32[], bf16[8,49]) while(x)") == \
        "%while.1 = (s32[], bf16[8,49]) while(x)"
    assert len(devtrace.op_name("%f = " + "x" * 500)) == len("%f = ") + 60
