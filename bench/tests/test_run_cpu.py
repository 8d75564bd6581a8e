"""The whole run on the CPU at smoke sizes, with the TPU check stubbed:
warm-up, window, metrics, reference check, and the result line."""
import json

import pytest

import run
from conftest import smoke_cell


@pytest.mark.parametrize("arch", ["opt", "granite"])
def test_run_cell_end_to_end(arch, cpu_device):
    identity, peak = cpu_device
    line = run.run_cell(smoke_cell(arch), 3_000_000_017, 2.0, False,
                        identity, peak)
    assert list(line)[-1] == "checks"
    assert line["correct"], line["checks"]
    assert line["attempted"] > 0 and line["failed"] == 0
    m = line["metrics"]
    assert m["out_tok_s"]["value"] > 0 and m["setup_s"]["value"] > 0
    assert m["out_tok_s.code"]["value"] == m["out_tok_s"]["value"]
    json.dumps(line)


def test_no_compile_inside_the_window(cpu_device, capsys):
    identity, peak = cpu_device
    run.run_cell(smoke_cell("opt"), 5, 2.0, False, identity, peak)
    out = capsys.readouterr().out
    window = [l for l in out.splitlines() if l.startswith("# window:")][0]
    assert "traces in window 0," in window and "compiles in window 0" in window


def test_traced_run_reports_counters(cpu_device):
    identity, peak = cpu_device
    line = run.run_cell(smoke_cell("opt"), 9, 2.0, True, identity, peak)
    m = line["metrics"]
    assert "decode_occupancy" in m and 0 < m["decode_occupancy"]["value"] <= 100
    assert "prefill_pad_share" in m
    assert line["device"]["window_s"] > 0
    assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}


def test_no_tpu_no_result(capsys, monkeypatch):
    monkeypatch.setattr("sys.argv", ["run.py"])
    rc = run.main(["--workload", "opt66b.decode", "--seed", "1",
                   "--seconds", "1", "--trace", "0"])
    assert rc != 0
    assert capsys.readouterr().out.strip() == ""
