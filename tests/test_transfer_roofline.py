"""Harness validation for scripts/transfer_roofline.py (round-5 lesson:
dry-run hardware harnesses BEFORE the window — harness bugs waste it).
CPU numbers are meaningless; the contract (fields, merge mode, fed
ratio arithmetic) is what's under test."""

import json
import os
import subprocess
import sys

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(*args):
    out = subprocess.run(
        [sys.executable, os.path.join(_ROOT, "scripts/transfer_roofline.py")]
        + list(args),
        capture_output=True, text=True, timeout=300, cwd=_ROOT)
    assert out.returncode == 0, out.stdout[-1500:] + out.stderr[-1500:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_measure_contract(tmp_path):
    rec = _run("--sizes-mb", "0.2,0.5", "--reps", "1")
    assert rec["platform"] == "cpu"
    assert rec["dispatch_latency_ms"] > 0
    assert len(rec["h2d_MBps"]) == 2 and len(rec["d2h_MBps"]) == 2
    assert rec["h2d_ceiling_MBps"] == max(rec["h2d_MBps"].values())
    assert rec["h2d_overlap_ratio"] > 0


def test_offline_fed_merge(tmp_path):
    """--from merges fed_frac_of_wire without touching any device."""
    wire = {"platform": "tpu", "h2d_ceiling_MBps": 10.0}
    bench = {"cluster_fed_shm": 63.16, "cluster_fed_queue": None}
    wire_p = tmp_path / "roofline.json"
    bench_p = tmp_path / "bench.json"
    wire_p.write_text(json.dumps(wire))
    bench_p.write_text(json.dumps(bench))
    rec = _run("--from", str(wire_p), "--fed-json", str(bench_p))
    # 63.16 img/s x 150528 B = 9.51 MB/s over a 10 MB/s wire
    assert rec["fed_effective_MBps"] == 9.51
    assert rec["fed_frac_of_wire"] == 0.951
    assert rec["fed_images_per_sec"] == 63.16


def test_offline_merge_reports_missing_fed(tmp_path):
    wire_p = tmp_path / "roofline.json"
    wire_p.write_text(json.dumps({"h2d_ceiling_MBps": 10.0}))
    bench_p = tmp_path / "bench.json"
    bench_p.write_text(json.dumps({"value": 0.0, "error": "no device"}))
    rec = _run("--from", str(wire_p), "--fed-json", str(bench_p))
    assert "fed_json_error" in rec
    assert "fed_frac_of_wire" not in rec


def test_offline_merge_survives_truncated_wire_artifact(tmp_path):
    """A timeout-killed roofline stage leaves an empty artifact; the
    merge must emit a valid JSON record, not a traceback."""
    wire_p = tmp_path / "roofline.json"
    wire_p.write_text("")  # tee truncated it
    bench_p = tmp_path / "bench.json"
    bench_p.write_text(json.dumps({"cluster_fed_shm": 63.16}))
    rec = _run("--from", str(wire_p), "--fed-json", str(bench_p))
    assert "from_error" in rec
    assert "fed_frac_of_wire" not in rec
