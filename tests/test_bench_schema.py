"""Golden-schema test for ``benchmarks/run.py --json``: the emitted JSON is
the machine-readable trajectory format (BENCH_*.json points), so its shape
must not silently drift."""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[1]


def test_run_json_golden_schema(tmp_path):
    out = tmp_path / "bench.json"
    env = dict(os.environ)
    env["PYTHONPATH"] = str(REPO / "src") + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
    proc = subprocess.run(
        [sys.executable, "-m", "benchmarks.run", "selector",
         "--json", str(out)],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-2000:]
    # stdout stays the CSV contract
    header, *rows = [ln for ln in proc.stdout.splitlines() if ln.strip()]
    assert header == "name,us_per_call,derived"
    assert rows

    data = json.loads(out.read_text())
    assert data, "JSON output must not be empty"
    for name, rec in data.items():
        assert isinstance(name, str) and name
        assert set(rec) == {"us", "derived"}, f"schema drift in {name}: {rec}"
        assert isinstance(rec["us"], float) and rec["us"] >= 0.0
        assert isinstance(rec["derived"], str)
    # per-module elapsed rows are part of the trajectory format
    assert "selector/elapsed" in data
    # the selector rows carry the serving telemetry the trajectory tracks
    req = data["selector/request"]["derived"]
    stats = dict(kv.split("=") for kv in req.split(";"))
    assert {"hit_rate", "fallback", "buckets", "within10"} <= set(stats)
    assert 0.0 <= float(stats["hit_rate"]) <= 1.0
    assert 0.0 <= float(stats["fallback"]) <= 1.0
    assert float(stats["within10"]) >= 0.8
    assert "selector/full_sweep_select" in data
    # every JSON record mirrors a CSV row with the same microseconds value
    csv_by_name = {r.split(",")[0]: float(r.split(",")[1]) for r in rows}
    for name, rec in data.items():
        assert name in csv_by_name
        assert rec["us"] == pytest.approx(csv_by_name[name], abs=1.0)


def test_run_exits_nonzero_when_a_module_raises(monkeypatch, capsys):
    """A raising module keeps its ERROR row, the others still run, and the
    harness exits non-zero."""
    import types
    from benchmarks import run

    def boom():
        raise RuntimeError("kernel refused")

    ok = types.SimpleNamespace(run=lambda: [("ok/row", 1.0, "-")])
    monkeypatch.setattr(run, "MODULES", [
        ("bad", types.SimpleNamespace(run=boom)), ("good", ok)])
    with pytest.raises(SystemExit) as exc:
        run.main([])
    assert exc.value.code not in (0, None)
    out = capsys.readouterr().out
    assert "bad/ERROR,0.0,RuntimeError:kernel refused" in out
    assert "ok/row,1.0,-" in out
