"""Bench emission contract: the FINAL merged-output line is ONE record.

Whoever drives a crucible (``benchmarks/production_day.py``,
``benchmarks/rlhf_chaos.py``) captures stdout+stderr MERGED and parses
the LAST line as its record.  These tests drive real subprocesses with
merged streams through ``ray_tpu._private.bench_emit``, covering both
leak classes: stderr interleaving after the record, and failures
exiting with a traceback instead of a record.
"""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_merged(source: str, tmp_path, env_extra=None, timeout=120):
    path = tmp_path / "bench_stub.py"
    path.write_text(source)
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env.setdefault("JAX_PLATFORMS", "cpu")
    env.update(env_extra or {})
    return subprocess.run(
        [sys.executable, str(path)], stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT,  # merged, like the harness capture
        text=True, env=env, cwd=REPO, timeout=timeout)


def _last_line_record(proc):
    lines = [ln for ln in proc.stdout.strip().splitlines() if ln.strip()]
    assert lines, proc.stdout
    return json.loads(lines[-1])


def test_final_record_is_last_despite_stderr_noise(tmp_path):
    """stderr chatter written right before emission (the XLA-warning
    pattern) must land BEFORE the record in the merged capture."""
    proc = _run_merged("""
import sys
from ray_tpu._private.bench_emit import emit_final_record, emit_record_line

sys.stderr.write("WARNING: involuntary full rematerialization blah\\n")
print("human progress line")
emit_record_line({"config": "intermediate", "value": 1})
sys.stderr.write("WARNING: one more, unflushed right before the record")
emit_final_record({"metric": "stub_metric", "value": 42.0, "unit": "x"})
""", tmp_path)
    assert proc.returncode == 0, proc.stdout
    rec = _last_line_record(proc)
    assert rec == {"metric": "stub_metric", "value": 42.0, "unit": "x"}


def test_guard_emits_error_record_when_body_dies(tmp_path):
    """A crash inside the guard still ends with a parseable record (and
    a traceback BEFORE it, on the merged stream), at rc 1."""
    proc = _run_merged("""
from ray_tpu._private.bench_emit import final_record_guard

with final_record_guard("stub_metric", detail={"scope": "t"}) as out:
    raise AssertionError("bench section exploded")
""", tmp_path)
    assert proc.returncode == 1
    rec = _last_line_record(proc)
    assert rec["metric"] == "stub_metric"
    assert rec["value"] == 0.0
    assert "bench section exploded" in rec["detail"]["error"]
    assert "Traceback" in proc.stdout  # the diagnosis is not swallowed


def test_guard_emits_error_record_when_no_record_set(tmp_path):
    proc = _run_merged("""
from ray_tpu._private.bench_emit import final_record_guard

with final_record_guard("stub_metric") as out:
    pass  # body forgot out["record"]
""", tmp_path)
    assert proc.returncode == 0
    rec = _last_line_record(proc)
    assert rec["value"] == 0.0
    assert "no record" in rec["detail"]["error"]
