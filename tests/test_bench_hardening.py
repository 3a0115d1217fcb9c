"""bench.py hardening (ROADMAP item 4a): per-phase persistence — every
completed phase's record lands in BENCH_partial.json the moment the
phase finishes, so a mid-run wedge/kill of the parent still leaves every
completed phase on disk — plus the cheap smoke probe and the shared
compilation cache wiring. Also: chip_smoke.py refuses to pass off the
chip."""

import json
import os
import signal
import subprocess
import sys
import time

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
import bench  # noqa: E402


def _read_partial(path):
    with open(path) as f:
        return [json.loads(line) for line in f if line.strip()]


def test_persist_phase_appends_jsonl(tmp_path, monkeypatch):
    p = tmp_path / "BENCH_partial.json"
    monkeypatch.setattr(bench, "PARTIAL_PATH", str(p))
    bench._persist_phase("cpu_standin", {"value": 1.5, "unit": "rows/s"})
    bench._persist_phase("tpu_attempt1", {"rc": "timeout"})
    recs = _read_partial(p)
    assert [r["phase"] for r in recs] == ["cpu_standin", "tpu_attempt1"]
    assert recs[0]["record"]["value"] == 1.5
    assert all("ts" in r for r in recs)


def test_completed_phase_is_on_disk_before_run_ends(tmp_path, monkeypatch):
    """The parent persists each phase AS IT COMPLETES — the file holds the
    record even though no later phase (and no final emit) ever ran, which
    is exactly the mid-run-kill scenario."""
    p = tmp_path / "BENCH_partial.json"
    monkeypatch.setattr(bench, "PARTIAL_PATH", str(p))
    env = {"JAX_PLATFORMS": "cpu"}
    rec = bench._spawn_phase("cpu_probe", env, ["--probe"],
                             timeout=bench.PROBE_TIMEOUT)
    assert rec["probe"] == "ok" and rec["backend"] == "cpu"
    # ... parent is "killed" here; the completed phase already persisted
    recs = _read_partial(p)
    assert recs[-1]["phase"] == "cpu_probe"
    assert recs[-1]["record"]["probe"] == "ok"


def test_failed_phase_rc_also_persisted(tmp_path, monkeypatch):
    p = tmp_path / "BENCH_partial.json"
    monkeypatch.setattr(bench, "PARTIAL_PATH", str(p))
    env = {"JAX_PLATFORMS": "definitely_not_a_backend"}
    with pytest.raises(RuntimeError):
        bench._spawn_phase("tpu_probe1", env, ["--probe"],
                           timeout=bench.PROBE_TIMEOUT)
    recs = _read_partial(p)
    assert recs[-1]["phase"] == "tpu_probe1"
    assert recs[-1]["record"]["rc"] != 0       # failure attributed on disk


def test_tpu_cache_env_is_stable_across_attempts(monkeypatch):
    """The one compile-cache resolver (common/compile_cache.py, loaded by
    path in bench.py's JAX-free parent): the environment wins; unset, the
    cache is ONE fixed path inside the checkout, identical across calls
    (the path is part of the cache key — a moving directory never hits)."""
    from risingwave_tpu.common import compile_cache

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    env1 = bench._tpu_cache_env()
    env2 = bench._tpu_cache_env()
    assert env1["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] == "0"
    assert env1["JAX_COMPILATION_CACHE_DIR"] \
        == env2["JAX_COMPILATION_CACHE_DIR"] \
        == compile_cache.compile_cache_dir() \
        == os.path.join(repo, ".jax_cache")
    # resolving does not leak the default into the environment
    assert "JAX_COMPILATION_CACHE_DIR" not in os.environ
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/tmp/fixed_cache")
    assert bench._tpu_cache_env()["JAX_COMPILATION_CACHE_DIR"] \
        == "/tmp/fixed_cache"
    assert compile_cache.compile_cache_dir() == "/tmp/fixed_cache"
    assert compile_cache.export_compile_cache_env() == "/tmp/fixed_cache"


def _run_chip_smoke(cwd, script):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    res = subprocess.run([sys.executable, script], cwd=cwd, env=env,
                         capture_output=True, text=True, timeout=300)
    lines = res.stdout.strip().splitlines()
    assert lines, res.stderr[-2000:]
    return res.returncode, json.loads(lines[-1])


def test_chip_smoke_fails_without_a_tpu():
    """`JAX_PLATFORMS=cpu python chip_smoke.py`: a run that finds no TPU
    ends non-zero and its last line says ``"ok": false`` — a CPU run can
    never be read as a chip result."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    rc, last = _run_chip_smoke(repo, "chip_smoke.py")
    assert rc != 0
    assert last["ok"] is False
    assert last["device"]["platform"] == "cpu"
    assert "tpu" in last["error"]


def test_chip_smoke_fails_alone_in_a_directory(tmp_path):
    """The script alone, without the program beside it, fails too."""
    import shutil
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    shutil.copy(os.path.join(repo, "chip_smoke.py"), tmp_path)
    rc, last = _run_chip_smoke(str(tmp_path), "chip_smoke.py")
    assert rc != 0
    assert last["ok"] is False and last["device"] is None


@pytest.mark.slow
def test_kill_mid_run_leaves_partial(tmp_path):
    """End-to-end: run the real parent, SIGKILL it after the first phase
    record appears, verify BENCH_partial.json survives with that record.
    Slow (runs a real CPU measurement phase)."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    partial = os.path.join(repo, "BENCH_partial.json")
    proc = subprocess.Popen([sys.executable, "bench.py"], cwd=repo,
                            env=env, stdout=subprocess.PIPE,
                            stderr=subprocess.PIPE)
    try:
        deadline = time.time() + 1200
        while time.time() < deadline:
            if os.path.exists(partial) and os.path.getsize(partial) > 0:
                break
            if proc.poll() is not None:
                break
            time.sleep(2)
        else:
            pytest.fail("no phase completed within deadline")
    finally:
        if proc.poll() is None:
            proc.send_signal(signal.SIGKILL)
            proc.wait()
    recs = _read_partial(partial)
    assert len(recs) >= 1
    assert recs[0]["phase"] == "cpu_standin"
