"""The driver contract of bench.py and chip_smoke.py off the chip: config
selection via BENCH_CONFIGS, and no chip -> non-zero exit with no metric
or result line (a number from another platform is not a result)."""

import importlib.util
import json
import os
import subprocess
import sys

from scanner_tpu.util.jaxenv import cpu_only_env

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_bench():
    spec = importlib.util.spec_from_file_location(
        "bench_under_test", os.path.join(REPO, "bench.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_configs_selection(monkeypatch):
    bench = _load_bench()
    monkeypatch.setenv("BENCH_CONFIGS", "all")
    assert bench._configs() == [1, 2, 3, 4, 5, 6, 7]
    monkeypatch.setenv("BENCH_CONFIGS", "3,1")
    assert bench._configs() == [1, 3]
    monkeypatch.setenv("BENCH_CONFIGS", "")
    assert bench._configs() == [1, 3]  # falls back to the default


def _run_off_chip(script, tmp_path):
    return subprocess.run(
        [sys.executable, os.path.join(REPO, script)], env=cpu_only_env(),
        cwd=str(tmp_path), capture_output=True, text=True, timeout=120)


def _json_lines(out):
    found = []
    for line in out.splitlines():
        try:
            found.append(json.loads(line))
        except ValueError:
            pass
    return found


def test_bench_without_chip_exits_nonzero_no_metric(tmp_path):
    """No chip -> bench.py exits non-zero and prints no metric line: no
    CPU fallback, no replayed capture."""
    r = _run_off_chip("bench.py", tmp_path)
    assert r.returncode != 0, (r.stdout, r.stderr)
    assert not _json_lines(r.stdout), r.stdout
    assert "cpu" in r.stderr, r.stderr


def test_chip_smoke_without_chip_exits_nonzero(tmp_path):
    """chip_smoke.py under JAX_PLATFORMS=cpu refuses within seconds,
    naming the platform it found, before building or importing
    anything of the repo, and prints no result line."""
    so = os.path.join(REPO, "scanner_tpu", "video", "libscvid.so")
    mtime = os.path.getmtime(so) if os.path.exists(so) else None
    r = _run_off_chip("chip_smoke.py", tmp_path)
    assert r.returncode != 0, (r.stdout, r.stderr)
    assert not _json_lines(r.stdout), r.stdout
    assert "'cpu'" in r.stderr, r.stderr
    assert (os.path.getmtime(so) if os.path.exists(so) else None) == mtime
