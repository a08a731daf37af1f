"""chip_smoke.py off the chip: no chip -> non-zero exit with no result
line (a number from another platform is not a result)."""

import json
import os
import subprocess
import sys

from scanner_tpu.util.jaxenv import cpu_only_env

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _json_lines(out):
    found = []
    for line in out.splitlines():
        try:
            found.append(json.loads(line))
        except ValueError:
            pass
    return found


def test_chip_smoke_without_chip_exits_nonzero(tmp_path):
    """chip_smoke.py under JAX_PLATFORMS=cpu refuses within seconds,
    naming the platform it found, before building or importing
    anything of the repo, and prints no result line."""
    so = os.path.join(REPO, "scanner_tpu", "video", "libscvid.so")
    mtime = os.path.getmtime(so) if os.path.exists(so) else None
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        env=cpu_only_env(), cwd=str(tmp_path), capture_output=True,
        text=True, timeout=120)
    assert r.returncode != 0, (r.stdout, r.stderr)
    assert not _json_lines(r.stdout), r.stdout
    assert "'cpu'" in r.stderr, r.stderr
    assert (os.path.getmtime(so) if os.path.exists(so) else None) == mtime
