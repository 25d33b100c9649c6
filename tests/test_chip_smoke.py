"""chip_smoke.py refuses to run anywhere but on a TPU: there is no CPU
fallback, and a copy of the script without the repo's sources fails too.
In both cases it exits non-zero and prints no result line."""

import json
import os
import shutil
import subprocess
import sys

ROOT = os.path.join(os.path.dirname(__file__), "..")
SCRIPT = os.path.join(ROOT, "chip_smoke.py")


def _run(script, cwd):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run([sys.executable, script], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def _no_result(r):
    lines = r.stdout.strip().splitlines()
    if not lines:
        return True
    try:
        return json.loads(lines[-1]).get("ok") is not True
    except ValueError:
        return True


def test_chip_smoke_fails_on_cpu_and_names_the_platform():
    r = _run(SCRIPT, ROOT)
    assert r.returncode != 0, r.stdout
    assert "found platform 'cpu'" in r.stderr, r.stderr
    assert "platform=cpu" in r.stdout, r.stdout
    assert _no_result(r), r.stdout


def test_chip_smoke_fails_without_the_repo(tmp_path):
    lone = shutil.copy(SCRIPT, tmp_path / "chip_smoke.py")
    r = _run(str(lone), str(tmp_path))
    assert r.returncode != 0, r.stdout
    assert _no_result(r), r.stdout
