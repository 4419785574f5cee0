"""chip_smoke.py must refuse to report success without a TPU: under
JAX_PLATFORMS=cpu, and from a directory that holds the script and
nothing else of the repo, it exits non-zero and prints no "ok"."""
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(ROOT, "chip_smoke.py")


@pytest.mark.parametrize("where", ["repo", "alone"])
def test_chip_smoke_fails_without_a_tpu(where, tmp_path):
    script = SCRIPT
    if where == "alone":
        script = str(tmp_path / "chip_smoke.py")
        shutil.copy(SCRIPT, script)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    p = subprocess.run([sys.executable, script], capture_output=True,
                       text=True, env=env, cwd=os.path.dirname(script),
                       timeout=300)
    assert p.returncode != 0
    assert '"ok"' not in p.stdout
    why = "no TPU" if where == "repo" else "not importable"
    assert why in p.stderr, p.stderr
