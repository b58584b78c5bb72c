"""chip_smoke.py: refuses to run off the chip, and its one-chip phases run
end to end here at a reduced size (CPU, interpret-mode Pallas)."""

import importlib.util
import os
import shutil
import subprocess
import sys

import pytest

from repro.configs import get_config

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(REPO, "chip_smoke.py")


def _run(script, cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu", JAX_COMPILATION_CACHE_DIR=os.path.join(cwd, "jax_cache"))
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, script], cwd=cwd, env=env, timeout=120,
                          capture_output=True, text=True)


@pytest.mark.parametrize("where", ["checkout", "alone"])
def test_refuses_without_tpu_or_program(tmp_path, where):
    """On a CPU backend, and in a directory holding nothing of the repo
    but the script, it exits non-zero and prints no result."""
    script = SCRIPT
    if where == "alone":
        script = str(tmp_path / "chip_smoke.py")
        shutil.copy(SCRIPT, script)
    r = _run(script, cwd=str(tmp_path))
    assert r.returncode != 0
    assert '"ok"' not in r.stdout


def test_one_chip_phases_at_reduced_size(tmp_path, monkeypatch):
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    cs = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(cs)
    monkeypatch.setattr(cs, "SEQ", 64)
    monkeypatch.setattr(cs, "IMAGE_BATCH", 32)
    cfg = get_config("olmo_1b").reduced().with_(
        param_dtype="bfloat16", compute_dtype="bfloat16", vocab=512
    )
    cs.run_one_chip(cfg, str(tmp_path), seed=0)
