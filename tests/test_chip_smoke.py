"""``chip_smoke.py`` off the chip: it refuses to run without a TPU, and its
operator-mix phase (at a small size) holds against the pyvm oracle."""

import os
import shutil
import subprocess
import sys
from pathlib import Path

import chip_smoke

SCRIPT = Path(chip_smoke.__file__)


def _run(script: Path, cwd: Path) -> subprocess.CompletedProcess:
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, str(script)], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=120)


def test_refuses_without_tpu():
    r = _run(SCRIPT, SCRIPT.parent)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
    assert "no TPU" in r.stderr


def test_refuses_alone_in_a_directory(tmp_path):
    alone = tmp_path / "chip_smoke.py"
    shutil.copy(SCRIPT, alone)
    r = _run(alone, tmp_path)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout


def test_operator_mix_matches_pyvm():
    check = chip_smoke.Checks()
    mix = chip_smoke.build_mix(1, graph_nodes=1024,
                               posts_per_op=chip_smoke.MIN_CHECKED_PER_OP,
                               seed=0)
    mem0 = mix.ep.host_mem().copy()
    res = chip_smoke.run_stream(mix, "single")
    oracle, oracle_mem = chip_smoke.oracle_replay(mix, mem0,
                                                  res.completions)
    chip_smoke.check_stream(check, "cpu", mix, res, oracle, oracle_mem)
    assert check.failed == []
