"""``chip_smoke.py`` off the chip: every phase rehearses at tiny sizes on the
CPU (one device, and the sharded path on four virtual devices), and the
plain smoke refuses to run, printing no result line, without a TPU or
without the rest of the repository.  Each case runs the script as its own
process, as a user would."""
import os
import shutil
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")
RESULT = '{"ok": true'


def _run(args, cwd, tmp_path, devices=1):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jax_cache"))
    env.pop("PYTHONPATH", None)  # the script finds the repo on its own
    if devices > 1:
        env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") + " --xla_force_host_"
                            f"platform_device_count={devices}").strip()
    return subprocess.run([sys.executable, *args], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=600)


@pytest.mark.parametrize("extra,devices,phases", [
    ([], 1, ("planner", "scheduler", "kernels", "physical")),
    (["--four-chips"], 4, ("four_chips",)),
])
def test_cpu_rehearsal_runs_every_phase(tmp_path, extra, devices, phases):
    proc = _run([SMOKE, "--cpu-rehearsal", *extra], REPO, tmp_path, devices)
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = proc.stdout.splitlines()
    for name in phases:
        assert any(ln.startswith(f"[smoke] {name}: wall_s=") for ln in lines), \
            (name, proc.stdout[-3000:])
    assert lines[-1] == "[smoke] CPU rehearsal passed; no device result"
    assert RESULT not in proc.stdout


def test_refuses_without_tpu(tmp_path):
    proc = _run([SMOKE], REPO, tmp_path)
    assert proc.returncode != 0
    assert "JAX found no TPU" in proc.stderr
    assert RESULT not in proc.stdout


def test_fails_without_the_repository(tmp_path):
    alone = tmp_path / "alone"
    alone.mkdir()
    shutil.copy(SMOKE, alone)
    proc = _run([str(alone / "chip_smoke.py")], alone, tmp_path)
    assert proc.returncode != 0
    assert "No module named 'repro'" in proc.stderr
    assert RESULT not in proc.stdout
