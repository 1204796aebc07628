"""``chip_smoke.py`` rehearsed on the CPU at tiny sizes.

The script itself refuses to run without a TPU; its phases are plain
functions of a shape, so these drive them here with the same checks and
tolerances. The mesh phase runs on four virtual CPU devices in a
subprocess, which has its own device count.
"""
import os
import subprocess
import sys
import textwrap

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402


@pytest.fixture(scope="module")
def compiles():
    return chip_smoke.Compiles()


@pytest.mark.parametrize("phase,kwargs", [
    (chip_smoke.phase_epsilon, dict(shape=(4000, 64), rounds=4, block=2)),
    (chip_smoke.phase_lasso, dict(shape=(1000, 100))),
])
def test_phase_passes_its_checks_at_tiny_size(compiles, phase, kwargs):
    phase(0, compiles, **kwargs)


def test_main_refuses_a_host_without_tpu(capsys):
    assert chip_smoke.main([]) != 0
    captured = capsys.readouterr()
    assert "no TPU found" in captured.err
    assert '"ok"' not in captured.out


MESH_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import chip_smoke
    chip_smoke.phase_mesh(0, chip_smoke.Compiles(), chips=4,
                          shape=(4000, 64), rounds=4, block=2)
    print("MESH_PHASE_OK")
""")


def test_mesh_phase_on_four_virtual_devices():
    env = dict(os.environ, PYTHONPATH=os.pathsep.join(
        [os.path.join(ROOT, "src"), ROOT]), JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-c", MESH_SCRIPT], env=env,
                         capture_output=True, text=True, timeout=600,
                         cwd=ROOT)
    assert "MESH_PHASE_OK" in out.stdout, out.stdout + "\n" + out.stderr
    assert "a_parts shards sit on 4 distinct devices" in out.stdout
