"""``chip_smoke.py`` rehearsed on the CPU at SF 0.005: its phases must
give the oracle's answers through the same checks it applies on the
chip, and its entry point must refuse to report on anything but a TPU."""
import importlib.util
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_one_chip_phases_match_oracle(smoke, capsys):
    failures = smoke.run_one_chip(0.005, seed=0)
    out = capsys.readouterr().out
    assert failures == [], failures
    for phase in ("fused", "auto", "shared"):
        assert out.count(f"phase={phase} query=") == 13, phase
    assert "wave_sizes=[13]" in out
    assert " FAIL " not in out


def test_four_chip_phase_on_virtual_devices():
    """``--chips 4``'s phase in a child process that sees four virtual
    CPU devices (the device count is fixed when JAX starts)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join(
                   [str(ROOT / "src"), os.environ.get("PYTHONPATH", "")]))
    code = ("import sys, importlib.util as u; "
            f"s = u.spec_from_file_location('s', {str(ROOT / 'chip_smoke.py')!r}); "
            "m = u.module_from_spec(s); s.loader.exec_module(m); "
            "f = m.run_four_chips(0.005, 0, 4); print('FAILURES', f); "
            "sys.exit(1 if f else 0)")
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    assert proc.stdout.count("phase=sharded query=") == 13
    assert proc.stdout.count("identical=True") == 13


@pytest.mark.parametrize("argv", [[], ["--chips", "4"]])
def test_entry_point_refuses_non_tpu(smoke, capsys, argv):
    assert smoke.main(argv) != 0
    captured = capsys.readouterr()
    assert '"ok"' not in captured.out
    assert "needs" in captured.err
