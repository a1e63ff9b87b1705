"""chip_smoke.py refuses to run without a TPU, and its phases pass on the
CPU at the reduced config (the chip runs them at mamba2-370m full width)."""
import importlib.util
import json
import os
import shutil
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parent.parent
SCRIPT = ROOT / "chip_smoke.py"
SMALL = dict(reduced=True, batch=2, seq_len=64, serve_batch=2, prompt_len=16,
             new_tokens=4)


def _cpu_env(**extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **extra)
    env.pop("PYTHONPATH", None)
    return env


def _ok_line(stdout: str) -> bool:
    return any('"ok"' in line for line in stdout.splitlines())


def _phase(stdout: str, tag: str) -> dict:
    (line,) = [l for l in stdout.splitlines() if l.startswith(f"[{tag}] ")]
    return json.loads(line[len(tag) + 3:])


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    sys.modules["chip_smoke"] = mod  # its dataclass resolves annotations
    spec.loader.exec_module(mod)
    return mod


def test_refuses_without_a_tpu():
    out = subprocess.run([sys.executable, str(SCRIPT)], cwd=ROOT,
                         env=_cpu_env(), capture_output=True, text=True,
                         timeout=300)
    assert out.returncode != 0
    assert not _ok_line(out.stdout)
    assert "no TPU" in out.stderr


def test_fails_outside_the_checkout(tmp_path):
    shutil.copy(SCRIPT, tmp_path / "chip_smoke.py")
    out = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                         env=_cpu_env(), capture_output=True, text=True,
                         timeout=300)
    assert out.returncode != 0
    assert not _ok_line(out.stdout)


def test_one_chip_phases_on_reduced_config(chip_smoke, tmp_path, capsys):
    chip_smoke.one_chip(chip_smoke.Smoke(**SMALL), tmp_path, interpret=True,
                        require_pallas=False)
    out = capsys.readouterr().out
    assert _phase(out, "b resume")["bit_exact"]
    written = _phase(out, "c parity")["written_bytes"]
    assert len(written) == 3 and max(written[1:]) < written[0]
    overlap = _phase(out, "d overlap")
    # both overlapped events are accounted, the first one committed by
    # the second one's begin
    assert [e["step"] for e in overlap["events"]] == [2, 4]
    assert overlap["restores_equal_sync_save"]
    fps = _phase(out, "e fingerprints")
    assert fps["path"] == "pallas" and fps["equal_to_oracle"]
    serve = _phase(out, "f serve")
    assert serve["swap_equals_cold"] and serve["swap"]["step_to"] == 4
    # every checkpoint root was deleted
    assert list(tmp_path.iterdir()) == []


def test_four_chip_phase_on_virtual_devices(tmp_path):
    code = textwrap.dedent(f"""
        import sys
        from pathlib import Path
        sys.path.insert(0, {str(ROOT)!r})
        import chip_smoke as cs
        cs.four_chips(cs.Smoke(reduced=True), Path({str(tmp_path)!r}))
    """)
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        env=_cpu_env(XLA_FLAGS="--xla_force_host_platform_device_count=4"),
        timeout=600)
    assert out.returncode == 0, out.stderr
    restore = _phase(out.stdout, "resharded restore")
    assert restore["bit_exact"] and len(restore["participants"]) == 4
    assert all(p["bytes_read"] < restore["full_restore_bytes"]
               for p in restore["participants"])
