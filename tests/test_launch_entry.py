"""Launch entry points: where the compile cache goes, the serve CLI's
``--full``, and a supervisor that stays off JAX so its trainer children
can take the accelerator."""
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parent.parent
SRC = str(ROOT / "src")


def _python(code: str, timeout: float = 300, **env_extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=SRC, **env_extra)
    if "JAX_COMPILATION_CACHE_DIR" not in env_extra:
        env.pop("JAX_COMPILATION_CACHE_DIR", None)
    return subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                          env=env, capture_output=True, text=True,
                          timeout=timeout)


@pytest.mark.parametrize("from_env", [False, True])
def test_compile_cache_location(tmp_path, from_env):
    """A set JAX_COMPILATION_CACHE_DIR is left to JAX; otherwise the cache
    goes to the checkout's git-ignored .jax_cache."""
    extra = ({"JAX_COMPILATION_CACHE_DIR": str(tmp_path / "cache")}
             if from_env else {})
    out = _python("""
        import jax
        from repro.launch.compile_cache import use_compile_cache
        print(use_compile_cache())
        print(jax.config.jax_compilation_cache_dir)
    """, **extra)
    assert out.returncode == 0, out.stderr
    returned, in_effect = out.stdout.split()[-2:]
    want = str(tmp_path / "cache") if from_env else str(ROOT / ".jax_cache")
    assert returned == in_effect == want
    assert ".jax_cache/" in (ROOT / ".gitignore").read_text().splitlines()


def test_serve_cli_full_flag(monkeypatch, capsys):
    from repro.launch import serve as serve_mod

    seen = []
    monkeypatch.setattr(serve_mod, "serve",
                        lambda **kw: seen.append(kw) or {})
    monkeypatch.setattr(serve_mod, "use_compile_cache", lambda: None)
    serve_mod.main(["--arch", "mamba2-370m", "--full"])
    serve_mod.main(["--arch", "mamba2-370m"])
    capsys.readouterr()
    assert [(kw["arch"], kw["reduced"]) for kw in seen] == [
        ("mamba2-370m", False), ("mamba2-370m", True)]


def test_supervisor_parent_never_imports_jax(tmp_path):
    """A drill with a kill, a scrub and a restore probe between attempts:
    the supervisor's own process never loads JAX."""
    out = _python(f"""
        import sys
        from repro.launch.supervisor import Injection, Supervisor
        sup = Supervisor({str(tmp_path / "ckpt")!r},
                         run_dir={str(tmp_path / "run")!r},
                         arch="llama3.2-3b", steps=8, interval=2, batch=2,
                         seq_len=16, policy="full", seed=5,
                         injections=[Injection("kill", at_step=5)],
                         verify_restore=True, scrub_on_restart=True)
        report = sup.run()
        assert report["completed"], report
        (kill,) = report["interruptions"]
        assert kill["scrub"]["unrecoverable"] == 0, kill
        assert kill["restore_probe"]["step"] == kill["committed_step"] > 0
        loaded = sorted(m for m in sys.modules if m.split(".")[0] == "jax")
        assert not loaded, loaded
        print("OK")
    """, timeout=600)
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip().endswith("OK")
