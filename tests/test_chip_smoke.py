"""The chip smoke script's contract off the chip, and the compile cache
helper the entry points share."""
import os
import pathlib
import shutil
import subprocess
import sys

import jax

from repro.launch import compile_cache

ROOT = pathlib.Path(__file__).resolve().parents[1]


def _run_smoke(script, cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, str(script)], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=300,
    )


class TestChipSmokeOffChip:
    def test_refuses_a_host_without_tpu(self):
        r = _run_smoke(ROOT / "chip_smoke.py", ROOT)
        assert r.returncode != 0
        assert "no TPU found" in r.stderr
        assert '"ok"' not in r.stdout

    def test_fails_outside_a_checkout(self, tmp_path):
        shutil.copy(ROOT / "chip_smoke.py", tmp_path)
        r = _run_smoke(tmp_path / "chip_smoke.py", tmp_path)
        assert r.returncode != 0
        assert '"ok"' not in r.stdout


class TestCompileCache:
    def _updates(self, monkeypatch):
        calls = []
        monkeypatch.setattr(jax.config, "update", lambda k, v: calls.append((k, v)))
        return calls

    def test_environment_variable_wins(self, monkeypatch, tmp_path):
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        calls = self._updates(monkeypatch)
        assert compile_cache.enable() == str(tmp_path)
        assert calls == []  # JAX reads the variable itself

    def test_default_is_fixed_under_the_checkout(self, monkeypatch):
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        calls = self._updates(monkeypatch)
        path = compile_cache.enable()
        assert path == str(ROOT / ".jax_cache")
        assert calls == [("jax_compilation_cache_dir", path)]
        assert ".jax_cache/" in (ROOT / ".gitignore").read_text().split()
