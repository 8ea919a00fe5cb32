"""`chip_smoke.py` and the compile-cache helper, run as subprocesses: this
process never imports JAX for them, and a persistent cache turned on here
would outlive the test."""
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(args, env, timeout=300):
    return subprocess.run([sys.executable, *args], capture_output=True,
                          text=True, timeout=timeout, cwd=ROOT, env=env)


def test_chip_smoke_refuses_cpu():
    """No TPU: non-zero exit before building anything, and no ok line."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    out = _run(["chip_smoke.py"], env)
    assert out.returncode != 0
    assert '"ok": true' not in out.stdout
    assert "no TPU" in out.stderr


@pytest.mark.parametrize("placed", [True, False])
def test_compile_cache_dir(tmp_path, placed):
    """JAX_COMPILATION_CACHE_DIR, when set, is where the cache goes and
    nothing overrides it; otherwise the fixed, git-ignored
    `<repo>/.jax_cache`."""
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    if placed:
        env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path)
    code = ("import json, jax; from repro.launch import compile_cache; "
            "d = compile_cache.enable(); "
            "print(json.dumps([d, jax.config.jax_compilation_cache_dir]))")
    out = _run(["-c", code], env)
    assert out.returncode == 0, out.stderr[-2000:]
    got, configured = json.loads(out.stdout.strip().splitlines()[-1])
    want = str(tmp_path) if placed else os.path.join(ROOT, ".jax_cache")
    assert got == configured == want
    with open(os.path.join(ROOT, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()
