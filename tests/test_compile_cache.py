"""Where the persistent compile cache lives (utils/compile_cache.py):
`JAX_COMPILATION_CACHE_DIR` if set — and then no directory is set in
code — else the fixed `<checkout>/.jax_cache`.  Checked in a fresh
interpreter, because the choice is made once, on `import lightgbm_tpu`."""
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_PROBE = ("import jax, lightgbm_tpu; "
          "print('DIR=' + str(jax.config.jax_compilation_cache_dir))")


@pytest.mark.parametrize("env_dir", [None, "elsewhere"])
def test_compile_cache_directory(tmp_path, env_dir):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    want = os.path.join(REPO, ".jax_cache")
    if env_dir:
        want = env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / env_dir)
    r = subprocess.run([sys.executable, "-c", _PROBE], env=env,
                       cwd=str(tmp_path), capture_output=True, text=True,
                       timeout=120)
    assert r.returncode == 0, r.stderr[-2000:]
    assert f"DIR={want}" in r.stdout.splitlines()

