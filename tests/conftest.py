"""Test harness config: run all tests on a virtual 8-device CPU mesh.

Mirrors the survey's recommendation (SURVEY.md §4): the reference cannot
test multi-node in-repo; we can, by forcing
``xla_force_host_platform_device_count=8`` so shard_map-based distributed
tree learners run as real 8-way SPMD programs on CPU.
"""
import os

# FORCE cpu: the tests are CPU tests wherever they run (a chip belongs
# to one process, and the suite runs several workers).
# The env var alone is not enough when something imported jax first, so
# the platform is also forced via jax.config below.
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()
os.environ.setdefault("JAX_ENABLE_X64", "0")
# persistent compile cache: the suite re-traces identical programs each
# run.  Its place is the program's own (`utils/compile_cache.py`:
# JAX_COMPILATION_CACHE_DIR if set, else <checkout>/.jax_cache) — set
# on `import lightgbm_tpu`, shared by the xdist workers and by every
# worker process a test starts.

import jax  # noqa: E402  (must come after the env setup above)

jax.config.update("jax_platforms", "cpu")


def pytest_configure(config):
    # bench-shaped tests (minutes each on the CPU mesh) carry this
    # marker; default runs include them, `-m "not slow"` is the fast
    # loop (documented in README "Running the tests")
    config.addinivalue_line(
        "markers", "slow: bench-shaped test (minutes on the CPU mesh); "
        "deselect with -m 'not slow'")
