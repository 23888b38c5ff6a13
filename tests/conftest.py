import os
import sys

# Tests run on the CPU, on a virtual 8-device mesh (fast, deterministic,
# exercises multi-chip sharding without hardware). The chip is reached
# through chip_smoke.py, never through this suite.
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

# The suite is dominated by jit compiles of the pallas interpret-mode
# programs, so every compile is worth keeping (min compile time 0). The
# cpu_aot_loader logs a spurious machine-feature-order mismatch error on
# every load — suppress C++ logging in tests.
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
os.environ.setdefault("TF_CPP_MIN_LOG_LEVEL", "3")

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

REFERENCE_DATA = "/root/reference/data"


def pytest_configure(config):
    config.addinivalue_line("markers", "jax: test drives a jax backend")
    config.addinivalue_line(
        "markers",
        "slow: large fuzz/sweep loops excluded from tier-1 (-m 'not slow')")
    import jax

    from cobrix_tpu.ops.device import ensure_compile_cache

    jax.config.update("jax_platforms", "cpu")
    # the same placement rule as the product: JAX_COMPILATION_CACHE_DIR if
    # the caller set it, <checkout>/.jax_cache otherwise
    ensure_compile_cache()
