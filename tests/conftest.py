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


# tests/benchmark/ belongs to the benchmark: no later PR may edit a file
# of it, and a PR's new `per_layer` entries go to the END of the list (the
# driver reads one put first or in the middle as a change to what was
# there). Two lines of PR 36's test assert that its own metric IS the
# last of that list, which the next appended metric (PR 38) made false and
# no later PR can make true again. What the test's other lines assert is
# held by test_benchmark_rehearsal_hier.py::test_pr36s_entry_stays_as_its_
# own_test_pinned_it, beside the hash of the parent's whole manifest. The
# two positional lines are a `benchmark` PR's to drop (PERF.md, section
# 7); strict, so that the marker fails and goes the day they are dropped
PINNED_AS_LAST = ("tests/benchmark/test_benchmark_preframed_share.py"
                  "::test_the_manifest_declares_the_metric")


def pytest_collection_modifyitems(config, items):
    import pytest

    for item in items:
        if item.nodeid.endswith(PINNED_AS_LAST):
            item.add_marker(pytest.mark.xfail(
                reason="pins preframed_shard_share as the last per_layer "
                       "metric; metrics were appended behind it",
                raises=AssertionError, strict=True))
