"""The served cell exp3_serve_c4 rehearsed on the CPU: a ScanServer in the
benchmark's process, two clients in processes of their own that never
load JAX. Run as the driver runs a cell, from a copy of the benchmark whose
manifest holds the cell (benchmark_testing.copy_with_serve_cell)."""
import pytest

from benchmark_testing import (check_result, copy_with_serve_cell,
                               rehearse_copy)

pytestmark = pytest.mark.jax
CELL = "exp3_serve_c4"


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_prints_the_contract(tmp_path, trace):
    spec = copy_with_serve_cell(tmp_path)
    result, lines = rehearse_copy(tmp_path, CELL, trace)
    check_result(CELL, trace, result, spec)
    (warm,) = [line for line in lines if line.get("phase") == "warm_up"]
    assert warm["requests"] == 2  # each file of the corpus once
    if not trace:
        latency = {line["metric"]: line for line in lines
                   if line.get("metric", "").endswith("_p95_s")}
        assert set(latency) == {"request_p95_s", "first_batch_p95_s"}
        assert all(line["samples"] == result["attempted"]
                   for line in latency.values())
        assert (result["metrics"]["first_batch_p95_s"]["value"]
                <= result["metrics"]["request_p95_s"]["value"])


def test_the_client_loads_no_jax():
    """What keeps a client off the chip: the serve client imports no JAX,
    so a client process cannot take the device from the server's."""
    import subprocess
    import sys

    from benchmark_testing import REPO

    code = ("import sys; sys.path.insert(0, %r); "
            "import benchmark.drivers.serve_client; "
            "from cobrix_tpu.serve import stream_scan; "
            "import pyarrow; print('jax' in sys.modules)" % REPO)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=120)
    assert out.stdout.strip() == "False", out.stderr
