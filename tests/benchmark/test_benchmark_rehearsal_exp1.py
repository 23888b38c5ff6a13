"""The cell exp1_read rehearsed on the CPU. A file of its own: the exp1
program takes about a minute to compile in interpret mode when the
compile cache is cold, and xdist gives each file to one worker."""
import pytest

from benchmark_testing import check_result, rehearse

pytestmark = pytest.mark.jax


def test_rehearsal_prints_the_contract(capsys):
    cell = "exp1_read"
    result, lines = rehearse(capsys, cell, trace=0)
    check_result(cell, 0, result)
    (warm,) = [line for line in lines if line.get("phase") == "warm_up"]
    assert list(warm["launches"]) == ["256x1493"]
    # the same process again, traced: nothing left to compile
    result, lines = rehearse(capsys, cell, trace=1)
    check_result(cell, 1, result)
    assert result["metrics"]["warm_compile_s"]["value"] >= 0
