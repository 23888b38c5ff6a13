"""The cell exp3_read rehearsed on the CPU (tiny file, Pallas interpreted,
the device labelled cpu), and the rules of a run that need no chip to be
shown: the window, a failed scan, no result without a TPU."""
import os
import subprocess
import sys

import pytest

from benchmark_testing import REPO, check_result, rehearse

from benchmark import run
from benchmark.drivers import inprocess_scan
from benchmark.end_to_end import scan_mb_per_s
from benchmark.harness import BenchFault, Run, Tracer

pytestmark = pytest.mark.jax
CELL = "exp3_read"


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_prints_the_contract(capsys, trace):
    result, lines = rehearse(capsys, CELL, trace)
    check_result(CELL, trace, result)
    (window,) = [line for line in lines if line.get("phase") == "window"]
    (warm,) = [line for line in lines if line.get("phase") == "warm_up"]
    # the window launched only shapes the warm-up had launched
    assert set(window["launches"]) <= set(warm["launches"])
    (check,) = [line for line in lines if line.get("phase") == "check"]
    assert check["failures"] == [] and check["oracle_records_per_file"] > 0


def test_a_failed_scan_is_counted_not_fatal(capsys, monkeypatch):
    import cobrix_tpu

    real = cobrix_tpu.read_cobol
    calls = []

    def flaky(path, **options):
        if options.get("backend") == "pallas":
            calls.append(path)
            if len(calls) == 2:  # the window's first: the warm-up is 1
                raise OSError("injected: the input went away")
        return real(path, **options)

    monkeypatch.setattr(cobrix_tpu, "read_cobol", flaky)
    code = run.main(["--workload", CELL, "--seed", "5", "--seconds", "2.5",
                     "--trace", "0", "--rehearse"])
    import json

    result = json.loads(capsys.readouterr().out.splitlines()[-1])
    assert code == 0 and result["failed"] == 1
    assert result["attempted"] == len(calls) - 1 >= 2
    assert result["correct"] is True  # the tables that came are right


def fake_run(n_files=1):
    files = [{"path": f"f{i}", "bytes": 1000, "facts": {"records": 1}}
             for i in range(n_files)]
    fake = Run(cell={}, config={}, traffic={"callers": 1}, seed=1,
               seconds=0.3, trace=False, rehearse=True, workdir="",
               out_dir="", files=files)
    fake.tracer = Tracer(False, "")
    return fake


def test_nothing_starts_after_the_window_and_the_one_in_flight_counts(
        monkeypatch):
    import time

    driver = inprocess_scan.Driver(fake_run())

    def slow_scan(index, keep):
        sent = time.monotonic()
        time.sleep(0.11)
        return {"file": index, "bytes": 1000, "sent": sent,
                "done": time.monotonic(), "ok": True}

    monkeypatch.setattr(driver, "scan", slow_scan)
    window = driver.window(0.3)
    requests = window["requests"]
    assert 2 <= len(requests) <= 3  # 0.11 s each, a loaded host a little more
    assert all(r["sent"] - window["start"] < 0.3 for r in requests)
    assert requests[-1]["done"] - window["start"] > 0.3  # in flight: counted
    rate = scan_mb_per_s.read({"window": window})
    elapsed = requests[-1]["done"] - window["start"]
    assert rate == pytest.approx(1000 * len(requests) / 1e6 / elapsed)


def test_a_compile_inside_the_window_is_a_fault():
    fake = fake_run()
    warm = {"device": {"launches": {"8x8": 1}, "compiles": 1}}
    late = {"device": {"launches": {"8x8": 1, "4x8": 1}, "compiles": 1}}
    fake.record = {"warm": {"requests": [warm]},
                   "window": {"requests": [late]}}
    with pytest.raises(BenchFault, match=r"shapes the warm-up did not "
                                         r"launch: \['4x8'\]"):
        run.compiled_in_window(fake)


def run_cli(args, cwd=REPO, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", TF_CPP_MIN_LOG_LEVEL="3")
    env.update(env_extra or {})
    return subprocess.run([sys.executable, "benchmark/run.py", *args],
                          cwd=cwd, env=env, capture_output=True, text=True,
                          timeout=300)


def test_without_a_tpu_and_without_rehearse_there_is_no_result():
    proc = run_cli(["--workload", CELL, "--seed", "1", "--seconds", "1",
                    "--trace", "0"])
    assert proc.returncode != 0
    assert '"correct"' not in proc.stdout
    assert "needs 1 tpu chip(s)" in proc.stderr


def test_without_the_program_there_is_no_result(tmp_path):
    """A directory that holds only BENCHMARK.json and the benchmark's own
    paths: non-zero, no result."""
    import shutil

    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(REPO, "benchmark"), tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = run_cli(["--workload", CELL, "--seed", "1", "--seconds", "1",
                    "--trace", "0", "--rehearse"], cwd=str(tmp_path),
                   env_extra={"PYTHONPATH": ""})
    assert proc.returncode != 0 and '"correct"' not in proc.stdout


def test_validate_flag_passes_on_the_committed_manifest():
    proc = run_cli(["--validate"])
    assert proc.returncode == 0, proc.stdout
