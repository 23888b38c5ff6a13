"""Shared by the benchmark's tests: run a cell's rehearsal in this process
and hand back the lines it printed."""
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark import manifest, run  # noqa: E402

RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def rehearse(capsys, cell: str, trace: int, seconds: float = 1.0,
             seed: int = 2147483999):
    """(last line as a dict, the earlier JSON lines) of one rehearsal."""
    code = run.main(["--workload", cell, "--seed", str(seed), "--seconds",
                     str(seconds), "--trace", str(trace), "--rehearse"])
    assert code == 0
    lines = [json.loads(line)
             for line in capsys.readouterr().out.splitlines()
             if line.startswith("{")]
    return lines[-1], lines[:-1]


def declared(cell: str, kind: str, spec=None) -> dict:
    return {m["name"]: m for m in
            manifest.metrics_of(cell, spec or manifest.load(), kind)}


def check_result(cell: str, trace: int, result: dict, spec=None) -> None:
    """The last line's keys, and exactly the cell's declared metrics: all
    end-to-end ones untraced; traced, all per-layer ones but those that
    only a device trace gives, which a CPU rehearsal must leave out."""
    assert RESULT_KEYS <= set(result)
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] >= 1
    assert result["device"]["rehearsal"] is True
    assert result["device"]["platform"] == "cpu"
    want = declared(cell, "per_layer" if trace else "end_to_end", spec)
    if trace:
        want = {name: m for name, m in want.items()
                if m["source"] != "device_trace"}
        assert "breakdown" not in result
    assert set(result["metrics"]) == set(want)
    for name, metric in result["metrics"].items():
        assert metric["unit"] == want[name]["unit"]
        # seconds of compiling or queueing can be 0.0 in a warm process
        assert isinstance(metric["value"], float) and metric["value"] >= 0


SERVE_CELL = {
    "name": "exp3_serve_c4", "config": "exp3_multiseg_wide",
    "traffic": "served_closed_loop", "chips": 1,
    "why": "4 closed-loop clients in own processes, each a tenant, 16 files "
           "of 32 MiB from one ScanServer: pipelined engine, 16 MiB chunks, "
           "IPC framing; same bytes as exp3_read"}
SERVE_METRICS = {
    "end_to_end": [
        {"name": "request_p95_s", "unit": "s", "better": "lower",
         "bound": 0.25, "source": "host_clock"},
        {"name": "first_batch_p95_s", "unit": "s", "better": "lower",
         "bound": 0.25, "source": "host_clock"}],
    "per_layer": [
        {"name": "server_scan_s_per_gb", "unit": "s/GB", "better": "lower",
         "source": "program_span", "layer": "serve_session",
         "moves": "request_p95_s"},
        {"name": "queue_wait_p95_s", "unit": "s", "better": "lower",
         "source": "program_span", "layer": "serve_session",
         "moves": "first_batch_p95_s"}]}


def copy_with_serve_cell(root) -> dict:
    """A copy of the benchmark under `root` whose manifest holds the served
    cell: as committed where it is there, else added by manifest entries
    alone (its traffic file, driver and metric readers are in the tree).
    Returns the copy's manifest."""
    import copy
    import shutil

    shutil.copytree(os.path.join(REPO, "benchmark"),
                    os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    spec = copy.deepcopy(manifest.load())
    spec["paths"] = ["benchmark"]
    name = SERVE_CELL["name"]
    if name not in [cell["name"] for cell in spec["workloads"]]:
        spec["workloads"].append(dict(SERVE_CELL))
        for kind, metrics in SERVE_METRICS.items():
            spec[kind] += [dict(m, workloads=[name]) for m in metrics]
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(spec, f)
    assert manifest.problems(spec, str(root)) == []
    return spec


def rehearse_copy(root, cell: str, trace: int, seconds: float = 1.5):
    """(last line, earlier JSON lines) of a rehearsal run as the driver
    runs a cell: a process of its own, from the root of `root`."""
    import subprocess

    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO,
               TF_CPP_MIN_LOG_LEVEL="3")
    proc = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", cell, "--seed",
         "2147483999", "--seconds", str(seconds), "--trace", str(trace),
         "--rehearse"], cwd=str(root), env=env, capture_output=True,
        text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = [json.loads(line) for line in proc.stdout.splitlines()
             if line.startswith("{")]
    return lines[-1], lines[:-1]
