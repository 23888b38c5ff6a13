"""How the program is launched toward the chip, rehearsed on the CPU.

chip_smoke.py's phase functions run here at a tiny size (Pallas in
interpret mode, the four-chip phase on four of conftest's virtual CPU
devices); its main() is held to its contract — no TPU, or a phase that
raises, means no `"ok": true` and a non-zero exit; and the launch code
around it is pinned: unknown backends raise, the compile cache goes
where JAX_COMPILATION_CACHE_DIR says or else under the checkout.
"""
import ast
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402

pytestmark = pytest.mark.jax


@pytest.fixture
def cpu_device():
    import jax

    return chip_smoke.find_device("cpu", len(jax.devices()))


def json_lines(capsys):
    return [json.loads(line)
            for line in capsys.readouterr().out.splitlines()
            if line.startswith("{")]


def test_one_chip_phases_rehearsal(cpu_device, tmp_path, capsys):
    """Every phase of the one-chip run, through the same functions and
    the same checks, at a few MiB: parity with the host kernels and the
    scalar oracle, outputs on JAX's first device, both exp1 decoders."""
    chip_smoke.run_one_chip(cpu_device, str(tmp_path), 3 << 20, 1 << 20,
                            seed=21)
    import jax

    lines = json_lines(capsys)
    reads = {(line["phase"], line["backend"]): line for line in lines
             if "steady_s" in line and line["phase"].startswith("read_")}
    assert set(reads) == {("read_exp3", "pallas"), ("read_exp1", "pallas"),
                          ("read_exp1", "jax")}
    for line in reads.values():
        assert line["compiles_in_steady"] == 0 and line["d2h_bytes"] > 0
        assert line["devices"] == [str(jax.devices()[0])]
    exp3 = reads[("read_exp3", "pallas")]
    assert exp3["interpreted"] is True
    # exp3 launches by redefine, two programs; exp1 brings no masks
    assert exp3["partitioned_batches"] >= 1 and not exp3["declined_batches"]
    assert sum(exp3["set_rows"].values()) == exp3["records"]
    # (`points_u8`: both set programs, and exp1's one, hand their
    # strings back as one matrix of 8-bit code points)
    assert exp3["device_groups"] == {"fused": 2, "fused_rows_in_lanes": 0,
                                     "sliced": 10, "gathered": 0,
                                     "points_u8": 2}
    assert reads[("read_exp1", "pallas")]["device_groups"] == {
        "fused": 61, "fused_rows_in_lanes": 61, "sliced": 4, "gathered": 0,
        "points_u8": 1}
    assert not reads[("read_exp1", "pallas")]["set_rows"]
    assert reads[("read_exp1", "jax")]["interpreted"] is None
    parity = [line for line in lines if line.get("parity") == "ok"]
    assert [line["phase"] for line in parity] == [
        "read_exp3", "device_aggregate", "read_exp1"]
    (again,) = [line for line in lines if line["phase"] == "compile_cache"]
    assert again["shape"] == "8192x16064" and again["again_s"] >= 0
    serves = [line for line in lines if line["phase"] == "serve"]
    assert len(serves) == chip_smoke.SERVE_REQUESTS
    assert all(line["table_equals_in_process"] for line in serves)


def test_four_chip_phase_rehearsal(cpu_device, tmp_path, capsys):
    """`--chips 4`'s only phase on four virtual devices: four distinct
    devices hold an input shard, results equal the one-device mesh's."""
    import jax

    if len(jax.devices()) < 4:
        pytest.skip("needs 4 virtual devices (conftest sets 8)")
    chip_smoke.run_sharded(cpu_device, str(tmp_path), 3 << 20, seed=21,
                           n_devices=4)
    lines = [line for line in json_lines(capsys)
             if line.get("phase") == "sharded"]
    by_mesh = {line["mesh_devices"]: line for line in lines
               if "mesh_devices" in line}
    assert set(by_mesh) == {4, 1}
    assert len(by_mesh[4]["decode_input_shard_bytes"]) == 4
    assert len(by_mesh[4]["aggregate_devices"]) == 4
    assert len(by_mesh[1]["decode_input_shard_bytes"]) == 1
    assert lines[-1]["parity"] == "ok"


def stub_phases(monkeypatch, device):
    monkeypatch.setattr(chip_smoke, "build_native", lambda: None)
    monkeypatch.setattr(chip_smoke, "find_device",
                        lambda platform, count: dict(device, count=count))
    for name in ("run_one_chip", "run_sharded"):
        monkeypatch.setattr(chip_smoke, name, lambda *a, **k: None)


TPU = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}


@pytest.mark.parametrize("argv,count", [([], 1), (["--chips", "4"], 4)])
def test_main_last_line_is_the_contract(monkeypatch, capsys, argv, count):
    stub_phases(monkeypatch, TPU)
    assert chip_smoke.main(argv) == 0
    last = capsys.readouterr().out.splitlines()[-1]
    assert json.loads(last) == {"ok": True,
                                "device": dict(TPU, count=count)}


def test_main_without_tpu_prints_no_result(monkeypatch, capsys):
    """conftest holds JAX to the CPU, as the sandbox does."""
    monkeypatch.setattr(chip_smoke, "build_native", lambda: None)
    with pytest.raises(RuntimeError, match="need 1 tpu device"):
        chip_smoke.main([])
    assert '"ok"' not in capsys.readouterr().out


def test_main_with_a_failing_phase_prints_no_result(monkeypatch, capsys):
    stub_phases(monkeypatch, TPU)

    def broken(*args, **kwargs):
        raise RuntimeError("chip_smoke: read_exp3 differs")

    monkeypatch.setattr(chip_smoke, "run_one_chip", broken)
    with pytest.raises(RuntimeError, match="differs"):
        chip_smoke.main([])
    assert '"ok"' not in capsys.readouterr().out


# ------------------------------------------------------- backend names

@pytest.mark.parametrize("backend", ["tpu", "auto", "gpu"])
def test_unknown_backend_raises(tmp_path, backend):
    """These names used to decode on the host kernels and report
    themselves in the metrics."""
    from cobrix_tpu import read_cobol
    from cobrix_tpu.reader.columnar import ColumnarDecoder
    from cobrix_tpu import parse_copybook
    from cobrix_tpu.testing.generators import (EXP1_COPYBOOK,
                                               generate_exp1)

    path = tmp_path / "exp1.dat"
    path.write_bytes(generate_exp1(4, seed=1).tobytes())
    with pytest.raises(ValueError, match="'numpy', 'host', 'jax', 'pallas'"):
        read_cobol(str(path), copybook_contents=EXP1_COPYBOOK,
                   backend=backend)
    with pytest.raises(ValueError, match="Unknown backend"):
        ColumnarDecoder(parse_copybook(EXP1_COPYBOOK), backend=backend)


# -------------------------------------------------- compile-cache placing

CACHE_PROBE = """
import sys
sys.path.insert(0, {repo!r})
import jax
import numpy as np
from cobrix_tpu import parse_copybook
from cobrix_tpu.ops.device import ensure_compile_cache
from cobrix_tpu.reader.columnar import ColumnarDecoder

before = jax.config.jax_compilation_cache_dir
decoder = ColumnarDecoder(parse_copybook('''
       01 R.
          05 N PIC S9(6) COMP.
'''), backend="jax")
decoder.decode(np.zeros((4, 4), dtype=np.uint8))  # builds the program
print(repr((before, ensure_compile_cache(),
            jax.config.jax_compilation_cache_dir)))
"""


def run_cache_probe(env_dir):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = env_dir
    proc = subprocess.run(
        [sys.executable, "-c", CACHE_PROBE.format(repo=REPO)],
        env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr
    return ast.literal_eval(proc.stdout.splitlines()[-1])


def test_compile_cache_respects_the_environment(tmp_path):
    placed = str(tmp_path / "placed_cache")
    before, helper, after = run_cache_probe(placed)
    assert before == helper == after == placed
    assert os.listdir(placed)  # the program's compile went there


def test_compile_cache_defaults_to_the_checkout():
    before, helper, after = run_cache_probe(None)
    assert before is None
    assert helper == after == os.path.join(REPO, ".jax_cache")

