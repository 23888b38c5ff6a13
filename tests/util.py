"""Shared helpers for golden-parity tests."""
import contextlib
import glob
import os
import signal

import pytest

REAL_REFERENCE_DATA = "/root/reference/data"
HAVE_GOLDEN_REFERENCE = os.path.isdir(REAL_REFERENCE_DATA)


def _generated_reference() -> str:
    """Encoder-built stand-in datasets (cobrix_tpu.testing.fixtures) for
    machines without the upstream golden set. Parity tests compare two
    independent decode paths against each other, so any decodable data
    of the right shape exercises them; only value-golden assertions
    (which go through read_copybook/read_binary/read_golden_lines and
    stay pinned to the real dataset below) still require the upstream
    bytes."""
    try:
        from cobrix_tpu.testing.fixtures import ensure_reference_fixtures
        return ensure_reference_fixtures() or REAL_REFERENCE_DATA
    except Exception:
        return REAL_REFERENCE_DATA


REFERENCE_DATA = (REAL_REFERENCE_DATA if HAVE_GOLDEN_REFERENCE
                  else _generated_reference())

# decorator for tests that touch the reference fixtures via explicit
# paths: with the upstream dataset absent these now run against the
# encoder-built stand-ins, and only skip if generation itself failed
needs_reference_data = pytest.mark.skipif(
    not os.path.isdir(REFERENCE_DATA),
    reason=f"reference fixtures absent ({REFERENCE_DATA}) and the "
           "encoder-built stand-ins could not be generated")


def require_reference_data():
    """Skip the calling test when the real golden dataset is absent.
    Used by the read_* helpers below, whose callers assert upstream
    golden VALUES — those cannot run on generated stand-ins."""
    if not HAVE_GOLDEN_REFERENCE:
        pytest.skip("upstream golden fixtures absent "
                    f"({REAL_REFERENCE_DATA}): value-golden assertions "
                    "cannot run on generated stand-in data")


@contextlib.contextmanager
def hard_timeout(seconds: float, label: str = "test"):
    """SIGALRM-backed hard per-test deadline: a hung test FAILS loud
    (TimeoutError with `label`) instead of wedging the whole CI run.
    Main-thread only (pytest runs tests there); plain pass-through where
    SIGALRM is unavailable. The distributed-execution tests wrap
    themselves in this so no fork/pipe bug can ever hang the suite —
    the in-code deadlines (shard_timeout_s / scan_deadline_s) are the
    first line of defense, this is the backstop."""
    if not hasattr(signal, "SIGALRM"):  # pragma: no cover - non-POSIX
        yield
        return

    def on_alarm(signum, frame):
        raise TimeoutError(
            f"{label} exceeded the hard {seconds:.0f}s test deadline "
            "(a distributed wait is unbounded somewhere)")

    previous = signal.signal(signal.SIGALRM, on_alarm)
    signal.setitimer(signal.ITIMER_REAL, seconds)
    try:
        yield
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0)
        signal.signal(signal.SIGALRM, previous)


def read_copybook(name: str) -> str:
    require_reference_data()
    with open(os.path.join(REAL_REFERENCE_DATA, name), encoding="utf-8") as f:
        return f.read()


def read_binary(name: str) -> bytes:
    """Read a data file; reference data entries may be directories of .bin files."""
    require_reference_data()
    path = os.path.join(REAL_REFERENCE_DATA, name)
    if os.path.isdir(path):
        chunks = []
        for f in sorted(glob.glob(os.path.join(path, "*"))):
            base = os.path.basename(f)
            if base.startswith((".", "_")):
                continue
            with open(f, "rb") as fh:
                chunks.append(fh.read())
        return b"".join(chunks)
    with open(path, "rb") as f:
        return f.read()


def read_golden_lines(name: str):
    require_reference_data()
    with open(os.path.join(REAL_REFERENCE_DATA, name), encoding="iso-8859-1") as f:
        return f.read().splitlines()


def check_stage_record(device: dict, wall_s: float, stages: set) -> None:
    stage_s, stage_n = device["stage_s"], device["stage_n"]
    assert stages <= set(stage_s), sorted(stages - set(stage_s))
    assert set(stage_s) == set(stage_n)
    assert all(s >= 0.0 for s in stage_s.values())
    assert all(n >= 1 for n in stage_n.values())
    launches = sum(device["launches"].values())
    assert stage_n["launch"] == launches
    assert stage_n["h2d"] == launches and stage_n["d2h_wait"] == launches
    # the fetch's two halves, once a launch: the wait for the chip, then
    # the copy home
    assert stage_n["d2h_wait.ready"] == stage_n["d2h_wait.copy"] == launches
    # the link's own counts: whole seconds of the fetching threads, and
    # the wall in which at least one of them was copying
    assert 0.0 < device["d2h_copy_busy_s"] <= device["d2h_copy_thread_s"]
    assert device["d2h_copy_busy_s"] <= wall_s
    assert 0 <= device["d2h_strided_bytes"] <= device["d2h_bytes"]
    # self time on one thread, each instant split among the threads of a
    # pool: the stages add up to at most the wall
    assert sum(stage_s.values()) <= wall_s
    assert 0.0 <= device["lower_s"] <= device["compile_s"]
