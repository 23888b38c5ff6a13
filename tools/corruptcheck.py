"""Corruption smoke check: a permissive read must survive a dirty file.

Usage (prints ONE JSON line on stdout, progress on stderr, exit
code 0 only if every check holds):

    python tools/corruptcheck.py [--records N] [--seed S]

Generates the exp2 RDW fixture, applies one instance of every corruption
class from `cobrix_tpu.testing.faults` (bit flip, truncated tail,
garbage splice, zero RDW, oversized RDW — all in the same file), then
asserts the `record_error_policy` contract end to end:

  * `permissive`      — read completes, returns rows, and the
                        ReadDiagnostics ledger is non-empty;
  * `drop_malformed`  — read completes with no more rows than permissive;
  * `fail_fast`       — read raises, and the error names a file offset.

This is the post-deploy / CI smoke companion to the full matrix in
tests/test_fault_tolerance.py: one file, one pass per policy, ~a second.
"""
import argparse
import json
import os
import re
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from cobrix_tpu import read_cobol
from cobrix_tpu.testing import faults
from cobrix_tpu.testing.generators import EXP2_COPYBOOK, generate_exp2


def _log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def _corrupt_everywhere(data: bytes) -> bytes:
    """One instance of every corruption class, spread across the file.

    The oversized RDW goes near the tail: a header that declares more
    bytes than the file holds clamps the remainder as one truncated
    record (reference semantics, ledgered by permissive), so placing it
    mid-file would swallow every later corruption site.
    """
    starts = faults.rdw_record_starts(data)
    if len(starts) < 8:
        raise SystemExit("fixture too small: need >= 8 records")
    q = len(starts) // 8
    # Open the splice with a zero RDW so the garbage region is
    # deterministically un-frameable (random garbage can start with a
    # plausible oversized header, which takes the reference's
    # clamp-remainder-as-tail path instead of resync).
    garbage = b"\x00\x00\x00\x00" + faults.garbage_run(93)
    splice_at, splice_len = starts[5 * q], len(garbage)
    data = faults.splice_garbage(data, splice_at, garbage)
    data = faults.zero_rdw(data, starts[q])
    data = faults.flip_bit(data, starts[3 * q] + 2, bit=7)  # length byte
    data = faults.oversize_rdw(data, starts[-2] + splice_len)
    return faults.truncate(data, len(data) - 3)             # torn tail


def _read(path: str, policy: str):
    return read_cobol(path, copybook_contents=EXP2_COPYBOOK,
                      is_record_sequence=True, record_error_policy=policy)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--records", type=int, default=512)
    ap.add_argument("--seed", type=int, default=100)
    args = ap.parse_args(argv)

    clean = bytes(generate_exp2(args.records, seed=args.seed))
    dirty = _corrupt_everywhere(clean)
    _log(f"fixture: {args.records} records, {len(clean)} clean bytes, "
         f"{len(dirty)} corrupted bytes")

    checks = {}
    with tempfile.TemporaryDirectory(prefix="corruptcheck_") as tmp:
        path = os.path.join(tmp, "dirty.dat")
        with open(path, "wb") as f:
            f.write(dirty)

        perm = _read(path, "permissive")
        diag = perm.diagnostics
        # >= 90%: each corruption site may cost a few records, but the
        # read must recover and return the decodable bulk of the file
        checks["permissive_survives"] = len(perm) >= 0.9 * args.records
        checks["ledger_populated"] = bool(
            diag is not None and not diag.is_clean and diag.entries)
        _log(f"permissive: {len(perm)} rows, "
             f"ledger={diag.as_dict() if diag else None}")

        dropped = _read(path, "drop_malformed")
        checks["drop_malformed_not_larger"] = len(dropped) <= len(perm)
        _log(f"drop_malformed: {len(dropped)} rows")

        try:
            _read(path, "fail_fast")
        except ValueError as e:
            checks["fail_fast_raises_with_offset"] = bool(
                re.search(r"\bat \d+\b", str(e)))
            _log(f"fail_fast: raised as expected: {e}")
        else:
            checks["fail_fast_raises_with_offset"] = False
            _log("fail_fast: ERROR — read of corrupt file did not raise")

    ok = all(checks.values())
    print(json.dumps({
        "metric": "corruptcheck",
        "ok": ok,
        "checks": checks,
        "rows_permissive": len(perm),
        "rows_drop_malformed": len(dropped),
        "ledger": diag.as_dict() if diag else None,
    }, separators=(",", ":")))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
