"""Synthetic load factory: encoder-built corpora at bench scale.

`generators.py` hand-packs bytes for its fixed profiles; this module
builds corpora *through the encoder* (cobrix_tpu.encode.BatchEncoder),
so every generated file is also a round-trip witness: the bytes are
produced by the same tables the readers decode with, and re-encoding
the decoded rows must reproduce them exactly (tools/rtcheck.py gates
that).

Two profiles, both chunked so multi-GB corpora stream to disk without
materializing:

* `write_fixed_corpus` — flat fixed-length transaction records with
  controlled *selectivity* knobs (`distinct_accounts` bounds the
  account-predicate cardinality, `status_weights` skews the status
  column) for filter/projection benches;
* `write_multiseg_corpus` — RDW-framed COMPANY/CONTACT hierarchy with a
  controlled *segment mix* (`contacts_per_company` drives the
  record-length distribution: 34-byte parent vs 60-byte child frames).

`corrupt_fixed_corpus` / `corrupt_multiseg_corpus` damage a sample of
records with the encoder-aware injectors (`faults.corrupt_record`):
bad packed sign nibble, invalid packed digit, RDW length damage,
unmapped segment id, and a mid-record torn tail — returning the damage
sites so checks can assert the diagnostic per class.
"""
from __future__ import annotations

import os
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from .faults import corrupt_record, field_site, rdw_record_starts

TXN_COPYBOOK = """
       01  TXN.
           05  TXN-ID        PIC 9(9)  COMP.
           05  ACCOUNT       PIC X(10).
           05  CURRENCY      PIC X(3).
           05  AMOUNT        PIC S9(9)V99 COMP-3.
           05  BALANCE       PIC S9(7)V99.
           05  STATUS        PIC X(1).
           05  BRANCH        PIC 9(4) COMP.
"""

MULTISEG_COPYBOOK = """
       01  COMPANY-DETAILS.
           05  SEGMENT-ID      PIC X(1).
           05  COMPANY-ID      PIC X(10).
           05  STATIC-DETAILS.
              10  COMPANY-NAME PIC X(15).
              10  REG-NUM      PIC 9(8)  COMP.
           05  CONTACTS REDEFINES STATIC-DETAILS.
              10  PHONE        PIC X(17).
              10  CONTACT      PIC X(28).
"""

# flat per-segment layouts the BatchEncoder can compile (REDEFINES
# need the record-at-a-time encoder; a corpus encodes each segment
# population as its own static layout and interleaves the frames)
_SEG_C_LAYOUT = """
       01  R.
           05  SEGMENT-ID      PIC X(1).
           05  COMPANY-ID      PIC X(10).
           05  COMPANY-NAME    PIC X(15).
           05  REG-NUM         PIC 9(8)  COMP.
"""

_SEG_P_LAYOUT = """
       01  R.
           05  SEGMENT-ID      PIC X(1).
           05  COMPANY-ID      PIC X(10).
           05  PHONE           PIC X(17).
           05  CONTACT         PIC X(28).
"""

_CURRENCIES = ("USD", "EUR", "GBP", "ZAR", "CHF", "JPY")
_STATUSES = "ACDPR"


def fixed_read_options() -> Dict[str, str]:
    return {"copybook_contents": TXN_COPYBOOK}


def member_compressor(compression: str):
    """One-shot `bytes -> compressed member` for a canonical codec name
    (io.compress registry names/aliases). Corpus writers emit ONE member
    per flushed chunk, so generated compressed corpora are seekable:
    every chunk boundary is a restartable checkpoint for the streaming
    inflate index."""
    from ..io.compress import codec_by_name

    name = codec_by_name(compression).name
    if name == "gzip":
        import gzip as _gzip

        return name, lambda b: _gzip.compress(b, compresslevel=1,
                                              mtime=0)
    if name == "zlib":
        import zlib as _zlib

        return name, lambda b: _zlib.compress(b, 1)
    if name == "bz2":
        import bz2 as _bz2

        return name, lambda b: _bz2.compress(b, 1)
    if name == "xz":
        import lzma as _lzma

        return name, lambda b: _lzma.compress(b, preset=0)
    if name == "zstd":
        try:
            import zstandard
        except ImportError as exc:
            raise ImportError(
                "writing a zstd corpus needs the optional 'zstandard' "
                "package (pip install zstandard)") from exc
        cctx = zstandard.ZstdCompressor()
        return name, cctx.compress
    raise ValueError(f"no corpus compressor for codec {name!r}")


class _CorpusSink:
    """File sink for the chunked corpus writers: plain pass-through, or
    one compressed member per write() when `compression` is given."""

    def __init__(self, path: str, compression: Optional[str] = None):
        self._f = open(path, "wb")
        self._compress = None
        self.wire_bytes = 0
        if compression:
            _name, self._compress = member_compressor(compression)

    def write(self, data: bytes) -> None:
        if self._compress is not None:
            data = self._compress(bytes(data))
        self._f.write(data)
        self.wire_bytes += len(data)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self._f.close()
        return False


def multiseg_read_options() -> Dict[str, str]:
    return {
        "copybook_contents": MULTISEG_COPYBOOK,
        "is_record_sequence": "true",
        "segment_field": "SEGMENT-ID",
        "redefine_segment_id_map": "STATIC-DETAILS => C",
        "redefine_segment_id_map_1": "CONTACTS => P",
    }


def write_fixed_corpus(path: str, num_records: int, *, seed: int = 7,
                       chunk_records: int = 262144,
                       distinct_accounts: int = 1000,
                       status_weights: Optional[Sequence[float]] = None,
                       compression: Optional[str] = None,
                       ) -> Dict[str, int]:
    """Stream `num_records` fixed-length TXN records to `path` through
    the vectorized encoder. With `compression` (a codec name the
    io.compress registry knows) each flushed chunk becomes one
    compressed member. Returns {records, bytes, record_size} — `bytes`
    is the DECOMPRESSED payload size; `wire_bytes` joins it when
    compressed."""
    from ..encode import BatchEncoder

    enc = BatchEncoder(TXN_COPYBOOK)
    rng = np.random.default_rng(seed)
    accounts = np.array([f"ACC{i:07d}" for i in range(distinct_accounts)],
                        dtype=object)
    currencies = np.array(_CURRENCIES, dtype=object)
    statuses = np.array(list(_STATUSES), dtype=object)
    weights = None
    if status_weights is not None:
        weights = np.asarray(status_weights, dtype=np.float64)
        weights = weights / weights.sum()
    written = 0
    total = 0
    with _CorpusSink(path, compression) as f:
        while written < num_records:
            n = min(chunk_records, num_records - written)
            cols = [
                np.arange(written, written + n, dtype=np.int64),  # TXN-ID
                accounts[rng.integers(0, distinct_accounts, size=n)],
                currencies[rng.integers(0, len(currencies), size=n)],
                rng.integers(-10 ** 11, 10 ** 11, size=n),  # AMOUNT m.
                rng.integers(-10 ** 9, 10 ** 9, size=n),    # BALANCE m.
                statuses[rng.choice(len(statuses), size=n, p=weights)],
                rng.integers(0, 10 ** 4, size=n),           # BRANCH
            ]
            data = enc.encode_fixed(cols, n)
            f.write(data)
            written += n
            total += len(data)
    out = {"records": written, "bytes": total,
           "record_size": enc.record_size}
    if compression:
        out["wire_bytes"] = f.wire_bytes
    return out


def _interleave_positions(contacts: np.ndarray
                          ) -> Tuple[np.ndarray, np.ndarray]:
    """Final-sequence row positions for c parent rows followed by their
    `contacts[i]` child rows each."""
    c = len(contacts)
    before = np.concatenate(([0], np.cumsum(contacts)[:-1]))
    pos_c = np.arange(c, dtype=np.int64) + before
    k_total = int(contacts.sum())
    within = np.arange(k_total, dtype=np.int64) - np.repeat(before,
                                                            contacts)
    pos_p = np.repeat(pos_c + 1, contacts) + within
    return pos_c, pos_p


def write_multiseg_corpus(path: str, num_companies: int, *,
                          seed: int = 7, chunk_companies: int = 131072,
                          contacts_per_company: Tuple[int, int] = (0, 4),
                          big_endian_rdw: bool = False,
                          compression: Optional[str] = None
                          ) -> Dict[str, int]:
    """Stream an RDW-framed COMPANY/CONTACT corpus to `path`. The
    contact range drives both the segment mix and the record-length
    distribution. With `compression` each flushed chunk becomes one
    compressed member. Returns {records, companies, contacts, bytes}
    (plus `wire_bytes` when compressed)."""
    from ..encode import BatchEncoder

    enc_c = BatchEncoder(_SEG_C_LAYOUT)
    enc_p = BatchEncoder(_SEG_P_LAYOUT)
    len_c = enc_c.record_size + 4
    len_p = enc_p.record_size + 4
    rng = np.random.default_rng(seed)
    lo, hi = contacts_per_company
    names = np.array([f"Company {i:05d} Ltd."[:15] for i in range(500)],
                     dtype=object)
    contacts_pool = np.array(
        [f"Contact Person {i:04d}" for i in range(500)], dtype=object)
    done = 0
    records = 0
    contacts_total = 0
    total = 0
    with _CorpusSink(path, compression) as f:
        while done < num_companies:
            c = min(chunk_companies, num_companies - done)
            k = rng.integers(lo, hi + 1, size=c)
            kt = int(k.sum())
            ids = np.array([f"C{gid:09d}" for gid in
                            range(done, done + c)], dtype=object)
            mat_c = np.frombuffer(enc_c.encode_rdw([
                np.full(c, "C", dtype=object),
                ids,
                names[rng.integers(0, len(names), size=c)],
                rng.integers(0, 10 ** 8, size=c),
            ], c, big_endian=big_endian_rdw), dtype=np.uint8
            ).reshape(c, len_c)
            pos_c, pos_p = _interleave_positions(k)
            lens = np.empty(c + kt, dtype=np.int64)
            lens[pos_c] = len_c
            lens[pos_p] = len_p
            offs = np.concatenate(([0], np.cumsum(lens)[:-1]))
            buf = np.empty(int(lens.sum()), dtype=np.uint8)
            buf[(offs[pos_c][:, None]
                 + np.arange(len_c)).ravel()] = mat_c.ravel()
            if kt:
                phones = np.array(
                    [f"+{n:014d}" for n in
                     rng.integers(0, 10 ** 12, size=kt)], dtype=object)
                mat_p = np.frombuffer(enc_p.encode_rdw([
                    np.full(kt, "P", dtype=object),
                    np.repeat(ids, k),
                    phones,
                    contacts_pool[rng.integers(0, len(contacts_pool),
                                               size=kt)],
                ], kt, big_endian=big_endian_rdw), dtype=np.uint8
                ).reshape(kt, len_p)
                buf[(offs[pos_p][:, None]
                     + np.arange(len_p)).ravel()] = mat_p.ravel()
            f.write(buf.tobytes())
            done += c
            records += c + kt
            contacts_total += kt
            total += buf.nbytes
    out = {"records": records, "companies": done,
           "contacts": contacts_total, "bytes": total}
    if compression:
        out["wire_bytes"] = f.wire_bytes
    return out


def corrupt_fixed_corpus(data: bytes, *, count: int = 3, seed: int = 0,
                         kinds: Sequence[str] = ("sign-nibble",
                                                 "packed-digit",
                                                 "torn-write")
                         ) -> Tuple[bytes, List[Dict[str, object]]]:
    """Damage `count` records of a TXN corpus per kind (torn-write
    always tears the file tail). Returns (corrupted, sites)."""
    from ..copybook.copybook import parse_copybook

    cb = parse_copybook(TXN_COPYBOOK)
    rec = cb.record_size
    amount = field_site(cb, "AMOUNT")
    n = len(data) // rec
    rng = np.random.default_rng(seed)
    out = bytearray(data)
    sites: List[Dict[str, object]] = []
    body_kinds = [k for k in kinds if k != "torn-write"]
    picks = rng.choice(n - 1, size=min(count * len(body_kinds), n - 1),
                       replace=False) if body_kinds else []
    for i, idx in enumerate(picks):
        kind = body_kinds[i % len(body_kinds)]
        start = int(idx) * rec
        out[start:start + rec] = corrupt_record(
            bytes(out[start:start + rec]), kind, site=amount)
        sites.append({"record": int(idx), "kind": kind,
                      "offset": start + amount[0]})
    if "torn-write" in kinds:
        keep = (n - 1) * rec + rec * 2 // 3
        out = out[:keep]
        sites.append({"record": n - 1, "kind": "torn-write",
                      "offset": keep})
    return bytes(out), sites


def corrupt_multiseg_corpus(data: bytes, *, count: int = 3,
                            seed: int = 0,
                            kinds: Sequence[str] = ("rdw-length",
                                                    "segment-id",
                                                    "torn-write"),
                            big_endian_rdw: bool = False
                            ) -> Tuple[bytes, List[Dict[str, object]]]:
    """Damage `count` records of an RDW multisegment corpus per kind.
    Returns (corrupted, sites)."""
    starts = rdw_record_starts(data, big_endian_rdw)
    seg_site = field_site(MULTISEG_COPYBOOK, "SEGMENT-ID")
    rng = np.random.default_rng(seed)
    out = bytearray(data)
    sites: List[Dict[str, object]] = []
    body_kinds = [k for k in kinds if k != "torn-write"]
    n = len(starts)
    picks = sorted(
        int(i) for i in rng.choice(n - 1,
                                   size=min(count * len(body_kinds),
                                            n - 1),
                                   replace=False)) if body_kinds else []
    for i, idx in enumerate(picks):
        kind = body_kinds[i % len(body_kinds)]
        start = starts[idx]
        end = starts[idx + 1] if idx + 1 < n else len(data)
        rec = corrupt_record(bytes(out[start:end]), kind,
                             site=seg_site, header=True,
                             big_endian=big_endian_rdw, seed=i)
        out[start:end] = rec
        sites.append({"record": idx, "kind": kind, "offset": start})
    if "torn-write" in kinds and n:
        last = starts[-1]
        keep = last + max(5, (len(data) - last) * 2 // 3)
        out = out[:keep]
        sites.append({"record": n - 1, "kind": "torn-write",
                      "offset": keep})
    return bytes(out), sites
