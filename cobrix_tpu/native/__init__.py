"""Native runtime bindings: C++ record framing + batch packing.

Builds `framing.cpp` into a shared library on first use (g++, cached next
to the source; rebuilt when the source is newer) and binds it with ctypes
— the image has no pybind11, and the C ABI keeps the boundary trivial.
Every entry point has a NumPy fallback so the package works without a
toolchain; `available()` reports which path is active.
"""
from __future__ import annotations

import ctypes
import logging
import os
import threading
from typing import Optional, Tuple

import numpy as np

_logger = logging.getLogger(__name__)

from . import build as _buildmod

_HERE = os.path.dirname(os.path.abspath(__file__))
_LIB_PATH = _buildmod.lib_path()

_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_build_failed = False
# operator/test kill switch: every native entry point reports
# unavailable, exercising the pure-Python fallbacks without touching the
# .so on disk (tools/asmcheck.py rides this). Env var for subprocesses,
# set_disabled() for in-process tests.
# Truthy spellings only: COBRIX_NATIVE_DISABLE=0/false/off keeps native
# dispatch ON (a bare bool() would silently disable it).
_disabled = (os.environ.get("COBRIX_NATIVE_DISABLE", "").strip().lower()
             in ("1", "true", "yes", "on"))

# COBRIX_FORCE_CPU_LEVEL=scalar|sse|avx2 (or 0|1|2) pins the native SIMD
# dispatch below the CPU's capability — the only way to exercise the
# scalar/SSE kernels and tails on an AVX2 machine. The .so clamps to the
# detected level, so forcing "avx2" on an SSE box degrades, never faults.
_CPU_LEVELS = {"scalar": 0, "sse": 1, "sse4.2": 1, "avx2": 2,
               "0": 0, "1": 1, "2": 2}


def _forced_cpu_level_env() -> int:
    raw = os.environ.get("COBRIX_FORCE_CPU_LEVEL", "").strip().lower()
    if not raw:
        return -1
    if raw not in _CPU_LEVELS:
        _logger.warning("COBRIX_FORCE_CPU_LEVEL=%r not in %s; ignored",
                        raw, sorted(set(_CPU_LEVELS)))
        return -1
    return _CPU_LEVELS[raw]


MAX_RDW_RECORD_SIZE = 100 * 1024 * 1024

_I32P = np.ctypeslib.ndpointer(dtype=np.int32, flags="C_CONTIGUOUS")
_I64P = np.ctypeslib.ndpointer(dtype=np.int64, flags="C_CONTIGUOUS")
_U8P = np.ctypeslib.ndpointer(dtype=np.uint8, flags="C_CONTIGUOUS")
_U16P = np.ctypeslib.ndpointer(dtype=np.uint16, flags="C_CONTIGUOUS")
_U64P = np.ctypeslib.ndpointer(dtype=np.uint64, flags="C_CONTIGUOUS")


def set_disabled(flag: bool) -> None:
    """Force the pure-Python fallbacks on (True) or restore native
    dispatch (False). Parity harnesses flip this to compare the two
    paths in one process; the loaded library itself is untouched."""
    global _disabled
    _disabled = bool(flag)


def _build() -> bool:
    ok, message = _buildmod.build()
    if not ok:
        _logger.warning("%s; using NumPy fallbacks", message)
    return ok


def _load() -> Optional[ctypes.CDLL]:
    global _lib, _build_failed
    if _disabled:
        return None
    if _lib is not None or _build_failed:
        return _lib
    with _lock:
        if _lib is not None or _build_failed:
            return _lib
        if _buildmod.needs_build() and not _build():
            _build_failed = True
            return None
        try:
            lib = ctypes.CDLL(_LIB_PATH)
        except OSError as exc:
            _logger.warning("native framing load failed (%s)", exc)
            _build_failed = True
            return None
        lib.rdw_scan.restype = ctypes.c_int64
        lib.rdw_scan.argtypes = [
            _U8P, ctypes.c_int64, ctypes.c_int32, ctypes.c_int32,
            ctypes.c_int64, ctypes.c_int64, _I64P, _I64P, ctypes.c_int64,
            ctypes.POINTER(ctypes.c_int64)]
        lib.length_field_scan.restype = ctypes.c_int64
        lib.length_field_scan.argtypes = [
            _U8P, ctypes.c_int64, ctypes.c_int64, ctypes.c_int64,
            ctypes.c_int32, ctypes.c_int64, _I64P, _I64P, ctypes.c_int64,
            ctypes.POINTER(ctypes.c_int64)]
        lib.text_scan.restype = ctypes.c_int64
        lib.text_scan.argtypes = [
            _U8P, ctypes.c_int64, _I64P, _I64P, ctypes.c_int64]
        lib.pack_records.restype = None
        lib.pack_records.argtypes = [
            _U8P, ctypes.c_int64, _I64P, _I64P, ctypes.c_int64,
            ctypes.c_int64, ctypes.c_int64, _U8P]
        lib.decode_binary_cols.restype = None
        lib.decode_binary_cols.argtypes = [
            _U8P, ctypes.c_int64, ctypes.c_int64, _I64P, ctypes.c_int64,
            ctypes.c_int32, ctypes.c_int32, ctypes.c_int32, _I64P, _U8P]
        lib.decode_bcd_cols.restype = None
        lib.decode_bcd_cols.argtypes = [
            _U8P, ctypes.c_int64, ctypes.c_int64, _I64P, ctypes.c_int64,
            ctypes.c_int32, _I64P, _U8P]
        lib.decode_display_cols.restype = None
        lib.decode_display_cols.argtypes = [
            _U8P, ctypes.c_int64, ctypes.c_int64, _I64P, ctypes.c_int64,
            ctypes.c_int32, ctypes.c_int32, ctypes.c_int32, ctypes.c_int32,
            ctypes.c_int32, ctypes.c_int32, _I64P, _U8P, _I64P]
        lib.decode_numeric_groups.restype = None
        lib.decode_numeric_groups.argtypes = [
            _U8P, ctypes.c_int64, ctypes.c_int64, ctypes.c_int64,
            _I32P, _I32P, _I64P, ctypes.c_void_p, _I32P, _I32P,
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p]
        lib.decode_bcd_wide_cols.restype = None
        lib.decode_bcd_wide_cols.argtypes = [
            _U8P, ctypes.c_int64, ctypes.c_int64, _I64P, ctypes.c_int64,
            ctypes.c_int32, _U64P, _U64P, _U8P, _U8P]
        lib.decode_binary_wide_cols.restype = None
        lib.decode_binary_wide_cols.argtypes = [
            _U8P, ctypes.c_int64, ctypes.c_int64, _I64P, ctypes.c_int64,
            ctypes.c_int32, ctypes.c_int32, ctypes.c_int32,
            _U64P, _U64P, _U8P, _U8P]
        lib.decode_display_wide_cols.restype = None
        lib.decode_display_wide_cols.argtypes = [
            _U8P, ctypes.c_int64, ctypes.c_int64, _I64P, ctypes.c_int64,
            ctypes.c_int32, ctypes.c_int32, ctypes.c_int32, ctypes.c_int32,
            ctypes.c_int32, ctypes.c_int32,
            _U64P, _U64P, _U8P, _U8P, _I64P]
        lib.decode_binary_cols_raw.restype = None
        lib.decode_binary_cols_raw.argtypes = [
            _U8P, _I64P, _I64P, ctypes.c_int64, _I64P, ctypes.c_int64,
            ctypes.c_int32, ctypes.c_int32, ctypes.c_int32, ctypes.c_int32,
            ctypes.c_void_p, _U8P]
        lib.decode_bcd_cols_raw.restype = None
        lib.decode_bcd_cols_raw.argtypes = [
            _U8P, _I64P, _I64P, ctypes.c_int64, _I64P, ctypes.c_int64,
            ctypes.c_int32, ctypes.c_int32, ctypes.c_void_p, _U8P]
        lib.transcode_string_cols.restype = None
        lib.transcode_string_cols.argtypes = [
            _U8P, ctypes.c_int64, ctypes.c_int64, _I64P, ctypes.c_int64,
            ctypes.c_int64, _U16P, _U16P]
        lib.transcode_string_cols_raw.restype = None
        lib.transcode_string_cols_raw.argtypes = [
            _U8P, _I64P, _I64P, ctypes.c_int64, _I64P, ctypes.c_int64,
            ctypes.c_int64, _U16P, _U16P]
        lib.decimal128_from_limbs.restype = None
        lib.decimal128_from_limbs.argtypes = [
            _U64P, _U64P, _U8P, _U8P, _I64P, ctypes.c_int64,
            ctypes.c_int32, _U8P, _U8P]
        lib.decimal128_batch.restype = None
        lib.decimal128_batch.argtypes = [
            ctypes.c_int64, ctypes.c_int64, ctypes.c_void_p,
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p, _U8P,
            ctypes.c_void_p, _U8P, _I64P, _I32P, _U8P, _U8P]
        lib.set_omp_threads.restype = None
        lib.set_omp_threads.argtypes = [ctypes.c_int32]
        lib.format_seg_id_level.restype = None
        lib.format_seg_id_level.argtypes = [
            _I64P, ctypes.c_void_p, ctypes.c_int64, _U8P, ctypes.c_int64,
            ctypes.c_int32, _U8P, _I32P, _U8P, ctypes.c_int64,
            ctypes.POINTER(ctypes.c_int64)]
        lib.transcode_string_cols_arrow.restype = None
        lib.transcode_string_cols_arrow.argtypes = [
            _U8P, ctypes.c_int64, ctypes.c_void_p, ctypes.c_void_p,
            ctypes.c_int64, _I64P, _I64P, ctypes.c_int64, ctypes.c_void_p,
            _U16P, ctypes.c_int32, ctypes.c_void_p, ctypes.c_void_p,
            _I64P, _I64P]
        lib.assemble_cols_arrow.restype = None
        lib.assemble_cols_arrow.argtypes = [
            _U8P, ctypes.c_int64, ctypes.c_void_p, ctypes.c_void_p,
            ctypes.c_int64, ctypes.c_int64,
            _I64P, _I32P, _I32P, _I32P, _I32P,
            _I32P, _I32P, _I64P, _I32P,
            ctypes.c_void_p, _I64P, ctypes.c_void_p, _I64P,
            ctypes.c_void_p, _U8P]
        lib.pack_validity.restype = ctypes.c_int64
        lib.pack_validity.argtypes = [_U8P, ctypes.c_int64,
                                      ctypes.c_int64, _U8P]
        lib.simd_level.restype = ctypes.c_int32
        lib.simd_level.argtypes = []
        lib.set_cpu_level.restype = None
        lib.set_cpu_level.argtypes = [ctypes.c_int32]
        lib.rdw_scan_segids.restype = ctypes.c_int64
        lib.rdw_scan_segids.argtypes = [
            _U8P, ctypes.c_int64, ctypes.c_int32, ctypes.c_int32,
            ctypes.c_int64, ctypes.c_int64, ctypes.c_int64, ctypes.c_int64,
            _I64P, _I64P, _U8P, ctypes.c_int64,
            ctypes.POINTER(ctypes.c_int64)]
        lib.fill_const_string.restype = None
        lib.fill_const_string.argtypes = [
            ctypes.c_int64, _U8P, ctypes.c_int64, _I32P, _U8P]
        forced = _forced_cpu_level_env()
        if forced >= 0:
            lib.set_cpu_level(forced)
        _lib = lib
        return _lib


def available() -> bool:
    return _load() is not None


def _as_u8(data) -> np.ndarray:
    if isinstance(data, np.ndarray):
        return np.ascontiguousarray(data, dtype=np.uint8)
    return np.frombuffer(data, dtype=np.uint8)


def _framing_error(buf: np.ndarray, pos: int, kind: str):
    """Structured framing error (reader.diagnostics.FramingError — imported
    lazily to keep this module free of reader dependencies at load time).
    Messages keep the reference wording plus a hex header snapshot."""
    from ..reader.diagnostics import FramingError, hex_snapshot

    header = bytes(buf[pos:pos + 4])
    hdr = ",".join(str(b) for b in header)
    if kind == "zero":
        message = (f"RDW headers should never be zero ({hdr}). "
                   f"Found zero size record at {pos} "
                   f"(header bytes: {hex_snapshot(header)}).")
        reason = "zero-length RDW header"
    else:
        message = (f"RDW headers too big at {pos} "
                   f"(header bytes: {hex_snapshot(header)}).")
        reason = "oversized RDW header"
    return FramingError(message, offset=int(pos), reason=reason,
                        header=header)


def rdw_scan(data, big_endian: bool, rdw_adjustment: int = 0,
             file_header_bytes: int = 0, file_footer_bytes: int = 0
             ) -> Tuple[np.ndarray, np.ndarray]:
    """All RDW record (payload offset, length) pairs of a file image.
    Raises ValueError on zero/oversized headers (reference
    RecordHeaderParserRDW hard errors)."""
    buf = _as_u8(data)
    size = buf.size
    cap = max(16, size // 4 + 2)
    offsets = np.empty(cap, dtype=np.int64)
    lengths = np.empty(cap, dtype=np.int64)
    lib = _load()
    if lib is not None:
        err = ctypes.c_int64(0)
        n = lib.rdw_scan(buf, size, int(big_endian), int(rdw_adjustment),
                         file_header_bytes, file_footer_bytes, offsets,
                         lengths, cap, ctypes.byref(err))
        if n == -1:
            raise _framing_error(buf, err.value, "zero")
        if n == -2:
            raise _framing_error(buf, err.value, "big")
        return offsets[:n].copy(), lengths[:n].copy()
    # NumPy fallback (still sequential in Python — the chain is data-dependent)
    pos = 0
    body_end = size - file_footer_bytes if 0 < file_footer_bytes < size else size
    out_o, out_l = [], []
    while pos + 4 <= body_end:
        if file_header_bytes > 4 and pos == 0:
            pos = file_header_bytes
            continue
        if big_endian:
            ln = int(buf[pos + 1]) + 256 * int(buf[pos])
        else:
            ln = int(buf[pos + 2]) + 256 * int(buf[pos + 3])
        ln += rdw_adjustment
        if ln <= 0:
            raise _framing_error(buf, pos, "zero")
        if ln > MAX_RDW_RECORD_SIZE:
            raise _framing_error(buf, pos, "big")
        out_o.append(pos + 4)
        out_l.append(min(ln, body_end - (pos + 4)))
        pos += 4 + ln
    return (np.asarray(out_o, dtype=np.int64),
            np.asarray(out_l, dtype=np.int64))


LENGTH_FIELD_BINARY_BE = 0
LENGTH_FIELD_BINARY_LE = 1
LENGTH_FIELD_DISPLAY_EBCDIC = 2
LENGTH_FIELD_DISPLAY_ASCII = 3


def length_field_scan(data, field_offset: int, field_width: int, kind: int,
                      length_adjust: int = 0
                      ) -> Tuple[np.ndarray, np.ndarray, int]:
    """Frame records whose byte length is a field inside each record.
    Returns (offsets, lengths, resume_pos): resume_pos < len(data) means an
    unreadable length field stopped the scan there (caller decides)."""
    buf = _as_u8(data)
    size = buf.size
    cap = max(16, size // max(field_offset + field_width, 1) + 2)
    offsets = np.empty(cap, dtype=np.int64)
    lengths = np.empty(cap, dtype=np.int64)
    lib = _load()
    if lib is not None:
        err = ctypes.c_int64(size)
        n = lib.length_field_scan(buf, size, field_offset, field_width,
                                  kind, length_adjust, offsets, lengths,
                                  cap, ctypes.byref(err))
        resume = err.value if err.value < size else (
            int(offsets[n - 1] + lengths[n - 1]) if n else 0)
        if n and offsets[n - 1] + lengths[n - 1] >= size:
            resume = size
        return offsets[:n].copy(), lengths[:n].copy(), resume
    out_o, out_l = [], []
    pos = 0
    while pos < size:
        if pos + field_offset + field_width > size:
            break
        f = buf[pos + field_offset: pos + field_offset + field_width]
        value = 0
        bad = False
        if kind == LENGTH_FIELD_BINARY_BE:
            for b in f:
                value = (value << 8) | int(b)
        elif kind == LENGTH_FIELD_BINARY_LE:
            for b in f[::-1]:
                value = (value << 8) | int(b)
        else:
            for b in f:
                b = int(b)
                if kind == LENGTH_FIELD_DISPLAY_EBCDIC:
                    if b == 0x40:
                        continue
                    if not (0xF0 <= b <= 0xF9):
                        bad = True
                        break
                    value = value * 10 + (b - 0xF0)
                else:
                    if b == 0x20:
                        continue
                    if not (0x30 <= b <= 0x39):
                        bad = True
                        break
                    value = value * 10 + (b - 0x30)
        value += length_adjust
        if bad or value <= 0:
            return (np.asarray(out_o, dtype=np.int64),
                    np.asarray(out_l, dtype=np.int64), pos)
        out_o.append(pos)
        out_l.append(min(value, size - pos))
        pos += value
    return (np.asarray(out_o, dtype=np.int64),
            np.asarray(out_l, dtype=np.int64),
            size if not out_o or out_o[-1] + out_l[-1] >= size else pos)


def text_scan(data) -> Tuple[np.ndarray, np.ndarray]:
    """(offset, length) of LF/CRLF-delimited text records."""
    buf = _as_u8(data)
    lib = _load()
    if lib is not None:
        cap = buf.size + 1
        offsets = np.empty(cap, dtype=np.int64)
        lengths = np.empty(cap, dtype=np.int64)
        n = lib.text_scan(buf, buf.size, offsets, lengths, cap)
        return offsets[:n].copy(), lengths[:n].copy()
    out_o, out_l = [], []
    pos = 0
    size = buf.size
    nl = np.flatnonzero(buf == 0x0A)
    for eol in list(nl) + ([size] if size and (not len(nl) or nl[-1] != size - 1)
                           else []):
        end = int(eol)
        if end > pos and buf[end - 1] == 0x0D:
            end -= 1
        out_o.append(pos)
        out_l.append(end - pos)
        pos = int(eol) + 1
    return (np.asarray(out_o, dtype=np.int64),
            np.asarray(out_l, dtype=np.int64))


DISPLAY_EBCDIC = 0
DISPLAY_ASCII = 1


def _batch_and_offsets(batch: np.ndarray, col_offsets: np.ndarray):
    b = np.ascontiguousarray(batch, dtype=np.uint8)
    offs = np.ascontiguousarray(col_offsets, dtype=np.int64)
    return b, offs


def decode_binary_cols(batch: np.ndarray, col_offsets: np.ndarray,
                       width: int, signed: bool, big_endian: bool
                       ) -> Optional[Tuple[np.ndarray, np.ndarray]]:
    """All same-width COMP columns of a packed [n, extent] batch in one
    native pass (ops/batch_np.decode_binary semantics). None when the
    native library is unavailable (caller uses the numpy slab path)."""
    lib = _load()
    if lib is None:
        return None
    b, offs = _batch_and_offsets(batch, col_offsets)
    n, extent = b.shape
    ncols = offs.shape[0]
    values = np.empty((n, ncols), dtype=np.int64)
    valid = np.empty((n, ncols), dtype=np.uint8)
    lib.decode_binary_cols(b, n, extent, offs, ncols, width,
                           int(signed), int(big_endian), values, valid)
    return values, valid.view(bool)


def decode_bcd_cols(batch: np.ndarray, col_offsets: np.ndarray, width: int
                    ) -> Optional[Tuple[np.ndarray, np.ndarray]]:
    """All same-width COMP-3 columns in one native pass
    (ops/batch_np.decode_bcd semantics)."""
    lib = _load()
    if lib is None:
        return None
    b, offs = _batch_and_offsets(batch, col_offsets)
    n, extent = b.shape
    ncols = offs.shape[0]
    values = np.empty((n, ncols), dtype=np.int64)
    valid = np.empty((n, ncols), dtype=np.uint8)
    lib.decode_bcd_cols(b, n, extent, offs, ncols, width, values, valid)
    return values, valid.view(bool)


def decode_display_cols(batch: np.ndarray, col_offsets: np.ndarray,
                        width: int, kind: int, signed: bool, allow_dot: bool,
                        require_digits: bool, dyn_sf: int = 0
                        ) -> Optional[Tuple[np.ndarray, np.ndarray, np.ndarray]]:
    """All same-shaped DISPLAY numeric columns in one native pass
    (ops/batch_np.decode_display_{ebcdic,ascii} semantics incl. the
    PIC P dynamic exponent plane)."""
    lib = _load()
    if lib is None:
        return None
    b, offs = _batch_and_offsets(batch, col_offsets)
    n, extent = b.shape
    ncols = offs.shape[0]
    values = np.empty((n, ncols), dtype=np.int64)
    valid = np.empty((n, ncols), dtype=np.uint8)
    dots = np.empty((n, ncols), dtype=np.int64)
    lib.decode_display_cols(b, n, extent, offs, ncols, width, kind,
                            int(signed), int(allow_dot), int(require_digits),
                            int(dyn_sf), values, valid, dots)
    return values, valid.view(bool), dots


def _wide_outputs(n: int, ncols: int):
    return (np.empty((n, ncols), dtype=np.uint64),
            np.empty((n, ncols), dtype=np.uint64),
            np.empty((n, ncols), dtype=np.uint8),
            np.empty((n, ncols), dtype=np.uint8))


def decode_bcd_wide_cols(batch: np.ndarray, col_offsets: np.ndarray,
                         width: int):
    """Wide (19-38 digit) COMP-3 columns -> uint128 magnitude limb pairs
    (ops/batch_np.decode_bcd_wide semantics)."""
    lib = _load()
    if lib is None:
        return None
    b, offs = _batch_and_offsets(batch, col_offsets)
    n, extent = b.shape
    ncols = offs.shape[0]
    hi, lo, neg, valid = _wide_outputs(n, ncols)
    lib.decode_bcd_wide_cols(b, n, extent, offs, ncols, width,
                             hi, lo, neg, valid)
    return hi, lo, neg.view(bool), valid.view(bool)


def decode_binary_wide_cols(batch: np.ndarray, col_offsets: np.ndarray,
                            width: int, signed: bool, big_endian: bool):
    """9-16 byte two's complement columns -> uint128 limb pairs
    (ops/batch_np.decode_binary_wide semantics)."""
    lib = _load()
    if lib is None:
        return None
    b, offs = _batch_and_offsets(batch, col_offsets)
    n, extent = b.shape
    ncols = offs.shape[0]
    hi, lo, neg, valid = _wide_outputs(n, ncols)
    lib.decode_binary_wide_cols(b, n, extent, offs, ncols, width,
                                int(signed), int(big_endian),
                                hi, lo, neg, valid)
    return hi, lo, neg.view(bool), valid.view(bool)


NUMERIC_GROUP_BINARY = 0
NUMERIC_GROUP_BCD = 1
NUMERIC_GROUP_DISPLAY_EBCDIC = 2
NUMERIC_GROUP_DISPLAY_ASCII = 3


class NumericGroupsPlan:
    """Pre-marshaled static descriptor arrays for decode_numeric_groups.

    Rebuilt per decode call these cost milliseconds of GIL-held numpy/
    ctypes work on many-group profiles (exp1: 59 groups) — the chunked
    pipeline pays that once per CHUNK, so decoders cache one plan per
    group subset and only the per-call output buffers remain."""

    __slots__ = ("ng", "kinds", "widths", "ncols", "flags", "dyn_sfs",
                 "offs_list", "offs_ptrs", "has_dots")

    def __init__(self, groups):
        ng = len(groups)
        self.ng = ng
        self.kinds = np.empty(ng, dtype=np.int32)
        self.widths = np.empty(ng, dtype=np.int32)
        self.ncols = np.empty(ng, dtype=np.int64)
        self.flags = np.zeros(ng, dtype=np.int32)
        self.dyn_sfs = np.zeros(ng, dtype=np.int32)
        self.offs_list = []
        self.has_dots = []
        for i, g in enumerate(groups):
            offs = np.ascontiguousarray(g["offsets"], dtype=np.int64)
            self.offs_list.append(offs)
            self.kinds[i] = g["kind"]
            self.widths[i] = g["width"]
            self.ncols[i] = offs.shape[0]
            self.flags[i] = (int(bool(g.get("signed")))
                             | (int(bool(g.get("big_endian"))) << 1)
                             | (int(bool(g.get("allow_dot"))) << 2)
                             | (int(bool(g.get("require_digits"))) << 3))
            self.dyn_sfs[i] = int(g.get("dyn_sf", 0))
            self.has_dots.append(
                g["kind"] >= NUMERIC_GROUP_DISPLAY_EBCDIC)
        self.offs_ptrs = np.asarray([a.ctypes.data for a in self.offs_list],
                                    dtype=np.uintp)


def decode_numeric_groups(batch: np.ndarray, groups, plan=None):
    """Merged one-pass decode of MANY narrow numeric kernel groups from a
    packed [n, extent] batch — each record's bytes are touched once for
    the whole plane instead of once per group. `groups`: list of dicts
    with keys kind (NUMERIC_GROUP_*), offsets, width, and (per kind)
    signed/big_endian/allow_dot/require_digits/dyn_sf — or None when a
    prebuilt `plan` (NumericGroupsPlan) is passed. Returns a list
    aligned to the groups: (values, valid) or (values, valid, dot_scale)
    for display kinds. None when the native library is unavailable."""
    lib = _load()
    if lib is None or (not groups and plan is None):
        return None
    b = np.ascontiguousarray(batch, dtype=np.uint8)
    n, extent = b.shape
    if plan is None:
        plan = NumericGroupsPlan(groups)
    ng = plan.ng
    values, valids, dots = [], [], []
    for i in range(ng):
        nc = int(plan.ncols[i])
        values.append(np.empty((n, nc), dtype=np.int64))
        valids.append(np.empty((n, nc), dtype=np.uint8))
        dots.append(np.empty((n, nc), dtype=np.int64)
                    if plan.has_dots[i] else None)

    def ptrs(arrs):
        return np.asarray([0 if a is None else a.ctypes.data for a in arrs],
                          dtype=np.uintp)
    v_ptrs = ptrs(values)
    ok_ptrs = ptrs(valids)
    dot_ptrs = ptrs(dots)
    lib.decode_numeric_groups(
        b, n, extent, ng, plan.kinds, plan.widths, plan.ncols,
        plan.offs_ptrs.ctypes.data, plan.flags, plan.dyn_sfs,
        v_ptrs.ctypes.data, ok_ptrs.ctypes.data, dot_ptrs.ctypes.data)
    out = []
    for i in range(ng):
        if dots[i] is None:
            out.append((values[i], valids[i].view(bool)))
        else:
            out.append((values[i], valids[i].view(bool), dots[i]))
    return out


def decode_display_wide_cols(batch: np.ndarray, col_offsets: np.ndarray,
                             width: int, kind: int, signed: bool,
                             allow_dot: bool, require_digits: bool,
                             dyn_sf: int = 0):
    """Wide DISPLAY numeric columns -> uint128 limb pairs + dots plane
    (ops/batch_np.decode_display_*_wide semantics)."""
    lib = _load()
    if lib is None:
        return None
    b, offs = _batch_and_offsets(batch, col_offsets)
    n, extent = b.shape
    ncols = offs.shape[0]
    hi, lo, neg, valid = _wide_outputs(n, ncols)
    dots = np.empty((n, ncols), dtype=np.int64)
    lib.decode_display_wide_cols(b, n, extent, offs, ncols, width, kind,
                                 int(signed), int(allow_dot),
                                 int(require_digits), int(dyn_sf),
                                 hi, lo, neg, valid, dots)
    return hi, lo, neg.view(bool), valid.view(bool), dots


def transcode_string_cols(batch: np.ndarray, col_offsets: np.ndarray,
                          width: int, lut_u16: np.ndarray
                          ) -> Optional[np.ndarray]:
    """All same-width EBCDIC string columns of a packed [n, extent] batch
    -> [n, ncols, width] uint16 code points in one native gather+LUT pass
    (ops/batch_np.transcode_ebcdic semantics)."""
    lib = _load()
    if lib is None:
        return None
    b, offs = _batch_and_offsets(batch, col_offsets)
    n, extent = b.shape
    ncols = offs.shape[0]
    lut = np.ascontiguousarray(lut_u16, dtype=np.uint16)
    out = np.empty((n, ncols, width), dtype=np.uint16)
    lib.transcode_string_cols(b, n, extent, offs, ncols, width, lut, out)
    return out


def transcode_string_cols_raw(data, rec_offsets, rec_lengths, col_offsets,
                              width: int, lut_u16: np.ndarray,
                              start_offset: int = 0
                              ) -> Optional[np.ndarray]:
    """Raw-image variant reading straight from the framed file; bytes past
    a record's end transcode like the packed batch's zero padding."""
    lib = _load()
    if lib is None:
        return None
    buf, offs, lens, cols = _raw_args(data, rec_offsets, rec_lengths,
                                      col_offsets, start_offset)
    n = offs.shape[0]
    ncols = cols.shape[0]
    lut = np.ascontiguousarray(lut_u16, dtype=np.uint16)
    out = np.empty((n, ncols, width), dtype=np.uint16)
    lib.transcode_string_cols_raw(buf, offs, lens, n, cols, ncols, width,
                                  lut, out)
    return out


def set_thread_omp_width(n: int) -> None:
    """Cap the CALLING thread's OpenMP team size for subsequent native
    kernel calls (per-thread ICV). The pipeline executor calls this from
    each worker/assembler thread so concurrent chunks split the cores
    instead of oversubscribing them; sequential reads are unaffected."""
    lib = _load()
    if lib is not None:
        lib.set_omp_threads(int(n))


def decimal128_batch(hi, lo, values, neg, valid, dots, use_dots, shifts,
                     maxd):
    """Whole-kernel-group decimal128 build: [k, n] packed column planes ->
    ([k, n, 16] little-endian decimal128 buffers, [k] per-column ok
    flags) in ONE native call. Narrow mode passes `values` (int64
    mantissas, hi/lo/neg None); wide mode passes the uint64 limb planes +
    sign plane. `use_dots[c]`=1 derives the shift per value as
    shifts[c] - dots[c, r]; otherwise shifts[c] is static. maxd[c] bounds
    the unscaled magnitude (0 disables the bound). ok[c]=0 -> the caller
    rebuilds column c via its exact fallback. None when the native
    library is unavailable."""
    lib = _load()
    if lib is None:
        return None
    valid = np.ascontiguousarray(valid, dtype=np.uint8)
    k, n = valid.shape
    out = np.empty((k, n, 16), dtype=np.uint8)
    ok = np.empty(k, dtype=np.uint8)
    # hold every converted array until the call returns — a bare
    # `ascontiguousarray(a).ctypes.data` could free the temporary first
    keep = [None if a is None else np.ascontiguousarray(a)
            for a in (hi, lo, values, neg, dots)]

    def ptr(a):
        return None if a is None else a.ctypes.data

    lib.decimal128_batch(
        n, k, ptr(keep[0]), ptr(keep[1]), ptr(keep[2]), ptr(keep[3]),
        valid, ptr(keep[4]),
        np.ascontiguousarray(use_dots, dtype=np.uint8),
        np.ascontiguousarray(shifts, dtype=np.int64),
        np.ascontiguousarray(maxd, dtype=np.int32), out, ok)
    return out, ok.view(bool)


def decimal128_from_limbs(hi, lo, neg, valid, shifts, max_digits: int = 38):
    """[n] uint128 magnitude limbs (+sign/valid planes, per-value decimal
    shift) -> ([n, 16] little-endian decimal128 bytes, ok mask). None when
    the native library is unavailable; ok[r]=0 marks values needing the
    exact-Decimal fallback (negative shift, magnitude past `max_digits`)."""
    lib = _load()
    if lib is None:
        return None
    hi = np.ascontiguousarray(hi, dtype=np.uint64)
    lo = np.ascontiguousarray(lo, dtype=np.uint64)
    neg = np.ascontiguousarray(neg, dtype=np.uint8)
    ok_in = np.ascontiguousarray(valid, dtype=np.uint8)
    n = hi.shape[0]
    shifts = np.ascontiguousarray(
        np.broadcast_to(np.asarray(shifts, dtype=np.int64), (n,)))
    out = np.empty((n, 16), dtype=np.uint8)
    ok = np.empty(n, dtype=np.uint8)
    lib.decimal128_from_limbs(hi, lo, neg, ok_in, shifts, n,
                              int(max_digits), out, ok)
    return out, ok.view(bool)


def format_seg_id_level(root_rid, counter, prefix: str, level: int, valid):
    """One Seg_Id level column as Arrow string buffers: (int32 offsets
    [n+1], UTF-8 data). `root_rid`: current root's record index per row;
    `counter`: child counter per row (None for level 0); `valid`: rows
    shown (others emit empty — the caller nulls them via the validity
    bitmap). None when the native library is unavailable."""
    lib = _load()
    if lib is None:
        return None
    rid = np.ascontiguousarray(root_rid, dtype=np.int64)
    n = rid.shape[0]
    cnt = (None if counter is None
           else np.ascontiguousarray(counter, dtype=np.int64))
    pref = np.frombuffer(prefix.encode("utf-8"), dtype=np.uint8)
    pref = np.ascontiguousarray(pref)
    ok = np.ascontiguousarray(valid, dtype=np.uint8)
    per_row = len(pref) + 21 + (0 if cnt is None else 25)
    data_cap = n * per_row + 16
    if n + 1 > 2**31 - 16 or data_cap > 2**31 - 16:
        return None
    out_offsets = np.empty(n + 1, dtype=np.int32)
    out_data = np.empty(data_cap, dtype=np.uint8)
    out_len = ctypes.c_int64(0)
    lib.format_seg_id_level(
        rid, None if cnt is None else cnt.ctypes.data, n, pref, len(pref),
        int(level), ok, out_offsets, out_data, data_cap,
        ctypes.byref(out_len))
    ln = out_len.value
    # view when the buffer is mostly full (the common dense case): the
    # Arrow column pins the parent either way
    return out_offsets, (out_data[:ln] if ln * 2 >= data_cap
                         else out_data[:ln].copy())


TRIM_NONE = 0
TRIM_BOTH = 1
TRIM_LEFT = 2
TRIM_RIGHT = 3


def _string_cols_arrow(buf, extent_or_size, rec_offsets, rec_lengths, n,
                       col_offsets, col_widths, lut_u16, trim_mode: int,
                       col_masks=None):
    lib = _load()
    if lib is None:
        return None
    cols = np.ascontiguousarray(col_offsets, dtype=np.int64)
    widths = np.ascontiguousarray(col_widths, dtype=np.int64)
    ncols = cols.shape[0]
    lut = np.ascontiguousarray(lut_u16, dtype=np.uint16)
    # per-column capacity sized for all-ASCII output (the overwhelmingly
    # common case); columns whose UTF-8 output outgrows it fall back.
    # Each column owns its OWN buffers so retaining one column never pins
    # the others' memory (zero-copy views below slice these per column).
    # The +64 slack lets the AVX2 write-then-trim kernel store whole
    # 32-byte chunks (up to 31 bytes past the last value's width)
    data_caps = n * widths + 64
    if n + 1 > 2**31 - 16 or bool((data_caps > 2**31 - 16).any()):
        return None  # int32 offsets can't address this batch
    out_offsets = [np.empty(n + 1, dtype=np.int32) for _ in range(ncols)]
    out_datas = [np.empty(int(c), dtype=np.uint8) for c in data_caps]
    offs_ptrs = np.asarray([a.ctypes.data for a in out_offsets],
                           dtype=np.uintp)
    data_ptrs = np.asarray([a.ctypes.data for a in out_datas],
                           dtype=np.uintp)
    data_lens = np.empty(ncols, dtype=np.int64)
    mask_ptrs_arg = None
    if col_masks is not None and any(m is not None for m in col_masks):
        mask_arrs = [None if m is None
                     else np.ascontiguousarray(m, dtype=np.uint8)
                     for m in col_masks]
        mask_ptrs = np.asarray(
            [0 if m is None else m.ctypes.data for m in mask_arrs],
            dtype=np.uintp)
        mask_ptrs_arg = mask_ptrs.ctypes.data
    lib.transcode_string_cols_arrow(
        buf, extent_or_size,
        None if rec_offsets is None else rec_offsets.ctypes.data,
        None if rec_lengths is None else rec_lengths.ctypes.data,
        n, cols, widths, ncols, mask_ptrs_arg, lut, trim_mode,
        offs_ptrs.ctypes.data, data_ptrs.ctypes.data, data_caps, data_lens)
    result = []
    for c in range(ncols):
        ln = int(data_lens[c])
        if ln < 0:
            result.append(None)  # non-ASCII expansion outgrew the buffer
            continue
        # zero-copy view of this column's own buffer when reasonably
        # full; copy only when most of it would be dead weight (heavy
        # trimming / sparse masks)
        chunk = out_datas[c][:ln]
        if ln * 2 < out_datas[c].size:
            chunk = chunk.copy()
        result.append((out_offsets[c], chunk))
    return result


def string_cols_arrow_packed(batch: np.ndarray, col_offsets, col_widths,
                             lut_u16, trim_mode: int, col_masks=None):
    """String columns (mixed widths) of a packed [n, extent] batch ->
    per-column (int32 offsets [n+1], trimmed UTF-8 bytes) Arrow buffers in
    one native transcode+trim pass. None when the library is unavailable;
    a None entry for a column whose output outgrew the all-ASCII-sized
    buffer. `col_masks`: optional per-column row-visibility masks (rows
    with 0 emit empty strings without transcoding)."""
    lib = _load()
    if lib is None:
        return None
    b = np.ascontiguousarray(batch, dtype=np.uint8)
    n, extent = b.shape
    return _string_cols_arrow(b, extent, None, None, n, col_offsets,
                              col_widths, lut_u16, trim_mode, col_masks)


def string_cols_arrow_raw(data, rec_offsets, rec_lengths, col_offsets,
                          col_widths, lut_u16, trim_mode: int,
                          start_offset: int = 0, col_masks=None):
    """Raw-image variant of string_cols_arrow_packed: reads framed records
    in place; bytes past a record's end behave like zero padding."""
    lib = _load()
    if lib is None:
        return None
    buf, offs, lens, cols = _raw_args(data, rec_offsets, rec_lengths,
                                      col_offsets, start_offset)
    return _string_cols_arrow(buf, buf.size, offs, lens, offs.shape[0],
                              cols, col_widths, lut_u16, trim_mode,
                              col_masks)


def _raw_args(data, rec_offsets, rec_lengths, col_offsets,
              start_offset: int):
    buf = _as_u8(data)
    offs = np.ascontiguousarray(rec_offsets, dtype=np.int64)
    lens = np.ascontiguousarray(rec_lengths, dtype=np.int64)
    if start_offset:
        offs = offs + start_offset
        lens = lens - start_offset
    cols = np.ascontiguousarray(col_offsets, dtype=np.int64)
    return buf, offs, lens, cols


def decode_binary_cols_raw(data, rec_offsets, rec_lengths,
                           col_offsets, width: int, signed: bool,
                           big_endian: bool, start_offset: int = 0,
                           fits32: bool = False
                           ) -> Optional[Tuple[np.ndarray, np.ndarray]]:
    """Same as decode_binary_cols but reading records in place from the
    framed file image (no [n, extent] pack copy). Columns past a record's
    end are invalid. `fits32`: int32 output (declared precision <= 9)."""
    lib = _load()
    if lib is None:
        return None
    buf, offs, lens, cols = _raw_args(data, rec_offsets, rec_lengths,
                                      col_offsets, start_offset)
    n, ncols = offs.shape[0], cols.shape[0]
    values = np.empty((n, ncols), dtype=np.int32 if fits32 else np.int64)
    valid = np.empty((n, ncols), dtype=np.uint8)
    lib.decode_binary_cols_raw(buf, offs, lens, n, cols, ncols, width,
                               int(signed), int(big_endian), int(fits32),
                               values.ctypes.data, valid)
    return values, valid.view(bool)


def decode_bcd_cols_raw(data, rec_offsets, rec_lengths, col_offsets,
                        width: int, start_offset: int = 0,
                        fits32: bool = False
                        ) -> Optional[Tuple[np.ndarray, np.ndarray]]:
    """Same as decode_bcd_cols but reading records in place from the
    framed file image. `fits32`: int32 output (precision <= 9)."""
    lib = _load()
    if lib is None:
        return None
    buf, offs, lens, cols = _raw_args(data, rec_offsets, rec_lengths,
                                      col_offsets, start_offset)
    n, ncols = offs.shape[0], cols.shape[0]
    values = np.empty((n, ncols), dtype=np.int32 if fits32 else np.int64)
    valid = np.empty((n, ncols), dtype=np.uint8)
    lib.decode_bcd_cols_raw(buf, offs, lens, n, cols, ncols, width,
                            int(fits32), values.ctypes.data, valid)
    return values, valid.view(bool)


# ---------------------------------------------------------------------------
# fused one-pass columnar assembly (columnar.cpp)
# ---------------------------------------------------------------------------

# decode kinds (columnar.cpp DecodeKind)
ASM_KIND_BINARY = 0
ASM_KIND_BCD = 1
ASM_KIND_DISPLAY_E = 2
ASM_KIND_DISPLAY_A = 3
ASM_KIND_BINARY_WIDE = 4
ASM_KIND_BCD_WIDE = 5
ASM_KIND_DISPLAY_E_WIDE = 6
ASM_KIND_DISPLAY_A_WIDE = 7
ASM_KIND_IEEE_F32 = 8
ASM_KIND_IEEE_F64 = 9
ASM_KIND_IBM_F32 = 10
ASM_KIND_IBM_F64 = 11

# output kinds (columnar.cpp OutKind) and their Arrow buffer item sizes
ASM_OUT_INT32 = 0
ASM_OUT_INT64 = 1
ASM_OUT_FLOAT32 = 2
ASM_OUT_FLOAT64 = 3
ASM_OUT_DECIMAL128 = 4
ASM_OUT_ITEMSIZE = {ASM_OUT_INT32: 4, ASM_OUT_INT64: 8,
                    ASM_OUT_FLOAT32: 4, ASM_OUT_FLOAT64: 8,
                    ASM_OUT_DECIMAL128: 16}
ASM_OUT_DTYPE = {ASM_OUT_INT32: np.int32, ASM_OUT_INT64: np.int64,
                 ASM_OUT_FLOAT32: np.float32, ASM_OUT_FLOAT64: np.float64}

# decimal128 shift modes (columnar.cpp DecMode)
ASM_DEC_STATIC = 0
ASM_DEC_DOTS = 1
ASM_DEC_DIGIT_COUNT = 2


def assemble_cols_arrow(data, rec_offsets, rec_lengths, extent: int,
                        col_offsets, widths, kinds, flags, dyn_sfs,
                        out_kinds, dec_modes, shifts, maxds,
                        out_ptrs, out_strides, valid_ptrs, valid_strides,
                        n: int, row_masks=None):
    """Fused decode -> Arrow assembly over many columns in one native
    pass with the GIL released: values land in the caller's final-dtype
    buffers (strided, so flat OCCURS planes share one buffer), validity
    lands in per-column byte planes for `pack_validity`. Descriptor
    arrays must be C-contiguous of matching length; `rec_offsets` None
    means `data` is a packed [n, extent] batch. `row_masks`: optional
    per-column uint8[n] row-visibility masks (None entries = all rows) —
    masked rows emit null/zero without decoding, so redefine-hidden
    bytes never reach the cell kernels. Returns the per-column
    exact-representation bool array (False -> the caller rebuilds that
    decimal column via its Python fallback), or None when the native
    library is unavailable."""
    lib = _load()
    if lib is None:
        return None
    buf = _as_u8(data)
    ncols = len(col_offsets)
    ok = np.empty(ncols, dtype=np.uint8)
    mask_ptrs_arg = None
    mask_keep = None
    if row_masks is not None and any(m is not None for m in row_masks):
        # dedupe by identity: columns sharing one mask object must hand
        # the kernel one POINTER (the uniform-plane fast path requires
        # every column's mask pointer to match), and bool->uint8
        # conversion would otherwise mint a fresh array per column
        conv: dict = {}
        mask_keep = []
        for m in row_masks:
            if m is None:
                mask_keep.append(None)
                continue
            a = conv.get(id(m))
            if a is None:
                a = np.ascontiguousarray(m, dtype=np.uint8)
                conv[id(m)] = a
            mask_keep.append(a)
        mask_ptrs = np.asarray(
            [0 if m is None else m.ctypes.data for m in mask_keep],
            dtype=np.uintp)
        mask_keep.append(mask_ptrs)  # pin until the call returns
        mask_ptrs_arg = mask_ptrs.ctypes.data
    lib.assemble_cols_arrow(
        buf, extent,
        None if rec_offsets is None else rec_offsets.ctypes.data,
        None if rec_lengths is None else rec_lengths.ctypes.data,
        n, ncols, col_offsets, widths, kinds, flags, dyn_sfs,
        out_kinds, dec_modes, shifts, maxds,
        out_ptrs.ctypes.data, out_strides,
        valid_ptrs.ctypes.data, valid_strides, mask_ptrs_arg, ok)
    return ok.view(bool)


def pack_validity(mask: np.ndarray):
    """Validity byte plane -> (Arrow validity bitmap bytes, null count);
    None when the native library is unavailable."""
    lib = _load()
    if lib is None:
        return None
    m = np.ascontiguousarray(mask, dtype=np.uint8)
    n = m.shape[0]
    bitmap = np.empty((n + 7) // 8, dtype=np.uint8)
    nulls = lib.pack_validity(m, n, 1, bitmap)
    return bitmap, int(nulls)


def simd_level() -> int:
    """Effective runtime SIMD level the loaded library reports (0 scalar,
    1 SSE4.2, 2 AVX2) — the CPU probe clamped by any set_cpu_level /
    COBRIX_FORCE_CPU_LEVEL override; -1 when the library is unavailable."""
    lib = _load()
    if lib is None:
        return -1
    return int(lib.simd_level())


def set_cpu_level(level) -> bool:
    """Pin the native dispatch level for this process: 0/'scalar',
    1/'sse', 2/'avx2', or -1/None to restore auto-detection. The .so
    clamps to the detected capability, so forcing a higher level than
    the CPU supports degrades safely. Returns False when the library is
    unavailable (the Python fallbacks have no dispatch to pin)."""
    lib = _load()
    if lib is None:
        return False
    if level is None:
        lvl = -1
    elif isinstance(level, str):
        lvl = _CPU_LEVELS.get(level.strip().lower())
        if lvl is None:
            raise ValueError(f"unknown CPU level {level!r}; expected one "
                             f"of {sorted(set(_CPU_LEVELS))}")
    else:
        lvl = int(level)
    lib.set_cpu_level(lvl)
    return True


def rdw_scan_segids(data, big_endian: bool, seg_off: int, seg_w: int,
                    rdw_adjustment: int = 0, file_header_bytes: int = 0,
                    file_footer_bytes: int = 0):
    """Fused RDW framing + segment-id gather: one native walk of the file
    image returns (offsets, lengths, seg_bytes) where seg_bytes is the
    [n, seg_w] matrix of each record's segment-id field bytes (zero-
    padded past short records, exactly like pack_records). None when the
    native library is unavailable (caller frames and packs separately).
    Raises the same framing errors as rdw_scan."""
    lib = _load()
    if lib is None:
        return None
    buf = _as_u8(data)
    size = buf.size
    cap = max(16, size // 4 + 2)
    offsets = np.empty(cap, dtype=np.int64)
    lengths = np.empty(cap, dtype=np.int64)
    seg_bytes = np.empty((cap, seg_w), dtype=np.uint8)
    err = ctypes.c_int64(0)
    n = lib.rdw_scan_segids(buf, size, int(big_endian),
                            int(rdw_adjustment), file_header_bytes,
                            file_footer_bytes, int(seg_off), int(seg_w),
                            offsets, lengths, seg_bytes.reshape(-1), cap,
                            ctypes.byref(err))
    if n == -1:
        raise _framing_error(buf, err.value, "zero")
    if n == -2:
        raise _framing_error(buf, err.value, "big")
    return offsets[:n].copy(), lengths[:n].copy(), seg_bytes[:n].copy()


def const_string_col(n: int, value: str):
    """Constant string column as Arrow buffers: (int32 offsets [n+1],
    UTF-8 data of n copies of `value`). Native when available, else a
    numpy/bytes build — both shapes feed StringArray.from_buffers, so the
    generated File-name column never pays a per-row Python object."""
    enc = value.encode("utf-8")
    ln = len(enc)
    if n < 0 or (n + 1) * max(ln, 1) > 2**31 - 16:
        return None
    lib = _load()
    if lib is not None and ln > 0:
        out_offsets = np.empty(n + 1, dtype=np.int32)
        out_data = np.empty(n * ln, dtype=np.uint8)
        lib.fill_const_string(n, np.frombuffer(enc, dtype=np.uint8), ln,
                              out_offsets, out_data)
        return out_offsets, out_data
    offsets = np.arange(n + 1, dtype=np.int32) * ln
    data = np.frombuffer(enc * n, dtype=np.uint8) if ln else \
        np.empty(0, dtype=np.uint8)
    return offsets, data


def pack_records(data, offsets: np.ndarray, lengths: np.ndarray,
                 extent: int, start_offset: int = 0) -> np.ndarray:
    """Zero-padded [n, extent] uint8 batch matrix of the selected records."""
    buf = _as_u8(data)
    offsets = np.ascontiguousarray(offsets, dtype=np.int64)
    lengths = np.ascontiguousarray(lengths, dtype=np.int64)
    n = offsets.shape[0]
    out = np.empty((n, extent), dtype=np.uint8)
    lib = _load()
    if lib is not None:
        lib.pack_records(buf, buf.size, offsets, lengths, n, extent,
                         start_offset, out)
        return out
    out[:] = 0
    for i in range(n):
        off = int(offsets[i]) + start_offset
        ln = min(int(lengths[i]) - start_offset, extent)
        if off < 0 or ln <= 0 or off >= buf.size:
            continue
        ln = min(ln, buf.size - off)
        out[i, :ln] = buf[off:off + ln]
    return out
