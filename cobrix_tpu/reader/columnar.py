"""Columnar batch decoder: [batch, record_len] uint8 -> decoded columns.

The production decode path. A `FieldPlan` (plan/compiler.py) is bound to
kernel launches: columns sharing (codec, width, kernel variant) are decoded
together — one byte slab + one vectorized kernel per group — instead
of the reference's per-record, per-field closure walk
(RecordExtractors.scala:49).

Backends:
- "numpy": batch_np kernels (CPU fast path; also the blueprint).
- "jax":   batch_jax kernels compiled by XLA — the TPU path. The whole
           batch decode is one jitted function; batch sizes are padded to
           buckets so jit retraces are bounded.

Row materialization (`to_rows`) mirrors extract_record's output shape so the
golden-parity suite can compare the columnar path against both the host
extractor and the reference goldens.
"""
from __future__ import annotations

import functools
import threading
from typing import NamedTuple, Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..copybook.ast import Group, Primitive, Statement
from ..copybook.copybook import Copybook
from ..copybook.datatypes import (
    AlphaNumeric,
    Decimal,
    Encoding,
    Integral,
    MAX_INTEGER_PRECISION,
    MAX_LONG_PRECISION,
    SchemaRetentionPolicy,
    TrimPolicy,
    Usage,
)
from .. import native
from ..obs import context as obs_context
from ..obs import fieldcost
from ..ops import batch_np
from ..ops import expand as odo
from ..profiling import LinkCopy, Stage, annotate
from ..plan.cache import cached_code_page_lut, cached_compile_plan
from ..plan.compiler import (Codec, ColumnSpec, FieldPlan,
                             merged_spans as _merged_spans,
                             packed_position)
from .extractors import DecodeOptions
import decimal as _decimal

PyDecimal = _decimal.Decimal

_NUMERIC_CODECS = (Codec.BINARY, Codec.BCD, Codec.DISPLAY_NUM,
                   Codec.DISPLAY_NUM_ASCII)

# wide (uint128) mantissas carry up to 39 digits; the default 28-digit
# context would round them during scaleb
_WIDE_CTX = _decimal.Context(prec=60)


def _exact_scaleb(mantissa: int, e: int) -> "PyDecimal":
    if e == 0:
        return PyDecimal(mantissa)
    return PyDecimal(mantissa).scaleb(e, _WIDE_CTX)
_FLOAT_CODECS = (Codec.FLOAT_IBM, Codec.FLOAT_IEEE, Codec.DOUBLE_IBM,
                 Codec.DOUBLE_IEEE)
_STRING_CODECS = (Codec.EBCDIC_STRING, Codec.ASCII_STRING, Codec.UTF16_STRING,
                  Codec.HEX_STRING, Codec.RAW_BYTES)


def strided_nbytes(leaves) -> int:
    """The bytes of the fetched numpy `leaves` that did not arrive
    C-contiguous (a narrow result comes home rows-minor: PERF.md, PR 33),
    each such leaf whole: what the host has still to transpose before
    Arrow can hold it. A leaf of one row or one column lies both ways,
    and numpy says so."""
    return sum(leaf.nbytes for leaf in leaves
               if not leaf.flags.c_contiguous)


def _is_wide(spec: ColumnSpec) -> bool:
    """>18-digit fields decode through the uint128-limb kernels (the
    reference's BigDecimal plane: BCDNumberDecoders.decodeBigBCDNumber,
    decodeBinaryAribtraryPrecision, decodeEbcdicBigNumber). DISPLAY
    classifies by byte width, not PIC precision: every byte of the field
    could be a digit, and the oracle decodes whatever digits are there."""
    if spec.codec is Codec.BINARY:
        return spec.width > 8
    if spec.codec in (Codec.DISPLAY_NUM, Codec.DISPLAY_NUM_ASCII):
        return spec.width > MAX_LONG_PRECISION
    return spec.params.precision > MAX_LONG_PRECISION


def _variant_key(spec: ColumnSpec) -> tuple:
    p = spec.params
    if spec.codec is Codec.BINARY:
        return (p.signed, p.big_endian, spec.width <= 4, _is_wide(spec))
    if spec.codec is Codec.BCD:
        return (p.precision <= MAX_INTEGER_PRECISION, _is_wide(spec))
    if spec.codec in (Codec.DISPLAY_NUM, Codec.DISPLAY_NUM_ASCII):
        # only a NEGATIVE scale factor changes the kernel (the dyn_sf
        # digit-count plane); positive sf is applied per column at
        # materialization, so grouping on min(sf, 0) avoids splitting
        # otherwise-identical columns into separate kernel launches.
        # require_digits is unconditional: a digit-less DISPLAY field
        # (blank fill) decodes to null for integrals, explicit-point
        # AND implied-point decimals alike — the value the encoder's
        # blank fill for None round-trips back to
        return (p.signed, p.explicit_decimal, True,
                spec.width <= MAX_INTEGER_PRECISION,
                min(p.scale_factor, 0),
                _is_wide(spec))
    return ()


def _dyn_scale(spec: ColumnSpec) -> bool:
    """PIC P with a negative scale factor on a DISPLAY/BINARY field: the
    reference's exponent depends on the decoded digit count
    (addDecimalPoint, BinaryUtils.scala:208-211), so it rides the
    per-value dot_scale plane instead of a static exponent."""
    return (spec.params.scale_factor < 0
            and spec.codec is not Codec.BCD)


def _digit_count(values, xp=np):
    """Decimal digit count of |value| for int64 planes; array-module
    parameterized so the host path (numpy) and the traced DeviceAggregator
    program (jnp) share the exact 10^k boundary logic."""
    absv = xp.abs(values.astype(xp.int64))
    nd = xp.ones(absv.shape, dtype=xp.int32)
    for k in range(1, 19):
        nd = nd + (absv >= 10 ** k)
    # int64 min has no positive abs; it carries 19 decimal digits
    return xp.where(absv < 0, 19, nd)


def _digit_count_limbs(hi, lo, xp=np):
    """Same for uint128 magnitudes held as (hi, lo) uint64 limb planes."""
    hi = hi.astype(xp.uint64)
    lo = lo.astype(xp.uint64)
    nd = xp.ones(hi.shape, dtype=xp.int32)
    for k in range(1, 39):
        p = 10 ** k
        ph = xp.uint64(p >> 64)
        pl = xp.uint64(p & 0xFFFFFFFFFFFFFFFF)
        nd = nd + ((hi > ph) | ((hi == ph) & (lo >= pl)))
    return nd


def _binary_dyn_dots(values: np.ndarray, sf: int) -> np.ndarray:
    """dot_scale plane for a narrow binary PIC P column: |sf| + number of
    decimal digits in str(|value|)."""
    return _digit_count(values).astype(np.int64) - sf


def _wide_dyn_dots(hi: np.ndarray, lo: np.ndarray, sf: int) -> np.ndarray:
    """Same for a wide (uint128-limb magnitude) binary PIC P column."""
    return _digit_count_limbs(hi, lo).astype(np.int64) - sf


# TrimPolicy -> native transcode+trim kernel mode (framing.cpp
# transcode_string_cols_arrow): BOTH is Java String.trim (cp <= 0x20),
# LEFT/RIGHT strip " \t" (scalar_decoders._trim parity)
_NATIVE_TRIM_MODES = {TrimPolicy.NONE: native.TRIM_NONE,
                      TrimPolicy.BOTH: native.TRIM_BOTH,
                      TrimPolicy.LEFT: native.TRIM_LEFT,
                      TrimPolicy.RIGHT: native.TRIM_RIGHT}


@functools.lru_cache(maxsize=1)
def _ascii_mask_lut() -> np.ndarray:
    """uint16 LUT expressing ops/batch_np.mask_ascii (control chars and
    high bytes -> space) for the native string kernel."""
    lut = np.arange(256, dtype=np.uint16)
    lut[(lut < 32) | (lut >= 0x80)] = 0x20
    return lut


@functools.lru_cache(maxsize=1)
def _identity_lut() -> np.ndarray:
    """The table that leaves a code point as it is: what the native
    string kernel takes for bytes a device has already looked up."""
    return np.arange(256, dtype=np.uint16)


def _scatter_rows(arr: np.ndarray, mask: np.ndarray, n: int) -> np.ndarray:
    """Expand a subset-row kernel output back to full batch length: rows
    outside `mask` are zeros (valid=False for bool planes)."""
    out = np.zeros((n,) + arr.shape[1:], dtype=arr.dtype)
    out[mask] = arr
    return out


def _scatter_outputs(outputs: Dict[int, dict], mask: np.ndarray,
                     n: int) -> Dict[int, dict]:
    """Scatter the columns' output dicts of one subset decode from subset
    rows to full length. Only array planes reach here (values, limbs,
    dot scales, a device backend's code-point matrices): the host
    kernels' string codecs defer before the masked routing, and
    HOST_FALLBACK groups are excluded from it explicitly in decode_raw.
    A group matrix that `_store_numeric` handed out column by column is
    scattered once, and the columns stay views of it (their `plane`
    stays true)."""
    whole: Dict[int, np.ndarray] = {}

    def matrix(m):
        full = whole.get(id(m))
        if full is None:
            full = whole[id(m)] = _scatter_rows(m, mask, n)
        return full

    result = {}
    for col, out in outputs.items():
        plane = out.get("plane")
        of_plane = ("char_plane",) if plane is None else (
            "plane", "values", "valid")
        full = {k: _scatter_rows(np.asarray(v), mask, n)
                for k, v in out.items() if k not in of_plane}
        if plane is not None:
            values, valid, pos = plane
            values, valid = matrix(values), matrix(valid)
            full.update(values=values[:, pos], valid=valid[:, pos],
                        plane=(values, valid, pos))
        result[col] = full
    return result


def _masks_equal(a, b) -> bool:
    """Compare two per-column mask lists (None or [bool array|None, ...])
    by value — bool-array memcmp, cheap next to a string rebuild."""
    if a is None or b is None:
        return a is None and b is None
    if len(a) != len(b):
        return False
    for ma, mb in zip(a, b):
        if ma is None or mb is None:
            if ma is not mb:
                return False
        elif ma is not mb and not np.array_equal(ma, mb):
            return False
    return True


class _KernelGroup:
    def __init__(self, codec: Codec, width: int, variant: tuple,
                 columns: List[ColumnSpec], names: Tuple[str, ...],
                 segment: Optional[str] = None):
        self.codec = codec
        self.width = width
        self.variant = variant
        self.columns = columns
        # the segment redefine (upper case) whose rows alone read these
        # columns; None: every row reads them (_column_owner)
        self.segment = segment
        self.offsets = np.array([c.offset for c in columns], dtype=np.int64)
        # cost-attribution identity: plan-resolved field names (OCCURS
        # slots repeat a name and merge into one cost row; names reused
        # across statements arrive path-qualified — FieldPlan.cost_name)
        # and the kernel-family label shown in the explain table
        self.names = names
        self.label = f"{codec.value}/w{width}"

    @property
    def wide(self) -> bool:
        """uint128-limb output layout (values_hi/values/negative planes)."""
        return (self.codec in _NUMERIC_CODECS and self.variant
                and self.variant[-1] is True)


class GroupPlanes(NamedTuple):
    """One kernel group's program outputs by name, each `[rows, columns]`
    (the tuple's order by codec is `_run_group_jax`'s and the Pallas
    kernel's): `values` the int32/int64 mantissas, float bit patterns or
    string code points, for a wide group the low uint64 limb beside `hi`
    and `negative`; `valid`; `dots` the per-value exponent plane of
    DISPLAY groups. None where the group has no such plane."""

    values: object
    valid: object = None
    dots: object = None
    hi: object = None
    negative: object = None


def group_planes(g: "_KernelGroup", out) -> GroupPlanes:
    """`out`, the output tuple of group `g`, read by name: what the
    device aggregate's reductions and predicate read a group through."""
    if g.codec in _STRING_CODECS or g.codec is Codec.HOST_FALLBACK:
        return GroupPlanes(out[0] if out else None)
    if g.wide:
        return GroupPlanes(out[1], out[3], out[4] if len(out) > 4 else None,
                           hi=out[0], negative=out[2])
    return GroupPlanes(out[0], out[1], out[2] if len(out) > 2 else None)


def _column_owner(spec: ColumnSpec) -> Optional[str]:
    """The segment redefine (upper case) whose rows alone read the
    column; None for a column outside every redefine, and for a
    DEPENDING ON dependee: the oracle's walk registers its counter on
    EVERY row, from whatever bytes are there (other segments' overlays
    included), so no row mask may ever hide it."""
    if spec.segment is None or (spec.statement is not None
                                and spec.statement.is_dependee):
        return None
    return spec.segment.upper()


def fixed_point_exponent(spec: ColumnSpec) -> int:
    """Constant power-of-ten exponent for a non-explicit-decimal fixed-point
    column: value = mantissa * 10**e. Shared by the row path and the Arrow
    columnar output (same branches as the reference's decimal placement,
    BCDNumberDecoders.scala:83-162 scale/scaleFactor rules). Negative
    scale factors on DISPLAY/BINARY are dynamic (see _dyn_scale) and never
    reach this path."""
    dt = spec.dtype
    sf = spec.params.scale_factor
    if isinstance(dt, Decimal) and dt.usage is Usage.COMP3:
        n_digits = spec.width * 2 - 1
        return sf if sf > 0 else sf - n_digits if sf < 0 else -spec.params.scale
    if sf > 0:
        # addDecimalPoint appends sf zeros and ignores the scale
        return sf
    return -spec.params.scale


def _rebuild_with_handler(value, group: Group, handler):
    """Re-materialize a nested tuple record (from the per-cell path)
    through a RecordHandler — the non-compiled twin of _row_maker's
    handler.create calls."""
    if value is None:
        return None
    out = []
    non_filler = [st for st in group.children if not st.is_filler]
    for st, v in zip(non_filler, value):
        if isinstance(st, Group):
            if st.is_array:
                out.append(None if v is None else
                           [_rebuild_with_handler(e, st, handler)
                            for e in v])
            else:
                out.append(_rebuild_with_handler(v, st, handler))
        else:
            out.append(v)
    return handler.create(out, group)


def _resolve_occurs(st: Statement, dep_value) -> int:
    """DEPENDING ON value -> element count (clamp + string-handler rules,
    reference RecordExtractors.scala:68-80). Shared by the per-cell and
    compiled row-assembly paths."""
    max_size = st.array_max_size
    if dep_value is None:
        return max_size
    if isinstance(dep_value, str):
        dep_value = st.depending_on_handlers.get(dep_value, max_size)
    else:
        dep_value = int(dep_value)
    if st.array_min_size <= dep_value <= max_size:
        return dep_value
    return max_size


def _pallas_group_spec(g: _KernelGroup):
    """StridedGroup for the fused Pallas kernel, or None if the group stays
    on the XLA route of build_jax_decode_fn (strings, floats) or on the
    host (host fallback).
    Every numeric plane is fused: int32 lanes natively, 10-18-digit and
    wide (BigDecimal) fields via base-2^16 limb arithmetic in int32
    lanes; the kernel reads a group of fewer than 128 columns from the
    transposed record matrix, whatever its offsets, and a wider one from
    byte planes XLA cuts (strided slices, gathers for irregular offsets)."""
    from ..ops import pallas_tpu

    if g.codec is Codec.BINARY:
        signed, big_endian, fits32, wide = g.variant
        out = "i32" if fits32 else "wide" if wide else "i64"
        return pallas_tpu.StridedGroup(
            g.offsets, g.width, "binary", out,
            signed=signed, big_endian=big_endian)
    if g.codec is Codec.BCD:
        fits32, wide = g.variant
        out = "i32" if fits32 else "wide" if wide else "i64"
        return pallas_tpu.StridedGroup(g.offsets, g.width, "bcd", out)
    if g.codec in (Codec.DISPLAY_NUM, Codec.DISPLAY_NUM_ASCII):
        signed, allow_dot, require_digits, fits32, sf, wide = g.variant
        out = "i32" if fits32 else "wide" if wide else "i64"
        kind = ("display_ebcdic" if g.codec is Codec.DISPLAY_NUM
                else "display_ascii")
        return pallas_tpu.StridedGroup(
            g.offsets, g.width, kind, out, signed=signed,
            allow_dot=allow_dot, require_digits=require_digits,
            dyn_sf=min(sf, 0))
    return None


# a group on the XLA route reads its bytes by static slices, up to this
# many (one per run of adjacent or evenly spaced columns; `width` for a run
# with other bytes between its columns); past it the group keeps XLA's
# gather, one operation whatever the columns, which a TPU runs element by
# element. The slices always run faster; the limit bounds what they add to
# lowering and compiling. exp1 with every group on this route
# (backend="jax") needs at most 6 a group; for a described v5e a group of
# 32 slices compiles as fast as its gather, one of 128 in 30 s against 4,
# one of 256 in 66 s against 5 (PERF.md section 6, PR 27)
SLICE_PIECES_MAX = 64


def _slice_pieces(offsets, width: int) -> List[Tuple[int, int, int]]:
    """A group's columns, in their order, as runs in arithmetic
    progression: [(first offset, columns, stride)], stride >= width (a
    lone column is dense: stride == width)."""
    pieces: List[Tuple[int, int, int]] = []
    for off in (int(o) for o in offsets):
        if pieces:
            start, count, stride = pieces[-1]
            step = off - (start + (count - 1) * stride)
            if step >= width and (count == 1 or step == stride):
                pieces[-1] = (start, count + 1, step)
                continue
        pieces.append((off, 1, width))
    return pieces


def _dense(piece: Tuple[int, int, int], width: int) -> bool:
    return piece[1] == 1 or piece[2] == width


def _groups_extent(groups) -> int:
    """The furthest byte any column of `groups` reads (at least 1)."""
    return max((int(g.offsets.max()) + g.width
                for g in groups if len(g.columns)), default=1)


def _group_pieces(g: _KernelGroup) -> Optional[list]:
    """How a group on the XLA route reads its bytes: its columns as runs
    in arithmetic progression, each one static slice (`width` of them
    for a run with other bytes between its columns), or None where that
    takes more than SLICE_PIECES_MAX slices and the gather stays."""
    pieces = _slice_pieces(g.offsets, g.width)
    slices = sum(1 if _dense(piece, g.width) else g.width
                 for piece in pieces)
    return pieces if slices <= SLICE_PIECES_MAX else None


class StringPoints(NamedTuple):
    """Where the EBCDIC string columns of one device program lie in
    `points`, the one [rows, width] matrix of code points the program
    hands back for them all (build_jax_decode_fn): first `spans`, the
    record's byte ranges that groups of adjacent columns cover, merged
    where fields touch or overlap (each byte once however many redefines
    read it) and laid side by side; behind them `blocks`, one
    (group index, run of columns or None for a gathered slab) a group
    with other bytes between its columns, the group's columns in their
    order. All of it is known from the plan."""

    spans: list
    blocks: list
    # group index -> [(position, columns)] runs, in the group's order
    reads: dict
    # column index -> (position of its first code point, width)
    columns: dict
    width: int
    # of a code point (ColumnarDecoder.points_dtype)
    dtype: type
    # which of the program's tuples holds the matrix
    index: int

    @property
    def row_bytes(self) -> int:
        return self.width * np.dtype(self.dtype).itemsize


def _string_points(groups, dtype) -> Optional[StringPoints]:
    """The StringPoints of a program over `groups` (None: it has no
    EBCDIC string column). A group whose columns all lie side by side
    goes into the merged spans; one with other bytes between columns
    keeps its columns together, in their order, behind the spans: the
    slots of an OCCURS are then evenly spaced in `points` as they were
    in the group's own slab."""
    pieces_of = {gi: _group_pieces(g) for gi, g in enumerate(groups)
                 if g.codec is Codec.EBCDIC_STRING}
    if not pieces_of:
        return None
    spanned = {gi for gi, pieces in pieces_of.items() if pieces
               and all(_dense(piece, groups[gi].width) for piece in pieces)}
    spans = _merged_spans(
        (piece[0], piece[0] + piece[1] * groups[gi].width)
        for gi in spanned for piece in pieces_of[gi])
    cursor = sum(hi - lo for lo, hi in spans)
    blocks, reads, columns = [], {}, {}
    for gi, pieces in pieces_of.items():
        g = groups[gi]
        reads[gi] = []
        for piece in pieces or [None]:
            count = len(g.columns) if piece is None else piece[1]
            if gi in spanned:
                at = packed_position(spans, piece[0])
            else:
                at = cursor
                blocks.append((gi, piece))
                cursor += count * g.width
            reads[gi].append((at, count))
        starts = (first + k * g.width for first, count in reads[gi]
                  for k in range(count))
        for c, start in zip(g.columns, starts):
            columns[c.index] = (start, g.width)
    return StringPoints(spans, blocks, reads, columns, cursor, dtype,
                        len(groups))


def _fetched_bytes(g: _KernelGroup) -> int:
    """Bytes a row that the device program hands back in `g`'s own
    tuple, from the plan alone (the dtypes of `_run_group_jax`'s
    tuples). EBCDIC strings come back in the program's one matrix
    (StringPoints.row_bytes), not here."""
    if g.codec is Codec.HOST_FALLBACK or g.codec is Codec.EBCDIC_STRING:
        return 0
    if g.codec in _STRING_CODECS:
        return len(g.columns) * g.width
    if g.wide:
        cell = 8 + 8 + 1 + 1
    elif g.codec in (Codec.DOUBLE_IBM, Codec.DOUBLE_IEEE):
        cell = 8 + 1
    elif g.codec in _FLOAT_CODECS:
        cell = 4 + 1
    else:
        fits32 = g.variant[{Codec.BINARY: 2, Codec.BCD: 0}.get(g.codec, 3)]
        cell = (4 if fits32 else 8) + 1
    if g.codec in (Codec.DISPLAY_NUM, Codec.DISPLAY_NUM_ASCII):
        cell += 4  # the dot_scale plane
    return len(g.columns) * cell


class _RowSet:
    """The rows of a batch under one active segment redefine (`name`;
    None: the rows under no mask), with the kernel groups they read:
    the common ones and that redefine's."""

    __slots__ = ("name", "mask", "rows", "groups", "extent")

    def __init__(self, name, mask, rows, groups):
        self.name = name
        self.mask = mask          # the caller's own mask object
        self.rows = rows          # ascending row indices
        self.groups = groups      # in the order of `kernel_groups`
        self.extent = _groups_extent(groups)

    @property
    def own(self):
        """The redefine's own groups; the others are every set's."""
        return [g for g in self.groups
                if self.name is not None and g.segment == self.name]


class _SubsetPlanes:
    """One kernel group decoded for its redefine's own rows only:
    `outputs` are its columns' output dicts over [rows of the set, ...]
    arrays (None: the batch has no such row), `mask` says which rows of
    the batch they are."""

    __slots__ = ("mask", "group", "outputs")

    def __init__(self, mask, group, outputs):
        self.mask = mask
        self.group = group
        self.outputs = outputs

    def columns(self, decoder: "ColumnarDecoder") -> Dict[int, dict]:
        """The output dicts to scatter from: over no rows at all where
        the batch has no row of the redefine."""
        if self.outputs is None:
            self.outputs = decoder.zero_row_outputs(self.group)
        return self.outputs


class DecodedBatch:
    """Decoded columns of one record batch."""

    def __init__(self, decoder: "ColumnarDecoder", data: np.ndarray,
                 outputs: Dict[int, dict],
                 lengths: Optional[np.ndarray] = None,
                 raw_source: Optional[tuple] = None,
                 compact: bool = False):
        self.decoder = decoder
        # `compact`: the rows are as the file holds them and the device
        # laid them out (variable regions): the host's copy is expanded
        # when something reads it (truncated tails, host-fallback columns)
        self._data = data
        self._compact = compact
        self.n_records = data.shape[0]
        # col index -> {"values","valid","plane","dot_scale","bytes"}
        self._out = outputs
        self._str_cache: Dict[int, List[str]] = {}
        self._col_cache: Dict[int, list] = {}
        self._maker_cache: Dict[tuple, object] = {}
        # id(group) -> (masks, buffers); (id(matrix), slots) -> (columns,
        # masks, buffers) for a device's code points (_point_string_buffers)
        self._arrow_str_cache: Dict[object, tuple] = {}
        self._arrow_dec_cache: Dict[int, dict] = {}   # id(group) -> {col: Array|None}
        # fused native assembly caches (arrow_out): scalar col -> pa.Array,
        # and (id(statement), slot_path) -> flat OCCURS values array
        self._asm_cache: Optional[dict] = None
        self._asm_flat_cache: Dict[tuple, object] = {}
        # actual byte length of each record when shorter than the padded row
        # (variable-length files); columns past a record's end are null /
        # truncated like reference Primitive.decodeTypeValue (Primitive.scala:102)
        self.lengths = lengths
        # (buf, rec_offsets, rec_lengths) when decoded in place from the
        # file image — the packed `data` matrix then covers only the narrow
        # prefix, so lazy string columns transcode from here instead
        self.raw_source = raw_source
        # the read's cost accumulator, captured at DECODE time (the obs
        # context is active here) so lazy work on this batch — string
        # transcode, Arrow assembly — attributes to the right read even
        # when it runs after read_cobol returned (sequential to_arrow)
        # or on a thread pool that never activated the context
        self.field_costs = fieldcost.current()
        # the read's fused-pass counters, captured the same way: lazy
        # Arrow assembly / string transcode increment these after the
        # obs context died (profiling.PassCounters; None outside a read)
        ctx = obs_context.current()
        self.pass_counts = ctx.pass_counts if ctx is not None else None
        # the read's DeviceStats, captured the same way: the assembly
        # stages (arrow_out) count on it after the read returned
        self.stage_stats = ctx.device_stats if ctx is not None else None

    @property
    def data(self) -> np.ndarray:
        """The packed [n, extent] rows in the plan's static layout."""
        if self._compact:
            self._data = self.decoder.expand_host(self._data)[0]
            self._compact = False
        return self._data

    # -- vectorized access -------------------------------------------------

    def column_arrays(self, col: int) -> dict:
        out = self._out[col]
        if "lazy_string" in out:
            self._materialize_strings(out["lazy_string"][0])
            out = self._out[col]
        elif "lazy_numeric" in out:
            self._materialize_numeric(out["lazy_numeric"][0])
            out = self._out[col]
        elif len(out) == 1 and "subset" in out:
            # a column decoded for its redefine's rows alone, asked for
            # by position (rows, the hierarchical walk, scalars and
            # strings of the Arrow path): this column's arrays go to
            # their places, hidden rows zeros and invalid. Its group's
            # matrices stay as they are for `plane_of` (a scalar that
            # shares a matrix with an OCCURS leaf's slots must not
            # scatter them all)
            part = out["subset"]
            out.update(
                (key, _scatter_rows(np.asarray(arr), part.mask,
                                    self.n_records))
                for key, arr in part.columns(self.decoder)[col].items()
                if key not in ("plane", "char_plane"))
        return out

    # -- subset planes (device launches partitioned by redefine) -----------

    def plane_of(self, col: int, rows_mask=None):
        """(`plane` of the column or None, whether it is a subset plane).
        A column decoded for the rows of `rows_mask` alone (the mask
        object decode_raw was given) hands its [rows of the set, ncols]
        matrices over as they are: the caller wants exactly those rows.
        Any other column answers by position: a subset group is
        scattered to full length first, matrices and all, once."""
        part = self._out[col].get("subset")
        if part is None:
            return self.column_arrays(col).get("plane"), False
        if rows_mask is not None and part.mask is rows_mask:
            return part.columns(self.decoder)[col].get("plane"), True
        self._out.update(_scatter_outputs(
            part.columns(self.decoder), part.mask, self.n_records))
        return self._out[col].get("plane"), False

    # -- lazy numeric planes ----------------------------------------------

    def _materialize_numeric(self, g: "_KernelGroup") -> None:
        """Resolve one lazily-deferred numeric kernel group into the
        (values, valid[, dot_scale]) planes the row/value paths consume.
        Arrow consumers normally never get here — the fused native
        assembly (arrow_out) emits deferred columns straight into Arrow
        buffers — so this cost lands on whoever actually needs Python
        planes (rows, dependee counts, diagnostics)."""
        fc = self.field_costs
        tok = fc.begin() if fc is not None else None
        self._materialize_numeric_impl([g])
        if tok is not None:
            # deferred decode runs at output materialization, so it
            # charges the assemble plane like lazy strings (keeping the
            # decode plane aligned with the decode-STAGE busy time)
            fc.commit(tok, g.names, fieldcost.PLANE_ASSEMBLE,
                      self.n_records * g.width, self.n_records, g.label)

    def materialize_numeric_all(self) -> None:
        """Resolve EVERY still-deferred numeric group in one bulk pass —
        row materialization calls this so whole-batch consumers keep the
        merged one-pass decode instead of a per-group trickle."""
        gs, seen = [], set()
        for out in self._out.values():
            lz = out.get("lazy_numeric")
            if lz is not None and id(lz[0]) not in seen:
                seen.add(id(lz[0]))
                gs.append(lz[0])
        if not gs:
            return
        fc = self.field_costs
        tok = fc.begin() if fc is not None else None
        self._materialize_numeric_impl(gs)
        if tok is not None:
            fc.commit_weighted(
                tok,
                [(g.names, g.width, self.n_records * g.width, g.label)
                 for g in gs],
                fieldcost.PLANE_ASSEMBLE, self.n_records)

    def _materialize_numeric_impl(self, groups) -> None:
        # dispatches through the decoder's existing kernels directly
        # (merged pass first), NOT through _run_groups: its fieldcost
        # regions charge the decode plane, and deferred work running at
        # materialization time belongs on the assemble plane (charged by
        # the callers above)
        dec = self.decoder
        if self.raw_source is None:
            src = self.data
            rest = list(groups)
        else:
            buf, offs, lens = self.raw_source
            rest = []
            for g in groups:
                res = None
                if g.codec is Codec.BINARY and not g.wide:
                    signed, big_endian, fits32, _ = g.variant
                    res = native.decode_binary_cols_raw(
                        buf, offs, lens, g.offsets, g.width, signed,
                        big_endian, fits32=fits32)
                elif g.codec is Codec.BCD and not g.wide:
                    fits32, _ = g.variant
                    res = native.decode_bcd_cols_raw(
                        buf, offs, lens, g.offsets, g.width,
                        fits32=fits32)
                if res is not None:
                    dec._store_numeric(g, self._out, *res)
                else:
                    rest.append(g)
            if not rest:
                return
            extent = max((int(g.offsets.max()) + g.width
                          for g in rest if len(g.columns)), default=1)
            src = native.pack_records(buf, offs, lens, extent)
        rest = dec._run_groups_merged(rest, src, self._out)
        for g in rest:
            if g.codec is Codec.HOST_FALLBACK or g.codec in _STRING_CODECS:
                continue
            if not dec._run_group_native(g, src, self._out):
                slab = src[:, g.offsets[:, None]
                           + np.arange(g.width)[None, :]]
                dec._run_group_numpy(g, slab, self._out)

    def _materialize_strings(self, g: "_KernelGroup") -> None:
        """Resolve a lazily-deferred string kernel group into the code-point
        ("bytes") matrices the row/value paths consume. Reads never pay this
        when the Arrow path already emitted the column natively."""
        fc = self.field_costs
        tok = fc.begin() if fc is not None else None
        self._materialize_strings_impl(g)
        if tok is not None:
            # lazy strings decode at output materialization, so their
            # cost lands on the assemble plane (keeping the decode plane
            # comparable to the decode-stage busy time)
            fc.commit(tok, g.names, fieldcost.PLANE_ASSEMBLE,
                      self.n_records * g.width, self.n_records, g.label)

    def _materialize_strings_impl(self, g: "_KernelGroup") -> None:
        dec = self.decoder
        if g.codec is Codec.EBCDIC_STRING:
            if self.raw_source is not None:
                buf, offs, lens = self.raw_source
                chars = native.transcode_string_cols_raw(
                    buf, offs, lens, g.offsets, g.width, dec.lut)
            else:
                chars = native.transcode_string_cols(
                    self.data, g.offsets, g.width, dec.lut)
            if chars is None:  # no native library: numpy gather + LUT
                slab = self._gather_slab(g)
                chars = batch_np.transcode_ebcdic(slab, dec.lut)
        else:  # ASCII
            chars = batch_np.mask_ascii(self._gather_slab(g))
        # `char_plane`: the group's code points as one [n, columns x
        # width] matrix and where the column starts in it
        flat = chars.reshape(len(chars), len(g.columns) * g.width)
        for pos, c in enumerate(g.columns):
            self._out[c.index] = {"bytes": chars[:, pos],
                                  "char_plane": (flat, pos * g.width)}

    def _gather_slab(self, g: "_KernelGroup") -> np.ndarray:
        """[n, ncols, width] byte slab for a group, from the packed batch or
        the raw file image."""
        if self.raw_source is not None:
            buf, offs, lens = self.raw_source
            extent = int(g.offsets.max()) + g.width
            if self.data.shape[1] >= extent:
                src = self.data
            else:
                src = native.pack_records(buf, offs, lens, extent)
            return src[:, g.offsets[:, None] + np.arange(g.width)[None, :]]
        return self.data[:, g.offsets[:, None] + np.arange(g.width)[None, :]]

    def string_arrow_buffers(self, spec: ColumnSpec, relevant_of=None):
        """(int32 offsets [n+1], trimmed UTF-8 bytes) Arrow buffers for a
        lazily-deferred string column via the native one-pass transcode+trim
        kernel. None when the column is not in the lazy state (already
        materialized, jax backend, host fallback) or the library/charset
        can't express it — callers fall back to the code-point path.
        `relevant_of(spec)`: optional per-column row-visibility masks
        (decode-once batches skip rows hidden by a null parent struct)."""
        out = self._out.get(spec.index)
        if out is None or not native.available():
            return None
        if "char_plane" in out and spec.codec is Codec.EBCDIC_STRING:
            return self._point_string_buffers(spec, out["char_plane"][0],
                                              relevant_of)
        if "lazy_string" not in out:
            return None
        g, pos = out["lazy_string"]
        cached = self._arrow_str_cache.get(id(g))
        if cached is not None and not _masks_equal(
                cached[0], self._group_masks(g, relevant_of)):
            # same batch rendered with a different mask set (e.g. two
            # segment_table calls over different redefine masks): the
            # cached buffers were trimmed for the other masks — rebuild
            cached = None
        if cached is None:
            self._build_arrow_strings(g.codec, relevant_of)
            cached = self._arrow_str_cache.get(id(g))
            if cached is None:
                return None
        return cached[1][pos]

    def _build_arrow_strings(self, codec: Codec, relevant_of=None) -> None:
        """Every lazily-deferred group of one string codec through ONE
        native transcode+trim pass — mixed-width columns share the walk
        over the record bytes."""
        dec = self.decoder
        seen: Dict[int, "_KernelGroup"] = {}
        for col_out in self._out.values():
            lz = col_out.get("lazy_string")
            if lz is not None and lz[0].codec is codec:
                if id(lz[0]) not in seen:
                    seen[id(lz[0])] = lz[0]
        if not seen:
            return
        gs = list(seen.values())
        fc = self.field_costs
        tok = fc.begin() if fc is not None else None
        col_offs = np.concatenate([g.offsets for g in gs])
        widths = np.concatenate(
            [np.full(len(g.offsets), g.width, dtype=np.int64) for g in gs])
        masks = None
        if relevant_of is not None:
            masks = [relevant_of(c) for g in gs for c in g.columns]
            if all(m is None for m in masks):
                masks = None
        # cache keys derived through the same helper the lookup path uses
        group_masks = {id(g): self._group_masks(g, relevant_of) for g in gs}
        trim_mode = _NATIVE_TRIM_MODES.get(dec.plan.trimming)
        res = None
        if trim_mode is not None:
            lut = (dec.lut if codec is Codec.EBCDIC_STRING
                   else _ascii_mask_lut())
            if self.raw_source is not None:
                buf, offs, lens = self.raw_source
                res = native.string_cols_arrow_raw(
                    buf, offs, lens, col_offs, widths, lut, trim_mode,
                    col_masks=masks)
            else:
                res = native.string_cols_arrow_packed(
                    self.data, col_offs, widths, lut, trim_mode,
                    col_masks=masks)
        if res is None:
            res = [None] * len(col_offs)
        elif self.pass_counts is not None:
            self.pass_counts.incr("string_transcode")
        i = 0
        for g in gs:
            self._arrow_str_cache[id(g)] = (group_masks[id(g)],
                                            res[i:i + len(g.offsets)])
            i += len(g.offsets)
        if tok is not None:
            # the one-pass transcode+trim covered every lazy group of
            # this codec: split by bytes touched, like the merged
            # numeric pass (assemble plane — see _materialize_strings)
            fc.commit_weighted(
                tok,
                [(g.names, g.width, self.n_records * g.width, g.label)
                 for g in gs],
                fieldcost.PLANE_ASSEMBLE, self.n_records)

    def _point_string_buffers(self, spec: ColumnSpec, matrix: np.ndarray,
                              relevant_of=None):
        """string_arrow_buffers for a column whose code points lie in
        `matrix` (its `char_plane`), as a device program hands them
        back (collect_outputs): the same native pass over the fetched
        matrix, with the columns' places in
        it for offsets and a table that maps a code point to itself,
        since the chip has done the lookup: per row, per column, trim
        and UTF-8 (a code point past 0x7F as two bytes) straight into
        Arrow buffers, the rows a redefine mask hides as empty strings
        without being read. One pass builds every scalar column of the
        matrix, a second the slots of OCCURS arrays, if one is ever
        asked for. None where the kernel cannot serve: 16-bit code
        points (cp875 on a device, any page once the host kernels have
        looked it up), a trim it does not know, a column whose UTF-8
        outgrew the buffer."""
        trim_mode = _NATIVE_TRIM_MODES.get(self.decoder.plan.trimming)
        if matrix.dtype != np.uint8 or trim_mode is None:
            return None
        columns = self.decoder.plan.columns
        slots = bool(spec.slot_path)
        key = (id(matrix), slots)
        entry = self._arrow_str_cache.get(key)  # (columns, masks, buffers)
        cols = entry[0] if entry is not None else [
            col for col, out in self._out.items()
            if out.get("char_plane", (None,))[0] is matrix
            and bool(columns[col].slot_path) == slots]
        masks = None
        if relevant_of is not None:
            masks = [relevant_of(columns[c]) for c in cols]
            if all(m is None for m in masks):
                masks = None
        if entry is None or not _masks_equal(entry[1], masks):
            # (buffers trimmed for another set of masks are no use)
            fc = self.field_costs
            tok = fc.begin() if fc is not None else None
            res = native.string_cols_arrow_packed(
                matrix,
                np.asarray([self._out[c]["char_plane"][1] for c in cols],
                           dtype=np.int64),
                np.asarray([columns[c].width for c in cols],
                           dtype=np.int64),
                _identity_lut(), trim_mode, col_masks=masks)
            if res is None:
                return None
            if self.pass_counts is not None:
                self.pass_counts.incr("point_strings")
            if tok is not None:
                plan = self.decoder.plan
                fc.commit_weighted(
                    tok,
                    [((plan.cost_name(columns[c]),), columns[c].width,
                      self.n_records * columns[c].width,
                      f"{Codec.EBCDIC_STRING.value}/w{columns[c].width}")
                     for c in cols],
                    fieldcost.PLANE_ASSEMBLE, self.n_records)
            entry = self._arrow_str_cache[key] = (
                cols, masks, dict(zip(cols, res)))
        return entry[2].get(spec.index)

    @staticmethod
    def _group_masks(g: "_KernelGroup", relevant_of):
        """Per-column row-visibility masks for one kernel group (the cache
        key companion for `_arrow_str_cache` — buffers built under one
        mask set must not serve a render with another)."""
        if relevant_of is None:
            return None
        masks = [relevant_of(c) for c in g.columns]
        return None if all(m is None for m in masks) else masks

    # -- scalar access (row materialization / parity) ----------------------

    def value(self, col: int, i: int):
        """Python value for column `col`, record `i` — same semantics as the
        scalar oracle (None for nulls)."""
        spec = self.decoder.plan.columns[col]
        out = self.column_arrays(col)
        if self.lengths is not None:
            length = int(self.lengths[i])
            if spec.codec in _STRING_CODECS:
                if spec.offset > length:
                    return None
                if spec.offset + spec.width > length:
                    # truncated varchar tail: decode the available bytes
                    # (from the raw file image when the packed matrix does
                    # not cover them — see decode_raw's lazy strings)
                    if self.raw_source is not None \
                            and self.data.shape[1] < length:
                        buf, offs, _lens = self.raw_source
                        o = int(offs[i])
                        chunk = buf[o + spec.offset:o + length].tobytes()
                    else:
                        chunk = self.data[i, spec.offset:length].tobytes()
                    return self.decoder.options.decode(spec.dtype, chunk)
            elif spec.offset + spec.width > length:
                return None
        if "host" in out:
            return out["host"][i]
        if spec.codec in _STRING_CODECS:
            return self._string_value(spec, out, i)
        if spec.codec in _FLOAT_CODECS:
            if not out["valid"][i]:
                return None
            return float(out["values"][i])
        # fixed-point
        if not out["valid"][i]:
            return None
        if "values_hi" in out:
            # wide (uint128-limb) mantissa; the oracle returns Decimal for
            # these even when integral (the reference's BigDecimal plane)
            mantissa = (int(out["values_hi"][i]) << 64) | int(out["values"][i])
            if out["negative"][i]:
                mantissa = -mantissa
            if spec.params.explicit_decimal or _dyn_scale(spec):
                return _exact_scaleb(mantissa, -int(out["dot_scale"][i]))
            return _exact_scaleb(mantissa, fixed_point_exponent(spec))
        mantissa = int(out["values"][i])
        dt = spec.dtype
        if isinstance(dt, Integral):
            return mantissa
        # Decimal; explicit '.' and PIC P exponents are per-value planes
        if spec.params.explicit_decimal or _dyn_scale(spec):
            scale = int(out["dot_scale"][i])
            return PyDecimal(mantissa).scaleb(-scale)
        return PyDecimal(mantissa).scaleb(fixed_point_exponent(spec))

    def _vectorizable_string(self, spec: ColumnSpec) -> bool:
        """EBCDIC columns always decode via the LUT code-point matrix;
        ASCII only when the charset is plain US-ASCII (a custom charset
        decodes per value through the scalar oracle)."""
        return spec.codec is Codec.EBCDIC_STRING or (
            spec.codec is Codec.ASCII_STRING
            and not self.decoder.non_standard_ascii_charset)

    def _string_value(self, spec: ColumnSpec, out: dict, i: int):
        if self._vectorizable_string(spec):
            # whole-column decode on first access: one C-level bytes->str
            # conversion + per-row slicing beats a per-value chr() join by
            # ~50x at narrow-record row counts
            cache = self._str_cache.get(spec.index)
            if cache is None:
                cache = self._decode_string_column(spec, out)
                self._str_cache[spec.index] = cache
            return cache[i]
        raw = out["bytes"][i]
        if spec.codec is Codec.RAW_BYTES:
            return bytes(raw.view(np.uint8))
        if spec.codec is Codec.HEX_STRING:
            return bytes(raw.view(np.uint8)).hex().upper()
        trimming = self.decoder.plan.trimming
        if spec.codec is Codec.ASCII_STRING:
            return self.decoder.options.decode(spec.dtype,
                                               bytes(raw.view(np.uint8)))
        # UTF16
        enc = ("utf-16-be" if self.decoder.plan.is_utf16_big_endian
               else "utf-16-le")
        s = bytes(raw.view(np.uint8)).decode(enc, errors="replace")
        from ..ops.scalar_decoders import _trim
        return _trim(s, trimming)

    def _decode_string_column(self, spec: ColumnSpec, out: dict,
                              relevant=None) -> List[str]:
        from ..ops.scalar_decoders import _trim

        arr = out["bytes"]
        n = arr.shape[0]
        w = arr.shape[1] if arr.ndim == 2 else 0
        trimming = self.decoder.plan.trimming
        if w == 0:
            return [""] * n
        if arr.dtype == np.uint16:  # EBCDIC LUT code points
            blob = np.ascontiguousarray(arr).tobytes()
            text = blob.decode("utf-16-le", errors="replace")
        else:  # masked ASCII bytes (always < 0x80)
            text = np.ascontiguousarray(arr).tobytes().decode("latin-1")
        if relevant is not None:
            # build only the rows the caller can see (hierarchical walks
            # read a redefine's columns solely on its own segment's rows)
            lst: List[Optional[str]] = [None] * n
            for i in np.nonzero(relevant)[0]:
                i = int(i)
                lst[i] = _trim(text[i * w:(i + 1) * w], trimming)
            return lst
        return [_trim(text[i * w:(i + 1) * w], trimming) for i in range(n)]

    def column_values(self, col: int, relevant=None) -> list:
        """Whole column as a Python value list (the vectorized form of
        `value` — same null/decimal semantics, one pass per column instead
        of one dynamic dispatch per cell). `relevant`: optional row mask —
        rows outside it skip the truncation fixups and may materialize as
        None (sparse masks take a per-row path; dense ones keep the
        vectorized conversion, so hidden rows may carry kernel values —
        hierarchical/decode-once callers never read them either way);
        masked results are not cached."""
        if relevant is None:
            lst = self._col_cache.get(col)
            if lst is not None:
                return lst
        spec = self.decoder.plan.columns[col]
        out = self.column_arrays(col)
        n = self.n_records
        if relevant is not None and "host" not in out \
                and not self._vectorizable_string(spec):
            k = int(np.count_nonzero(relevant))
            if k * 4 < n:
                # sparse segment: per-row scalar decode of just its rows
                # beats whole-column Python materialization
                lst = [None] * n
                for i in np.nonzero(relevant)[0]:
                    lst[int(i)] = self.value(col, int(i))
                return lst
        if "host" in out:
            lst = list(out["host"])
        elif self._vectorizable_string(spec):
            if relevant is not None:
                lst = self._decode_string_column(spec, out, relevant)
            else:
                cached = self._str_cache.get(spec.index)
                if cached is None:
                    cached = self._decode_string_column(spec, out)
                    self._str_cache[spec.index] = cached
                # copy only when the truncation fixup below may mutate it
                lst = list(cached) if self.lengths is not None else cached
        elif spec.codec in _STRING_CODECS:
            lst = [self._string_value(spec, out, i) for i in range(n)]
        elif spec.codec in _FLOAT_CODECS:
            vals = [float(v) for v in out["values"].tolist()]
            valid = out["valid"]
            if not valid.all():
                vb = valid.tolist()
                lst = [v if ok else None for v, ok in zip(vals, vb)]
            else:
                lst = vals
        elif "values_hi" in out:
            valid = out["valid"]
            all_ok = bool(valid.all())
            vb = None if all_ok else valid.tolist()
            his = out["values_hi"].tolist()
            los = out["values"].tolist()
            negs = out["negative"].tolist()
            if spec.params.explicit_decimal or _dyn_scale(spec):
                exps = out["dot_scale"].tolist()
                es = [-e for e in exps]
            else:
                es = [fixed_point_exponent(spec)] * n
            mk = (lambda h, l, ng, e:
                  _exact_scaleb(-((h << 64) | l) if ng else (h << 64) | l, e))
            if all_ok:
                lst = [mk(h, l, ng, e)
                       for h, l, ng, e in zip(his, los, negs, es)]
            else:
                lst = [mk(h, l, ng, e) if ok else None
                       for h, l, ng, e, ok in zip(his, los, negs, es, vb)]
        else:
            valid = out["valid"]
            mant = out["values"].tolist()
            dt = spec.dtype
            all_ok = bool(valid.all())
            vb = None if all_ok else valid.tolist()
            if isinstance(dt, Integral):
                lst = (mant if all_ok
                       else [v if ok else None for v, ok in zip(mant, vb)])
            elif spec.params.explicit_decimal or _dyn_scale(spec):
                dots = out["dot_scale"].tolist()
                if all_ok:
                    lst = [PyDecimal(v).scaleb(-d)
                           for v, d in zip(mant, dots)]
                else:
                    lst = [PyDecimal(v).scaleb(-d) if ok else None
                           for v, d, ok in zip(mant, dots, vb)]
            else:
                e = fixed_point_exponent(spec)
                if all_ok:
                    lst = [PyDecimal(v).scaleb(e) for v in mant]
                else:
                    lst = [PyDecimal(v).scaleb(e) if ok else None
                           for v, ok in zip(mant, vb)]
        if self.lengths is not None:
            # columns (partly) past a record's end: re-derive through the
            # scalar path, which owns the truncation rules (only for rows
            # the caller can see — OTHER segments' shorter records would
            # otherwise storm the per-value path)
            trunc = self.lengths < spec.offset + spec.width
            if relevant is not None:
                trunc = trunc & relevant
            for i in np.nonzero(trunc)[0]:
                lst[int(i)] = self.value(col, int(i))
        if relevant is None:
            self._col_cache[col] = lst
        return lst

    # -- row materialization ----------------------------------------------

    def to_rows(self,
                policy: SchemaRetentionPolicy = SchemaRetentionPolicy.KEEP_ORIGINAL,
                generate_record_id: bool = False,
                file_id: int = 0,
                first_record_id: int = 0,
                generate_input_file_field: bool = False,
                input_file_name: str = "",
                segment_level_ids: Optional[List[List[object]]] = None,
                active_segments: Optional[Sequence[Optional[str]]] = None,
                record_ids: Optional[Sequence[int]] = None,
                handler=None) -> List[List[object]]:
        """Assemble nested rows (same shape as reader.extractors.extract_record).
        `record_ids` overrides the sequential first_record_id+i numbering
        (used when a batch holds non-contiguous records, e.g. one segment
        of a multisegment file). `handler`: the RecordHandler seam — group
        records materialize through handler.create instead of tuples."""
        # whole-batch row materialization touches every column: resolve
        # all deferred numeric groups in one bulk (merged) pass up front
        self.materialize_numeric_all()
        # one compiled maker per DISTINCT active segment; mixed-active
        # batches (decode-once) dispatch per row
        if active_segments is None or not len(active_segments):
            makers = {None: self._row_maker(None, policy, handler)}
            actives = None
        else:
            distinct = (set(active_segments.uniq)
                        if hasattr(active_segments, "uniq")
                        else set(active_segments))
            makers = {a: self._row_maker(a or None, policy, handler)
                      for a in distinct}
            actives = active_segments if len(makers) > 1 else None
            if actives is None:
                makers = {None: makers[next(iter(distinct))]}

        rows = []
        the_maker = makers.get(None)
        for i in range(self.n_records):
            maker = (the_maker if actives is None
                     else makers[actives[i]])
            body = maker(i)
            seg = list(segment_level_ids[i]) if segment_level_ids else []
            rid = (record_ids[i] if record_ids is not None
                   else first_record_id + i)
            if generate_record_id and generate_input_file_field:
                row = [file_id, rid, input_file_name] + seg + body
            elif generate_record_id:
                row = [file_id, rid] + seg + body
            elif generate_input_file_field:
                row = seg + [input_file_name] + body
            else:
                row = seg + body
            rows.append(row)
        return rows

    # -- compiled row assembly ---------------------------------------------

    def _row_maker(self, active: Optional[str],
                   policy: SchemaRetentionPolicy, handler=None):
        """Compile the nested-row assembly into closures over the column
        value lists: leaf access becomes list indexing instead of per-cell
        dynamic dispatch (the difference between ~30us and ~3us per row on
        narrow records). One maker per (active segment, policy, handler)
        per batch; group records materialize through handler.create when a
        RecordHandler is supplied (tuples otherwise)."""
        key = (active, policy, id(handler) if handler is not None else None)
        maker = self._maker_cache.get(key)
        if maker is not None:
            return maker

        def occurs_counts(st: Statement):
            """Per-record element counts for an array statement (None when
            the count is the constant max size)."""
            dep_col = (self.decoder.dependee_columns.get(st.depending_on)
                       if st.depending_on is not None else None)
            if dep_col is None:
                return None
            return [_resolve_occurs(st, v)
                    for v in self.column_values(dep_col)]

        def build_group(group: Group, slot_path: Tuple[int, ...]):
            makers = []
            for st in group.children:
                if st.is_array:
                    counts = occurs_counts(st)
                    if isinstance(st, Group):
                        elems = [build_group(st, slot_path + (k,))
                                 for k in range(st.array_max_size)]
                    else:
                        elems = [self._leaf_maker(st, slot_path + (k,))
                                 for k in range(st.array_max_size)]
                    if counts is None:
                        m = (lambda i, e=elems:
                             [mk(i) for mk in e])
                    else:
                        m = (lambda i, e=elems, c=counts:
                             [e[k](i) for k in range(c[i])])
                elif isinstance(st, Group):
                    if st.is_segment_redefine and (
                            active is None
                            or st.name.upper() != active.upper()):
                        m = lambda i: None
                    else:
                        m = build_group(st, slot_path)
                else:
                    m = self._leaf_maker(st, slot_path)
                if not st.is_filler:
                    makers.append(m)
            if handler is not None:
                return (lambda i, ms=tuple(makers), g=group:
                        handler.create([mk(i) for mk in ms], g))
            return lambda i, ms=tuple(makers): tuple([mk(i) for mk in ms])

        root_makers = [build_group(root, ())
                       for root in self.decoder.copybook.ast.children
                       if isinstance(root, Group)]
        if policy is SchemaRetentionPolicy.COLLAPSE_ROOT:
            def maker(i):
                body: List[object] = []
                for rm in root_makers:
                    rec = rm(i)
                    body.extend(handler.to_seq(rec) if handler is not None
                                else rec)
                return body
        else:
            def maker(i):
                return [rm(i) for rm in root_makers]
        self._maker_cache[key] = maker
        return maker

    def _leaf_maker(self, st: Primitive, slot_path: Tuple[int, ...]):
        col = self.decoder.slot_map.get((id(st), slot_path))
        if col is None:
            return lambda i: None
        values = self.column_values(col)
        return values.__getitem__

_decoder_build_lock = threading.Lock()

# every decode backend there is: the native/numpy host kernels, the scalar
# oracle (row-wise; the readers walk it, the columnar decoder never runs
# under that name), the plain XLA program, the fused Pallas program
BACKENDS = ("numpy", "host", "jax", "pallas")
DEVICE_BACKENDS = ("jax", "pallas")

# one device launch decodes at most this many input bytes; larger batches
# stream through as equal blocks, so device memory per scan thread is
# bounded whatever the shard size and a big read compiles one shape
DEVICE_BLOCK_BYTES = 128 * 1024 * 1024

# a launch pads its rows to a bucket (ColumnarDecoder._bucket_size), and
# the host's link work (the zeroed buffer, its linearization, the fetch of
# every output) grows with the padded rows. Past BUCKET_OCTAVE_ROWS the
# buckets are a quarter octave apart, where a power of two left up to half
# a launch zeros: exp1's 64 MiB chunk of 44,949 records goes as 49,152
# rows, not 65,536. Every bucket is a multiple of the rows-in-lanes
# kernel's grid step (ops/pallas_tpu.LANE_TILE), so neither kernel pads
LAUNCH_ROW_STEP = 4096
BUCKET_OCTAVE_ROWS = 4 * LAUNCH_ROW_STEP

# a program that lays variable-size OCCURS records to the static layout
# (ops/expand.py) holds a launch's rows several times over on the chip:
# as they came, with the bytes behind each array shifted, expanded, and
# as the planes the kernel reads. XLA keeps such intermediates in the
# chip's fast memory beside the kernel's own 96 MiB, and a launch of
# 65,536 rows of 1,153 B (75 MB a copy) never came back on a TPU v5e
# where one of 16,384 took 3.2 ms and the two halves alone, at 65,536,
# 2.3 and 10.6 ms (PERF.md section 6, PR 32): its launches read this
# share of DEVICE_BLOCK_BYTES
EXPAND_BLOCK_SHARE = 4

# a device decode_raw with segment row masks launches by redefine where
# that spares the link at least this many bytes a row, averaged over the
# batch: (the plan's extent and fetched bytes, less the set's own) times
# each set's share of the rows, all known before any launch. Against it
# stand a fancy index of offsets and lengths a set, a launch more a batch
# and the scatter of whatever is later asked for by position. The twin of
# the host kernels' `hidden * g.width < 4.0` (_group_segment_mask). Set
# between the two readings that bracket the break-even on the chip's host
# (PERF.md section 6, PR 29): exp2's copybook spares 101 B a row and reads
# 3 % slower partitioned (its strings are all asked for by position); the
# same records with an OCCURS 32 of 8 B under 'C' spare 485 B and read
# 12 % faster; exp3 spares 24 KB
PARTITION_MIN_SAVED_BYTES = 256

# a program's matrix of code points (StringPoints) leaves the chip as
# rows of this many: the same row-major bytes, the 128 lanes full. A TPU
# hands a [rows, 64] uint8 matrix back with the rows minor, and the host
# then transposes it (0.59 s for the 134 MB of a 2,097,152-row launch
# beside the 0.19 s its transfer takes); whole rows of 128 arrive as
# they lie, in the same 0.19 s, and so does a flat array, whose first
# compile takes 27 s where this takes 6 (PERF.md section 6, PR 33).
# Every launch bucket (a power of two from 256, or a multiple of
# LAUNCH_ROW_STEP) divides into such rows
POINTS_LANES = 128


def validate_backend(backend: str) -> str:
    """`backend` if it names a decode backend, else ValueError. A name
    that is not checked would decode on the host kernels while the read
    reports the name it was given."""
    if backend not in BACKENDS:
        raise ValueError(
            f"Unknown backend {backend!r}; the decode backends are "
            + ", ".join(repr(b) for b in BACKENDS))
    return backend


def decoder_for_segment(cache: Dict[str, "ColumnarDecoder"],
                        copybook: Copybook, active: str,
                        backend: str,
                        select: Optional[Sequence[str]] = None,
                        variable_size_occurs: bool = False,
                        rows_of: Optional[Tuple[str, str]] = None
                        ) -> "ColumnarDecoder":
    """Shared per-(active segment, backend) decoder cache used by both the
    fixed-length and variable-length readers. Locked: the indexed parallel
    scan hits a shared reader's cache from worker threads, and plan
    compilation (or a jax jit) must not be duplicated per worker."""
    from ..plan.cache import note_decoder

    key = f"{active}|{backend}|{','.join(select) if select else ''}"
    if variable_size_occurs:
        key += "|odo"
    if rows_of is not None:
        key += "|" + ":".join(rows_of)
    dec = cache.get(key)
    if dec is None:
        with _decoder_build_lock:
            dec = cache.get(key)
            if dec is None:
                note_decoder(hit=False)
                dec = ColumnarDecoder(
                    copybook, active_segment=active or None, backend=backend,
                    select=select,
                    variable_size_occurs=variable_size_occurs,
                    rows_of=rows_of)
                cache[key] = dec
                return dec
    note_decoder(hit=True)
    return dec


class ColumnarDecoder:
    def __init__(self, copybook: Copybook,
                 active_segment: Optional[str] = None,
                 backend: str = "numpy",
                 select: Optional[Sequence[str]] = None,
                 variable_size_occurs: bool = False,
                 rows_of: Optional[Tuple[str, str]] = None):
        """`variable_size_occurs`: the rows this decoder is given hold
        each DEPENDING ON array at its count's size (`plan.regions`); it
        lays them to the static layout before it decodes them. `rows_of`:
        the rows are one of the two kinds an array of variable arrays
        cuts its records into (compiler.compile_plan)."""
        self.copybook = copybook
        self.select = tuple(select) if select else None
        self.plan: FieldPlan = cached_compile_plan(
            copybook, active_segment, select=self.select,
            variable_size_occurs=variable_size_occurs, rows_of=rows_of)
        self.backend = validate_backend(backend)
        self.options = DecodeOptions.from_copybook(copybook)
        self.non_standard_ascii_charset = (
            copybook.ascii_charset.lower().replace("_", "-")
            not in ("us-ascii", "ascii"))
        self.lut = cached_code_page_lut(copybook.ebcdic_code_page)
        # a code point on the link: a byte where the table's largest
        # fits one (every shipped page but cp875)
        self.points_dtype = (np.uint8 if int(self.lut.max()) <= 0xFF
                             else np.uint16)
        self._jax_fn = None
        self.rebuild_groups()

    def rebuild_groups(self) -> None:
        """(Re)build kernel groups and lookup maps from the plan columns —
        called at construction and after an offset remap (device byte
        projection rewrites column offsets into a packed layout)."""
        # a group is one redefine's or nobody's, never a mix: a row mask
        # of a segment then covers whole groups (the host kernels' subset
        # decode, the device launches partitioned by redefine)
        groups: Dict[tuple, List[ColumnSpec]] = {}
        for c in self.plan.columns:
            key = (c.codec, c.width, _variant_key(c), _column_owner(c))
            groups.setdefault(key, []).append(c)
        self.kernel_groups = [
            _KernelGroup(codec, width, variant, cols,
                         tuple(self.plan.cost_name(c) for c in cols),
                         segment=owner)
            for (codec, width, variant, owner), cols in groups.items()]
        # column index -> its kernel group (group-batched Arrow builds)
        self.group_of_col: Dict[int, _KernelGroup] = {
            c.index: g for g in self.kernel_groups for c in g.columns}
        # statements with at least one compiled column: a projected plan
        # (select/filter pushdown) leaves pruned statements out, and the
        # Arrow builder emits whole pruned subtrees as cheap null bodies
        # instead of walking thousands of absent OCCURS slots
        self.planned_statement_ids = frozenset(
            id(c.statement) for c in self.plan.columns
            if c.statement is not None)
        # marshaled merged-numeric descriptors, keyed by the group subset
        # (decode() always passes the full list; decode_raw passes masked
        # subsets) — rebuilt per decode call they cost ~5ms on a
        # 59-group profile, pure GIL-held overhead per pipeline chunk
        self._numeric_descs: Dict[tuple, tuple] = {}
        # lookup maps for row assembly
        self.slot_map: Dict[Tuple[int, Tuple[int, ...]], int] = {}
        for c in self.plan.columns:
            self.slot_map.setdefault((id(c.statement), c.slot_path), c.index)
        self.dependee_columns: Dict[str, int] = {}
        for c in self.plan.columns:
            if c.statement is not None and c.statement.is_dependee:
                self.dependee_columns.setdefault(c.statement.name, c.index)
        self._jax_fn = None
        # programs of group subsets (_program_for), by the groups' ids
        self._set_programs: Dict[tuple, object] = {}
        # where each program's EBCDIC strings lie in its matrix of code
        # points (_points_of), by the groups' ids
        self._points: Dict[tuple, Optional[StringPoints]] = {}
        # the plan's variable regions (compiler.VariableRegion): rows
        # given to this decoder are laid to the static layout first
        self.regions = self.plan.regions

    def _points_of(self, groups) -> Optional[StringPoints]:
        """The StringPoints of the program over `groups`, worked out
        once (a batch with row masks asks for every set's)."""
        key = tuple(id(g) for g in groups)
        if key not in self._points:
            self._points[key] = _string_points(groups, self.points_dtype)
        return self._points[key]

    def expand_host(self, arr: np.ndarray):
        """(`arr` with every variable region at its maximum size, the
        counts [n, regions]) by the host kernels: `backend="numpy"`'s
        pass, and what a device batch's host copy takes when it is read
        (DecodedBatch.data)."""
        with Stage("expand"):
            return odo.expand_rows(np, batch_np, arr, self.regions)

    # ------------------------------------------------------------------

    def decode(self, data, lengths: Optional[np.ndarray] = None) -> DecodedBatch:
        """data: bytes (length N*record_size) or uint8 array [N, record_size].
        `lengths`: optional per-record actual byte counts for padded
        variable-length batches."""
        rs = self.plan.record_size
        if isinstance(data, (bytes, bytearray, memoryview)):
            arr = np.frombuffer(data, dtype=np.uint8)
            if len(arr) % rs != 0:
                raise ValueError(
                    f"Data size {len(arr)} is not divisible by the record size {rs}")
            arr = arr.reshape(-1, rs)
        else:
            arr = np.asarray(data, dtype=np.uint8)
            if arr.ndim != 2:
                raise ValueError("Expected a [batch, record_len] uint8 array")
            extent = self.plan.max_extent
            if arr.shape[1] < extent:
                # pad to the plan's byte extent; columns past a record's
                # true end are nulled via `lengths`
                with Stage("pack"):
                    padded = np.zeros((arr.shape[0], extent),
                                      dtype=np.uint8)
                    padded[:, :arr.shape[1]] = arr
                arr = padded
        counts = None
        compact = False
        if self.backend in DEVICE_BACKENDS and self.regions:
            outputs, counts = self._decode_launches(arr)
            compact = True
        elif self.backend in DEVICE_BACKENDS:
            outputs = self._decode_jax(arr)
        else:
            if self.regions:
                arr, counts = self.expand_host(arr)
            outputs = self._decode_numpy(arr)
        if counts is not None:
            lengths = self._note_expansion(arr, counts, lengths)
        batch = DecodedBatch(self, arr, outputs, lengths=lengths,
                             compact=compact)
        if any(g.codec is Codec.HOST_FALLBACK for g in self.kernel_groups):
            self._decode_host_fallback(batch.data, outputs)
        return batch

    def _note_expansion(self, arr: np.ndarray, counts: np.ndarray,
                        lengths: Optional[np.ndarray]):
        """The rows' lengths in the expanded layout (None stays None:
        every row reaches its end), and the read's odo counters."""
        n, extent = arr.shape
        with Stage("expand"):
            full = (np.full(n, extent, dtype=np.int64) if lengths is None
                    else lengths)
            moved, shifted = odo.expanded_lengths(
                np, full, counts, self.regions, extent)
        ctx = obs_context.current()
        if ctx is not None and ctx.device_stats is not None:
            ctx.device_stats.note_odo(regions=len(self.regions), records=n,
                                      shifted_bytes=int(shifted.sum()))
        return None if lengths is None else moved

    def decode_raw(self, data, rec_offsets, rec_lengths,
                   start_offset: int = 0,
                   segment_row_masks: Optional[Dict[str, np.ndarray]] = None,
                   lazy_masked: bool = False) -> DecodedBatch:
        """Decode framed records in place from the file image.

        On the numpy backend numeric groups read straight through the
        native raw kernels (no [batch, extent] pack copy — for wide
        records the pack costs as much as the decode), and only the
        narrow prefix covering the remaining groups is packed. A device
        backend packs every record to the plan's extent and decodes the
        matrix (`decode`), unless the masks let it launch by redefine
        (below); so does the numpy backend without the native library,
        and every backend for a plan with variable regions: the rows
        are packed compact, each record from its first byte, and
        `decode` lays them to the static layout (on a device backend
        the program does, so that they cross the link compact).

        `segment_row_masks`: segment-redefine name -> row-visibility mask
        (disjoint: a row has at most one active redefine). On the numpy
        backend a kernel group of one masked redefine decodes ONLY that
        segment's rows (subset kernel + scatter); hidden rows come back
        invalid instead of as decoded garbage. On interleaved
        multisegment profiles (hierarchical) this skips the majority of
        the numeric decode work. On a device backend the rows are
        partitioned by their active redefine (`_segment_sets`): each set
        is packed at its own extent and launched through its own
        program (the common groups and that redefine's), so only a
        segment's own rows cross the link, at the segment's own width,
        where the plan's widths make that worth a launch more a batch
        (PARTITION_MIN_SAVED_BYTES); a redefine's outputs stay subset
        planes, [rows of the set, ncols], until a consumer asks for
        them by position (DecodedBatch.column_arrays scatters once a
        group; the Arrow list builder takes the subset whole through
        DecodedBatch.plane_of).

        `lazy_masked`: defer even the masked numeric groups (numpy
        backend). The fused native Arrow assembly applies the same row
        masks in-kernel (hidden rows emit null without being decoded),
        so Arrow consumers skip both the subset gather and the Python
        scatter — the decode-once multisegment path uses this instead of
        splitting size-skewed profiles into per-segment decodes."""
        rec_lengths = np.asarray(rec_lengths, dtype=np.int64)
        extent_full = self.plan.max_extent
        lengths = np.minimum(rec_lengths - start_offset, extent_full)

        def packed_fallback():
            with Stage("pack"):
                batch = native.pack_records(data, rec_offsets, rec_lengths,
                                            extent_full,
                                            start_offset=start_offset)
            return self.decode(batch, lengths=lengths)

        if self.regions:
            # compact rows to the extent, laid out by `decode`
            return packed_fallback()
        if self.backend in DEVICE_BACKENDS and segment_row_masks:
            partitioned = self._decode_raw_partitioned(
                data, rec_offsets, rec_lengths, start_offset,
                segment_row_masks, lengths)
            if partitioned is not None:
                return partitioned
        if self.backend != "numpy" or not native.available():
            return packed_fallback()

        # convert/adjust once — the per-group kernels receive ready arrays
        # (their own ascontiguousarray checks become no-ops)
        buf = (np.ascontiguousarray(data, dtype=np.uint8)
               if isinstance(data, np.ndarray)
               else np.frombuffer(data, dtype=np.uint8))
        offs = np.ascontiguousarray(rec_offsets, dtype=np.int64)
        if start_offset:
            offs = offs + start_offset
            rec_lengths = rec_lengths - start_offset
        if segment_row_masks:
            segment_row_masks = {k.upper(): v
                                 for k, v in segment_row_masks.items()}

        n = len(offs)
        fc = fieldcost.current()
        outputs: Dict[int, dict] = {}
        narrow_groups = []
        narrow_extent = 1
        # masked narrow groups, batched per distinct row mask
        masked_narrow: Dict[int, Tuple[np.ndarray, list]] = {}
        def subset(gmask):
            return ((offs, rec_lengths) if gmask is None
                    else (offs[gmask], rec_lengths[gmask]))

        for g in self.kernel_groups:
            res = None
            tok = None
            g_rows = n
            gmask = (None if g.codec in _STRING_CODECS
                     else self._group_segment_mask(g, segment_row_masks))
            if (gmask is None or lazy_masked) and self._lazy_numeric_ok(g):
                # deferred like the string groups: the Arrow path emits
                # these columns straight from the raw image through the
                # fused native assembly; rows materialize planes lazily
                for pos, c in enumerate(g.columns):
                    outputs[c.index] = {"lazy_numeric": (g, pos)}
                continue
            if g.codec is Codec.BINARY and not g.wide:
                signed, big_endian, fits32, _ = g.variant
                goffs, glens = subset(gmask)
                g_rows = len(goffs)
                tok = fc.begin() if fc is not None else None
                res = native.decode_binary_cols_raw(
                    buf, goffs, glens, g.offsets, g.width,
                    signed, big_endian, fits32=fits32)
            elif g.codec is Codec.BCD and not g.wide:
                fits32, _ = g.variant
                goffs, glens = subset(gmask)
                g_rows = len(goffs)
                tok = fc.begin() if fc is not None else None
                res = native.decode_bcd_cols_raw(
                    buf, goffs, glens, g.offsets, g.width,
                    fits32=fits32)
            elif g.codec is Codec.EBCDIC_STRING or (
                    g.codec is Codec.ASCII_STRING
                    and not self.non_standard_ascii_charset):
                # deferred: the Arrow path emits these columns straight from
                # the raw image through the native transcode+trim kernel;
                # the row path materializes the code-point matrix on demand.
                # Truncated varchar tails re-decode from the raw image too
                # (DecodedBatch.value raw_source fallback), so records
                # short of this group never force a wider pack — on a
                # string-dominated profile the pack was the single largest
                # decode cost and served only rows that masked reads skip
                for pos, c in enumerate(g.columns):
                    outputs[c.index] = {"lazy_string": (g, pos)}
                continue
            if res is not None:
                if gmask is not None:
                    res = tuple(_scatter_rows(a, gmask, n) for a in res)
                self._store_numeric(g, outputs, *res)
                if tok is not None:
                    fc.commit(tok, g.names, fieldcost.PLANE_DECODE,
                              g_rows * g.width, g_rows, g.label)
                continue
            if tok is not None:
                # no native library: the group re-times on the packed
                # fallback path below, so this region charges nobody
                fc.discard(tok)
            if gmask is not None and g.codec is not Codec.HOST_FALLBACK:
                masked_narrow.setdefault(id(gmask), (gmask, []))[1].append(g)
                continue
            narrow_groups.append(g)
            if len(g.columns):
                narrow_extent = max(narrow_extent,
                                    int(g.offsets.max()) + g.width)

        for mask, gs in masked_narrow.values():
            ext = max(int(g.offsets.max()) + g.width
                      for g in gs if len(g.columns))
            sub = native.pack_records(buf, offs[mask], rec_lengths[mask],
                                      ext)
            sub_out: Dict[int, dict] = {}
            self._run_groups(gs, sub, sub_out)
            outputs.update(_scatter_outputs(sub_out, mask, n))

        batch = native.pack_records(buf, offs, rec_lengths, narrow_extent)
        self._run_groups(narrow_groups, batch, outputs)
        self._decode_host_fallback(batch, outputs)
        return DecodedBatch(self, batch, outputs, lengths=lengths,
                            raw_source=(buf, offs, rec_lengths))

    @staticmethod
    def _group_segment_mask(g: "_KernelGroup", segment_row_masks):
        """The row mask of the segment redefine that owns `g`
        (_column_owner: never a dependee's group); None keeps the full
        decode."""
        if not segment_row_masks or g.segment is None:
            return None
        m = segment_row_masks.get(g.segment)
        if m is None:
            return None
        # engage only when the skipped decode outweighs the subset
        # gather + full-length scatter (narrow planes are a wash: the
        # zeros/fancy-index cost per row rivals the decode saved)
        hidden = 1.0 - float(m.mean())
        if hidden * g.width < 4.0:
            return None
        return m

    def _segment_sets(self, segment_row_masks, n: int):
        """The rows of a batch of `n` split by their active redefine for
        the device: a _RowSet a masked redefine that owns kernel groups
        (its rows may be none), one more for the rows under no mask.
        None where the masks are no partition (two of them share a row),
        where no group is any masked redefine's, or where the plan's
        widths say the link would be spared too little
        (PARTITION_MIN_SAVED_BYTES). A redefine without a mask keeps
        its columns in every set: every row decodes them, as the host
        kernels do."""
        masks = {k.upper(): v for k, v in segment_row_masks.items()}
        masked = [name for name in masks
                  if any(g.segment == name for g in self.kernel_groups)]
        if not masked or not n:
            return None
        rest = np.ones(n, dtype=bool)
        sets, covered = [], 0
        for name in masked:
            rows = np.flatnonzero(masks[name])
            rest[rows] = False
            covered += len(rows)
            sets.append(_RowSet(name, masks[name], rows, [
                g for g in self.kernel_groups
                if g.segment == name or g.segment not in masked]))
        rest_rows = np.flatnonzero(rest)
        if covered + len(rest_rows) != n:
            return None
        if len(rest_rows):
            sets.append(_RowSet(None, rest, rest_rows, [
                g for g in self.kernel_groups if g.segment not in masked]))

        def link_bytes(extent, groups):
            points = self._points_of(groups)
            return (extent + sum(_fetched_bytes(g) for g in groups)
                    + (points.row_bytes if points is not None else 0))

        whole = link_bytes(self.plan.max_extent, self.kernel_groups)
        saved = sum(len(rs.rows) * (whole - link_bytes(rs.extent, rs.groups))
                    for rs in sets) / n
        return sets if saved >= PARTITION_MIN_SAVED_BYTES else None

    def _decode_raw_partitioned(self, data, rec_offsets, rec_lengths,
                                start_offset: int, segment_row_masks,
                                lengths) -> Optional[DecodedBatch]:
        """decode_raw on a device backend, the launches partitioned by
        segment redefine (`_segment_sets`); None where it declines. Each
        set's rows are packed once, straight into their launch's
        bucket-sized buffers at the set's own extent, and decoded by the
        set's own program. The common groups' outputs are put together
        by position; a redefine's stay subset planes (_SubsetPlanes)."""
        n = len(rec_offsets)
        ctx = obs_context.current()
        stats = ctx.device_stats if ctx is not None else None
        sets = self._segment_sets(segment_row_masks, n)
        if stats is not None and n:
            stats.note_partition(
                None if sets is None
                else {rs.name or "": len(rs.rows) for rs in sets})
        if sets is None:
            return None
        buf = (np.ascontiguousarray(data, dtype=np.uint8)
               if isinstance(data, np.ndarray)
               else np.frombuffer(data, dtype=np.uint8))
        offs = np.ascontiguousarray(rec_offsets, dtype=np.int64)
        lens = rec_lengths
        if start_offset:
            offs = offs + start_offset
            lens = lens - start_offset

        def packed_blocks(rs: _RowSet):
            """The set's rows as [block, extent] launch buffers, zero
            rows after the last real one."""
            with Stage("pack"):
                set_offs, set_lens = offs[rs.rows], lens[rs.rows]
            block = self._device_block(len(rs.rows), rs.extent)
            for start in range(0, len(rs.rows), block):
                with Stage("pack"):
                    o = set_offs[start:start + block]
                    ln = set_lens[start:start + block]
                    m = len(o)
                    if m != block:
                        # a record of no length packs as a row of zeros
                        o, ln = (np.concatenate(
                            [a, np.zeros(block - m, dtype=np.int64)])
                            for a in (o, ln))
                    rows = native.pack_records(buf, o, ln, rs.extent)
                yield rows, m

        fc = fieldcost.current()
        tok = fc.begin() if fc is not None else None
        launched = [rs for rs in sets if len(rs.rows)]
        programs = {rs.name: self._program_for(rs.groups) for rs in launched}
        with annotate("cobrix_decode"):
            parts = [self._launch_blocks(programs[rs.name],
                                         packed_blocks(rs), stats)
                     for rs in launched]
        # set name -> its fetched tuples: the groups' in the set's order,
        # behind them the matrix of its strings' code points, if any
        fetched = {rs.name: self._merge_blocks(p)
                   for rs, p in zip(launched, parts)}

        def tuples_of(rs: _RowSet, groups) -> list:
            """`groups`' tuples of the set's launch as collect_outputs
            reads them: the matrix's behind them."""
            outs = fetched[rs.name]
            at = {id(g): k for k, g in enumerate(rs.groups)}
            return [outs[at[id(g)]] for g in groups] + outs[len(rs.groups):]

        outputs: Dict[int, dict] = {}
        with Stage("collect"):
            for rs in sets:
                # what the host decodes row by row is no launch's
                own = [g for g in rs.own
                       if g.codec is not Codec.HOST_FALLBACK]
                sub = None
                if len(rs.rows):
                    sub = self.collect_outputs(
                        tuples_of(rs, own), len(rs.rows), own,
                        points=programs[rs.name].points)
                for g in own:
                    part = _SubsetPlanes(
                        rs.mask, g, None if sub is None else
                        {c.index: sub[c.index] for c in g.columns})
                    for c in g.columns:
                        outputs[c.index] = {"subset": part}
            # every set decoded the groups no masked redefine owns: their
            # rows go back to their places (one set with every row: as is)
            common = [g for g in sets[0].groups if g not in sets[0].own]
            if len(launched) == 1:
                (rs,) = launched
                outputs.update(self.collect_outputs(
                    tuples_of(rs, common), n, common,
                    points=programs[rs.name].points))
            else:
                by_set = [(rs.rows, tuples_of(rs, common))
                          for rs in launched]
                whole = []
                for k in range(len(common)):
                    arrays = []
                    for j, first in enumerate(by_set[0][1][k]):
                        full = np.empty((n,) + first.shape[1:], first.dtype)
                        for rows, outs in by_set:
                            full[rows] = outs[k][j][:len(rows)]
                        arrays.append(full)
                    whole.append(tuple(arrays))
                # the common strings' code points, each column from its
                # place in its set's matrix to its place in one matrix
                # of the common groups alone
                points = self._points_of(common)
                if points is not None:
                    full = np.empty((n, points.width), points.dtype)
                    for rs in launched:
                        matrix = fetched[rs.name][len(rs.groups)][0]
                        held = programs[rs.name].points.columns
                        for col, (at, width) in points.columns.items():
                            src = held[col][0]
                            full[rs.rows, at:at + width] = matrix[
                                :len(rs.rows), src:src + width]
                    whole.append((full,))
                outputs.update(self.collect_outputs(whole, n, common,
                                                    points=points))
        self._commit_device_cost(fc, tok, n)
        # the packed matrix covers what the host decodes row by row
        # (HOST_FALLBACK columns); everything else reads the file image
        batch = native.pack_records(buf, offs, lens, _groups_extent(
            [g for g in self.kernel_groups
             if g.codec is Codec.HOST_FALLBACK]))
        self._decode_host_fallback(batch, outputs)
        return DecodedBatch(self, batch, outputs, lengths=lengths,
                            raw_source=(buf, offs, lens))

    @staticmethod
    def _bucket_size(n: int) -> int:
        """The rows a launch of `n` is padded to, so the jitted decode is
        traced a bounded number of times: a power of two from 256 up to
        BUCKET_OCTAVE_ROWS, above it a quarter of the octave's power of
        two (LAUNCH_ROW_STEP at least), at most four shapes an octave and
        each at least 80 % full."""
        n = int(n)
        if n > BUCKET_OCTAVE_ROWS:
            step = max(LAUNCH_ROW_STEP, (1 << (n.bit_length() - 1)) // 4)
            return -(-n // step) * step
        b = 256
        while b < n:
            b *= 2
        return b

    # -- numpy backend ---------------------------------------------------

    def _decode_numpy(self, arr: np.ndarray) -> Dict[int, dict]:
        outputs: Dict[int, dict] = {}
        self._run_groups(self.kernel_groups, arr, outputs, defer=True)
        return outputs

    def _lazy_numeric_ok(self, g: "_KernelGroup") -> bool:
        """Numeric/float groups DEFER on the numpy backend when the
        native library can emit their Arrow buffers directly (the fused
        one-pass assembly in arrow_out): Arrow consumers then never pay
        for the intermediate [n, ncols] planes at all, and the row/value
        paths materialize them on demand."""
        return (self.backend == "numpy" and native.available()
                and len(g.columns) > 0
                and (g.codec in _NUMERIC_CODECS
                     or g.codec in _FLOAT_CODECS))

    def _run_groups(self, groups, arr: np.ndarray,
                    outputs: Dict[int, dict], defer: bool = False) -> None:
        """Per-group numpy-path dispatch (native single-pass kernel when
        available, else gather + vectorized numpy) over a packed batch.
        Narrow numeric groups first go through ONE merged native pass —
        each record's bytes are touched once for the whole numeric plane
        instead of once per kernel group (exp1's type-variety profile has
        59 such groups). `defer=True` (the decode entry points) parks
        fused-assembly-eligible numeric groups as lazy markers instead."""
        fc = fieldcost.current()
        if defer:
            rest = []
            for g in groups:
                if self._lazy_numeric_ok(g):
                    for pos, c in enumerate(g.columns):
                        outputs[c.index] = {"lazy_numeric": (g, pos)}
                else:
                    rest.append(g)
            groups = rest
        groups = self._run_groups_merged(groups, arr, outputs, fc)
        n = arr.shape[0]
        for g in groups:
            if g.codec is Codec.HOST_FALLBACK:
                continue
            if g.codec is Codec.EBCDIC_STRING or (
                    g.codec is Codec.ASCII_STRING
                    and not self.non_standard_ascii_charset):
                # deferred (see decode_raw): Arrow emits these natively,
                # rows materialize the code-point matrix on first touch
                for pos, c in enumerate(g.columns):
                    outputs[c.index] = {"lazy_string": (g, pos)}
                continue
            # attribution: one timed region per kernel-group launch
            # (native single-pass or gather + numpy), split across the
            # group's columns — call-granularity, never per record
            tok = fc.begin() if fc is not None else None
            if not self._run_group_native(g, arr, outputs):
                slab = arr[:, g.offsets[:, None]
                           + np.arange(g.width)[None, :]]
                self._run_group_numpy(g, slab, outputs)
            if tok is not None:
                fc.commit(tok, g.names, fieldcost.PLANE_DECODE,
                          n * g.width, n, g.label)

    def _run_groups_merged(self, groups, arr: np.ndarray,
                           outputs: Dict[int, dict], fc=None) -> list:
        """Decode all narrow binary/BCD/DISPLAY groups in one native pass
        (native.decode_numeric_groups); returns the groups still needing
        the per-group path. A single eligible group keeps the per-group
        kernel (same work, simpler call). The marshaled descriptor plan
        is cached per group subset — per-chunk pipeline decodes reuse it
        instead of re-marshaling ~ms of arrays every call."""
        key = tuple(id(g) for g in groups)
        cached = self._numeric_descs.get(key)
        if cached is None:
            descs, eligible, rest = [], [], []
            for g in groups:
                desc = None
                if g.codec is Codec.BINARY and not g.wide:
                    signed, big_endian, _, _ = g.variant
                    desc = dict(kind=native.NUMERIC_GROUP_BINARY,
                                offsets=g.offsets, width=g.width,
                                signed=signed, big_endian=big_endian)
                elif g.codec is Codec.BCD and not g.wide:
                    desc = dict(kind=native.NUMERIC_GROUP_BCD,
                                offsets=g.offsets, width=g.width)
                elif g.codec in (Codec.DISPLAY_NUM,
                                 Codec.DISPLAY_NUM_ASCII) and not g.wide:
                    signed, allow_dot, require_digits, _, sf, _ = g.variant
                    kind = (native.NUMERIC_GROUP_DISPLAY_EBCDIC
                            if g.codec is Codec.DISPLAY_NUM
                            else native.NUMERIC_GROUP_DISPLAY_ASCII)
                    desc = dict(kind=kind, offsets=g.offsets,
                                width=g.width, signed=signed,
                                allow_dot=allow_dot,
                                require_digits=require_digits,
                                dyn_sf=min(sf, 0))
                if desc is None or not len(g.columns):
                    rest.append(g)
                else:
                    descs.append(desc)
                    eligible.append(g)
            plan = (native.NumericGroupsPlan(descs)
                    if len(eligible) >= 2 else None)
            cached = (eligible, rest, plan)
            self._numeric_descs[key] = cached
        eligible, rest, plan = cached
        if plan is None:
            return groups
        tok = fc.begin() if fc is not None else None
        res = native.decode_numeric_groups(arr, None, plan=plan)
        if res is None:  # no native library: per-group numpy path
            if tok is not None:
                fc.discard(tok)
            return groups
        for g, out in zip(eligible, res):
            self._store_numeric(g, outputs, *out)
        if tok is not None:
            # ONE native pass decoded every narrow numeric group: split
            # its time across the groups weighted by the bytes each one
            # made the pass touch (columns * width), then per column
            n = arr.shape[0]
            fc.commit_weighted(
                tok,
                [(g.names, g.width, n * g.width, g.label)
                 for g in eligible],
                fieldcost.PLANE_DECODE, n)
        return rest

    def _run_group_native(self, g: _KernelGroup, arr: np.ndarray,
                          outputs: Dict[int, dict]) -> bool:
        """Single-pass C++ kernels reading straight from the packed batch
        (no intermediate slab). False -> caller uses the numpy path."""
        if g.codec is Codec.BINARY:
            signed, big_endian, _, wide = g.variant
            if wide:
                res = native.decode_binary_wide_cols(
                    arr, g.offsets, g.width, signed, big_endian)
                if res is None:
                    return False
                self._store_wide(g, outputs, *res)
                return True
            res = native.decode_binary_cols(
                arr, g.offsets, g.width, signed, big_endian)
            if res is None:
                return False
            self._store_numeric(g, outputs, *res)
            return True
        if g.codec is Codec.BCD:
            if g.wide:
                res = native.decode_bcd_wide_cols(arr, g.offsets, g.width)
                if res is None:
                    return False
                self._store_wide(g, outputs, *res)
                return True
            res = native.decode_bcd_cols(arr, g.offsets, g.width)
            if res is None:
                return False
            self._store_numeric(g, outputs, *res)
            return True
        if g.codec in (Codec.DISPLAY_NUM, Codec.DISPLAY_NUM_ASCII):
            signed, allow_dot, require_digits, _, sf, wide = g.variant
            kind = (native.DISPLAY_EBCDIC if g.codec is Codec.DISPLAY_NUM
                    else native.DISPLAY_ASCII)
            if wide:
                res = native.decode_display_wide_cols(
                    arr, g.offsets, g.width, kind, signed, allow_dot,
                    require_digits, dyn_sf=min(sf, 0))
                if res is None:
                    return False
                self._store_wide(g, outputs, *res)
                return True
            res = native.decode_display_cols(
                arr, g.offsets, g.width, kind, signed, allow_dot,
                require_digits, dyn_sf=min(sf, 0))
            if res is None:
                return False
            self._store_numeric(g, outputs, *res)
            return True
        return False

    def _run_group_numpy(self, g: _KernelGroup, slab: np.ndarray,
                         outputs: Dict[int, dict]) -> None:
        if g.codec is Codec.BINARY:
            signed, big_endian, _, wide = g.variant
            if wide:
                hi, lo, neg, valid = batch_np.decode_binary_wide(
                    slab, signed, big_endian)
                self._store_wide(g, outputs, hi, lo, neg, valid)
                return
            values, valid = batch_np.decode_binary(slab, signed, big_endian)
            self._store_numeric(g, outputs, values, valid)
        elif g.codec is Codec.BCD:
            _, wide = g.variant
            if wide:
                hi, lo, neg, valid = batch_np.decode_bcd_wide(slab)
                self._store_wide(g, outputs, hi, lo, neg, valid)
                return
            values, valid = batch_np.decode_bcd(slab)
            self._store_numeric(g, outputs, values, valid)
        elif g.codec in (Codec.DISPLAY_NUM, Codec.DISPLAY_NUM_ASCII):
            signed, allow_dot, require_digits, _, sf, wide = g.variant
            dyn_sf = sf if sf < 0 else 0
            if wide:
                fn = (batch_np.decode_display_ebcdic_wide
                      if g.codec is Codec.DISPLAY_NUM
                      else batch_np.decode_display_ascii_wide)
                hi, lo, neg, valid, dots = fn(slab, signed, allow_dot,
                                              require_digits, dyn_sf)
                self._store_wide(g, outputs, hi, lo, neg, valid, dots)
                return
            fn = (batch_np.decode_display_ebcdic
                  if g.codec is Codec.DISPLAY_NUM else batch_np.decode_display_ascii)
            values, valid, dots = fn(slab, signed, allow_dot, require_digits,
                                     dyn_sf)
            self._store_numeric(g, outputs, values, valid, dots)
        elif g.codec is Codec.FLOAT_IBM:
            s = slab if g.columns[0].params.big_endian else slab[..., ::-1]
            values, valid = batch_np.decode_ibm_float32(s)
            self._store_numeric(g, outputs, values, valid)
        elif g.codec is Codec.DOUBLE_IBM:
            s = slab if g.columns[0].params.big_endian else slab[..., ::-1]
            values, valid = batch_np.decode_ibm_float64(s)
            self._store_numeric(g, outputs, values, valid)
        elif g.codec is Codec.FLOAT_IEEE:
            values, valid = batch_np.decode_ieee_float(
                slab, g.columns[0].params.big_endian, double=False)
            self._store_numeric(g, outputs, values, valid)
        elif g.codec is Codec.DOUBLE_IEEE:
            values, valid = batch_np.decode_ieee_float(
                slab, g.columns[0].params.big_endian, double=True)
            self._store_numeric(g, outputs, values, valid)
        elif g.codec is Codec.EBCDIC_STRING:
            chars = batch_np.transcode_ebcdic(slab, self.lut)
            for pos, c in enumerate(g.columns):
                outputs[c.index] = {"bytes": chars[:, pos]}
        elif g.codec is Codec.ASCII_STRING:
            if self.non_standard_ascii_charset:
                for pos, c in enumerate(g.columns):
                    outputs[c.index] = {"bytes": slab[:, pos]}
            else:
                masked = batch_np.mask_ascii(slab)
                for pos, c in enumerate(g.columns):
                    outputs[c.index] = {"bytes": masked[:, pos]}
        else:  # UTF16 / HEX / RAW: keep raw bytes
            for pos, c in enumerate(g.columns):
                outputs[c.index] = {"bytes": slab[:, pos]}

    def _store_numeric(self, g: _KernelGroup, outputs: Dict[int, dict],
                       values, valid, dot_scale=None) -> None:
        """One output dict per column of a decoded group. `values` and
        `valid` are column views of the group's [n, ncols] matrices;
        `plane` names those matrices and the column's position in them,
        so that a consumer of many columns of one group (the Arrow list
        builder, over the slots of an OCCURS) can take the matrix's own
        rows instead of stacking the views back together."""
        values = np.asarray(values)
        valid = np.asarray(valid)
        dots = None if dot_scale is None else np.asarray(dot_scale)
        for pos, c in enumerate(g.columns):
            out = {"values": values[:, pos], "valid": valid[:, pos],
                   "plane": (values, valid, pos)}
            if dots is not None:
                out["dot_scale"] = dots[:, pos]
            elif c.params.scale_factor < 0 and g.codec is Codec.BINARY:
                # binary PIC P: exponent = |sf| + decimal digit count of the
                # value (addDecimalPoint over str(value)); the kernels have
                # no digit-count plane for binary, so derive it here
                out["dot_scale"] = _binary_dyn_dots(
                    values[:, pos], c.params.scale_factor)
            outputs[c.index] = out

    def _store_wide(self, g: _KernelGroup, outputs: Dict[int, dict],
                    hi, lo, negative, valid, dot_scale=None) -> None:
        """uint128-limb layout: magnitude = (values_hi << 64) | values,
        sign in `negative` (the columnar form of the BigDecimal plane)."""
        hi = np.asarray(hi)
        lo = np.asarray(lo)
        negative = np.asarray(negative)
        valid = np.asarray(valid)
        dots = None if dot_scale is None else np.asarray(dot_scale)
        for pos, c in enumerate(g.columns):
            out = {"values": lo[:, pos], "values_hi": hi[:, pos],
                   "negative": negative[:, pos], "valid": valid[:, pos]}
            if dots is not None:
                out["dot_scale"] = dots[:, pos]
            elif c.params.scale_factor < 0 and g.codec is Codec.BINARY:
                out["dot_scale"] = _wide_dyn_dots(
                    hi[:, pos], lo[:, pos], c.params.scale_factor)
            outputs[c.index] = out

    # -- jax backend ------------------------------------------------------

    def build_jax_decode_fn(self, mesh=None, groups=None):
        """The pure decode program: [batch, record_len] uint8 -> list of
        per-kernel-group output tuples. One XLA computation; suitable for
        `jax.jit` directly (single chip) or a sharded jit over a device mesh
        (parallel.ShardedColumnarDecoder).

        `groups`: the kernel groups the program decodes, one output tuple
        each in their order; default all of `kernel_groups`, at the
        plan's extent. decode_raw's launches partitioned by segment
        redefine build one program a set from the common groups and that
        redefine's (`_program_for`): its input is as wide as the
        furthest byte those groups read, the other redefines' columns
        are not in it, and a group's tuple is what it is in the whole
        program.

        backend "pallas": every numeric group (`_pallas_group_spec`)
        decodes through the fused Pallas kernel, one VMEM pass of each
        batch tile for the whole numeric plane (ops/pallas_tpu.py), in
        the orientation its column count asks for: a group that fills
        the 128 lanes with columns (OCCURS arrays) in row tiles, 32 rows
        a grid step, its byte planes strided slices; a narrower one
        (scattered scalars: all of exp1, the TPC-H queries, exp2) with
        the batch's rows in the lanes, 4,096 a grid step, its bytes read
        from one transpose of the record matrix. A program with both
        kinds makes two pallas_calls. Every other group, and with
        backend "jax" every group, takes the XLA route: its [n, columns,
        width] bytes are static slices chosen from its offsets (one for a
        run of adjacent columns, `width` strided ones for a run with other
        bytes between; XLA's gather only past SLICE_PIECES_MAX slices).
        EBCDIC strings become code points in one element-wise lookup
        a program (batch_jax.transcode_ebcdic) over the bytes their
        fields cover, each byte once however many redefines read it,
        and leave the program as the lookup's own matrix `points`
        [rows, width] (as rows of POINTS_LANES of the same row-major
        bytes where the batch divides into them, which a launch
        bucket does), one more tuple `(points,)` behind the groups':
        an EBCDIC string group's own tuple is empty, and
        `decode_all.points` (StringPoints) says where each column lies
        in the matrix. Its code points are uint8 where the code page's
        table fits a byte (every shipped page but cp875), uint16
        otherwise. A program round decode_all that reads a group inside
        the program takes its planes from `decode_all.group_planes(outs,
        gi)`, strings included.
        `decode_all.device_groups` counts the groups by route, and
        under `fused_rows_in_lanes` the fused ones of the second
        orientation. A decoder of variable-size OCCURS records
        (`self.regions`, the whole program only) first lays the rows to
        the static layout under the scope `cobrix.expand`
        (ops/expand.py), ahead of everything above, and hands the
        regions' counts back as the last tuple, `(counts,)`.
        `mesh`: with a multi-device mesh the fused
        pallas_calls are wrapped in shard_map over the ``data`` axis (GSPMD
        cannot partition a custom call — an unwrapped kernel would force
        an all-gather of the whole batch onto every chip); the non-fused
        XLA groups stay in the outer GSPMD context."""
        import jax
        import jax.numpy as jnp
        from ..ops import batch_jax

        batch_jax.ensure_x64()
        kernel_groups = (self.kernel_groups if groups is None
                         else list(groups))
        extent = (self.plan.max_extent if groups is None
                  else _groups_extent(kernel_groups))
        lut = self.lut

        fused = None
        interpret = None
        rows_in_lanes = 0
        fused_indices: List[int] = []
        if self.backend == "pallas":
            from ..ops import pallas_tpu

            strided = []
            for gi, g in enumerate(kernel_groups):
                sg = _pallas_group_spec(g)
                if sg is not None:
                    fused_indices.append(gi)
                    strided.append(sg)
            if strided:
                fused = pallas_tpu.build_fused_decode(strided, extent)
                interpret = fused.interpret
                rows_in_lanes = fused.rows_in_lanes
                if mesh is not None and mesh.devices.size > 1:
                    from jax.sharding import PartitionSpec

                    # decode is embarrassingly parallel: each device runs
                    # the fused kernel on its batch shard, no collectives
                    fused = jax.shard_map(
                        fused, mesh=mesh,
                        in_specs=PartitionSpec("data"),
                        out_specs=PartitionSpec("data"),
                        check_vma=False)

        # the route of every group the kernel does not take, from what
        # the plan knows (None: too many runs, the gather stays)
        pieces_of: Dict[int, Optional[list]] = {
            gi: _group_pieces(g) for gi, g in enumerate(kernel_groups)
            if gi not in fused_indices
            and g.codec is not Codec.HOST_FALLBACK}
        # EBCDIC bytes become code points in one lookup a program, each
        # byte once however many fields (redefines) read it, and leave
        # the program as that one matrix
        layout = self._points_of(kernel_groups)
        # across a mesh every output's leading axis stays the record axis
        lane_dense = mesh is None or mesh.devices.size == 1
        device_groups = {
            "fused": len(fused_indices),
            "fused_rows_in_lanes": rows_in_lanes,
            "sliced": sum(p is not None for p in pieces_of.values()),
            "gathered": sum(p is None for p in pieces_of.values())}

        def piece_bytes(data, piece, width):
            """[n, columns, width] bytes of one run of columns, by one
            static slice."""
            start, count, stride = piece
            n = data.shape[0]
            if _dense(piece, width):
                return jax.lax.slice_in_dim(
                    data, start, start + count * width,
                    axis=1).reshape(n, count, width)
            # other bytes between the columns: byte j of every column is
            # one strided slice (as pallas_tpu._byte_planes reads numerics)
            last = start + (count - 1) * stride
            return jnp.stack(
                [jax.lax.slice_in_dim(data, start + j, last + j + 1,
                                      stride=stride, axis=1)
                 for j in range(width)], axis=2)

        def group_bytes(data, g, pieces):
            if pieces is None:
                offs = jnp.asarray(g.offsets)
                return data[:, offs[:, None] + jnp.arange(g.width)[None, :]]
            parts = [piece_bytes(data, piece, g.width) for piece in pieces]
            return (parts[0] if len(parts) == 1
                    else jnp.concatenate(parts, axis=1))

        def group_scope(g):
            # named by the plan (codec and width), not by the order of
            # fusion: it lands in the `op_name` of every operation of the
            # group
            return jax.named_scope(
                "cobrix.group." + g.label.replace("/", "_"))

        regions = self.regions if groups is None else ()

        def decode_all(data):
            n = data.shape[0]
            counts = None
            if regions:
                # the rows came compact: every region to its maximum
                # size first, then the one static program
                with jax.named_scope("cobrix.expand"):
                    data, counts = odo.expand_rows(jnp, batch_jax, data,
                                                   regions)
            outs: List[tuple] = [()] * len(kernel_groups)
            if fused is not None:
                for gi, pair in zip(fused_indices, fused(data)):
                    outs[gi] = pair
            for gi, pieces in pieces_of.items():
                g = kernel_groups[gi]
                if g.codec is Codec.EBCDIC_STRING:
                    # no tuple of its own (as a host-fallback group has
                    # none): its code points leave in the matrix below
                    continue
                with group_scope(g):
                    outs[gi] = self._run_group_jax(
                        g, group_bytes(data, g, pieces), jnp, batch_jax)
            if layout is not None:
                # the lookup's input, [n, k] blocks side by side
                blocks = [jax.lax.slice_in_dim(data, lo, hi, axis=1)
                          for lo, hi in layout.spans]
                for gi, piece in layout.blocks:
                    g = kernel_groups[gi]
                    with group_scope(g):
                        block = (group_bytes(data, g, None) if piece is None
                                 else piece_bytes(data, piece, g.width))
                    blocks.append(block.reshape(
                        n, block.shape[1] * g.width))
                # the lookup's own scope: its operations are no one group's
                with jax.named_scope("cobrix.lookup.ebcdic"):
                    points = batch_jax.transcode_ebcdic(
                        blocks[0] if len(blocks) == 1
                        else jnp.concatenate(blocks, axis=1), lut,
                        layout.dtype)
                if lane_dense and (n * layout.width) % POINTS_LANES == 0:
                    points = points.reshape(-1, POINTS_LANES)
                outs.append((points,))
            if counts is not None:
                # behind the groups' tuples: the host reads the rows'
                # expanded lengths off the counts the device used
                outs.append((counts,))
            return outs

        def planes_of(outs, gi: int) -> GroupPlanes:
            """Group `gi`'s planes by name from `outs`, what decode_all
            returned: what a program built round decode_all (the device
            aggregate, the sharded statistics) reads a group through.
            An EBCDIC string group's code points are its columns cut out
            of the matrix, [rows, columns, width]: slices that cost
            nothing in the program, and nothing at all unless read."""
            g = kernel_groups[gi]
            if layout is None or gi not in layout.reads:
                return group_planes(g, outs[gi])
            points = outs[len(kernel_groups)][0].reshape(-1, layout.width)
            parts = [jax.lax.slice_in_dim(
                points, at, at + count * g.width,
                axis=1).reshape(points.shape[0], count, g.width)
                for at, count in layout.reads[gi]]
            return GroupPlanes(parts[0] if len(parts) == 1
                               else jnp.concatenate(parts, axis=1))

        # which route each group took, known when the program is built
        decode_all.device_groups = device_groups
        # whether the fused kernel goes through the Pallas interpreter;
        # None when the program holds no Pallas kernel at all
        decode_all.interpret = interpret
        # where each EBCDIC string column lies in the matrix behind the
        # groups' tuples (StringPoints); None: the program returns none
        decode_all.points = layout
        decode_all.group_planes = planes_of
        return decode_all

    def device_program(self):
        """The decode as an ops.device.DeviceProgram, built once."""
        if self._jax_fn is None:
            # double-checked: indexed-scan shards share one decoder across
            # ThreadPoolExecutor workers
            with _decoder_build_lock:
                if self._jax_fn is None:
                    self._jax_fn = self._read_program(
                        self.build_jax_decode_fn())
        return self._jax_fn

    @staticmethod
    def _read_program(fn, **jit_options):
        """The DeviceProgram of a read round `fn` (build_jax_decode_fn):
        everything `fn` returns is fetched, so its route counts say
        under `points_u8` whether the matrix of code points, where it
        returns one, crosses the link in 8 bits a code point."""
        from ..ops.device import DeviceProgram

        device_groups = dict(fn.device_groups)
        if fn.points is not None:
            device_groups["points_u8"] = int(fn.points.dtype is np.uint8)
        return DeviceProgram(fn, interpreted=fn.interpret,
                             device_groups=device_groups, points=fn.points,
                             **jit_options)

    def _program_for(self, groups):
        """The DeviceProgram that decodes `groups` and no other (a set
        of launches partitioned by redefine), built once a decoder;
        all of `kernel_groups` is `device_program()` itself."""
        if len(groups) == len(self.kernel_groups):
            return self.device_program()
        key = tuple(id(g) for g in groups)
        program = self._set_programs.get(key)
        if program is None:
            with _decoder_build_lock:
                program = self._set_programs.get(key)
                if program is None:
                    program = self._set_programs[key] = self._read_program(
                        self.build_jax_decode_fn(groups=groups))
        return program

    def _device_block(self, n: int, extent: int) -> int:
        """Rows per device launch: the jit bucket for `n`, capped so one
        launch reads at most DEVICE_BLOCK_BYTES (a share of it where the
        program expands variable regions: EXPAND_BLOCK_SHARE)."""
        limit = DEVICE_BLOCK_BYTES // (EXPAND_BLOCK_SHARE if self.regions
                                       else 1)
        cap = 256
        while cap * 2 * extent <= limit:
            cap *= 2
        return min(self._bucket_size(n), cap)

    def _decode_jax(self, arr: np.ndarray) -> Dict[int, dict]:
        """Every row of the packed [n, extent] matrix through the whole
        program (`_decode_launches`). What decode_raw's row masks can
        spare the link never comes here: see `_decode_raw_partitioned`."""
        return self._decode_launches(arr)[0]

    def _decode_launches(self, arr: np.ndarray):
        """Every row of the packed [n, extent] matrix through the whole
        program, in blocks of one bucket size (`_device_block`): (the
        columns' outputs, the regions' counts [n, regions] or None)."""
        program = self.device_program()
        n, extent = arr.shape
        block = self._device_block(n, extent)
        ctx = obs_context.current()
        stats = ctx.device_stats if ctx is not None else None
        fc = fieldcost.current()
        tok = fc.begin() if fc is not None else None

        def blocks():
            # an empty batch still launches once: the outputs' dtypes and
            # column counts come from the program
            for start in range(0, max(n, 1), block):
                rows = arr[start:start + block]
                m = rows.shape[0]
                if m != block:
                    with Stage("pack"):
                        padded = np.zeros((block, extent), dtype=np.uint8)
                        padded[:m] = rows
                    rows = padded
                yield rows, m

        with annotate("cobrix_decode"):
            parts = self._launch_blocks(program, blocks(), stats)
        merged = self._merge_blocks(parts)
        with Stage("collect"):
            outputs = self.collect_outputs(merged, n,
                                           points=program.points)
            counts = (np.asarray(merged[-1][0])[:n] if self.regions
                      else None)
        self._commit_device_cost(fc, tok, n)
        return outputs, counts

    def _commit_device_cost(self, fc, tok, n: int) -> None:
        """The device decode of `n` rows, charged: jitted programs decode
        every group at once, so their wall (transfers included) is split
        across the groups by bytes touched — coarser than the host
        path's per-launch timing, but the same table."""
        if tok is not None:
            fc.commit_weighted(
                tok,
                [(g.names, g.width, n * g.width, g.label)
                 for g in self.kernel_groups
                 if g.codec is not Codec.HOST_FALLBACK and g.names],
                fieldcost.PLANE_DECODE, n)

    @staticmethod
    def _submit_block(program, rows, m: int, *more):
        """One [block, extent] uint8 buffer of `m` real rows over the
        link and into `program` (with `more` arguments after it), not
        waited for: the launch `_fetch_block` brings home."""
        import jax

        with Stage("h2d"):
            x = jax.device_put(rows)
        compiled, built = program.compiled_for(x, *more)
        with Stage("launch"):
            device_outs = compiled.executable(x, *more)
        return rows.shape, m, x.nbytes, device_outs, compiled, built, program

    @staticmethod
    def _fetch_block(launched, stats):
        """(fetched outputs, real rows) of one `_submit_block` launch,
        counted in `stats`."""
        import jax

        shape, m, h2d_bytes, device_outs, compiled, built, program = launched
        leaves = jax.tree_util.tree_leaves(device_outs)
        with Stage("d2h_wait"):
            # the copies home are queued first, as `device_get` alone
            # would queue them: they start when the outputs exist, not
            # when this thread has the interpreter lock again. Then the
            # wait for the outputs (the rest of the H2D copy, the
            # program's run, this thread's wake-up) and what is left of
            # the bytes' way home, apart
            jax.copy_to_host_async(device_outs)
            with Stage("d2h_wait.ready"):
                jax.block_until_ready(device_outs)
            with LinkCopy():
                host_outs = jax.device_get(device_outs)
        if stats is not None:
            stats.note_launch(
                shape, m, h2d_bytes,
                sum(leaf.nbytes for leaf in leaves),
                {d for leaf in leaves for d in leaf.devices()},
                compiled, built, program.interpreted,
                program.device_groups,
                strided_nbytes(jax.tree_util.tree_leaves(host_outs)))
        if program.points is not None:
            # the [rows, width] matrix, whatever shape it crossed in
            at = program.points.index
            host_outs[at] = (host_outs[at][0].reshape(shape[0], -1),)
        return host_outs, m

    @classmethod
    def _launch_blocks(cls, program, blocks, stats) -> list:
        """Each ([block, extent] uint8 buffer, its real rows) of `blocks`
        over the link, through `program` and back: [(fetched outputs,
        real rows)]."""
        return [cls._fetch_block(cls._submit_block(program, rows, m), stats)
                for rows, m in blocks]

    @staticmethod
    def _merge_blocks(parts) -> list:
        """The blocks' outputs as one, padding dropped between them; a
        lone block's are handed on as fetched (collect_outputs drops its
        padding)."""
        if len(parts) == 1:
            return parts[0][0]
        with Stage("merge"):
            return [tuple(np.concatenate([outs[gi][k][:m]
                                          for outs, m in parts])
                          for k in range(len(group_outs)))
                    for gi, group_outs in enumerate(parts[0][0])]

    def collect_outputs(self, device_outs, n: int, groups=None,
                        points: Optional[StringPoints] = None
                        ) -> Dict[int, dict]:
        """A program's outputs (device arrays, or already fetched) as
        host numpy column arrays, dropping batch padding (`n` = real
        record count). `groups`: the groups the program was built from
        (build_jax_decode_fn), default all; `device_outs` holds their
        tuples in that order and, where the program has EBCDIC strings
        (`points`: its StringPoints), the tuple of its matrix of code
        points behind them. The matrix is kept as it was fetched and
        its columns are views of it, no copies: `bytes` a column's
        [n, width] code points, `char_plane` the matrix and the
        column's place in it (what `plane` is for numerics): what the
        one native pass that builds every string column of a batch
        reads (DecodedBatch.string_arrow_buffers), and the list
        builder."""
        outputs: Dict[int, dict] = {}
        groups = self.kernel_groups if groups is None else groups
        matrix = None
        if points is not None:
            matrix = np.asarray(device_outs[len(groups)][0]).reshape(
                -1, points.width)[:n]
        for g, out in zip(groups, device_outs):
            if g.codec is Codec.HOST_FALLBACK:
                continue
            if g.codec is Codec.EBCDIC_STRING:
                for c in g.columns:
                    at, width = points.columns[c.index]
                    outputs[c.index] = {"bytes": matrix[:, at:at + width],
                                        "char_plane": (matrix, at)}
            elif g.codec in _STRING_CODECS:
                chars = np.asarray(out[0])[:n]
                flat = chars.reshape(len(chars), len(g.columns) * g.width)
                for pos, c in enumerate(g.columns):
                    outputs[c.index] = {"bytes": chars[:, pos],
                                        "char_plane": (flat, pos * g.width)}
            elif g.wide:
                arrs = [np.asarray(o)[:n] for o in out]
                self._store_wide(g, outputs, *arrs)
            elif g.codec in (Codec.DISPLAY_NUM, Codec.DISPLAY_NUM_ASCII):
                values, valid, dots = (np.asarray(o)[:n] for o in out)
                self._store_numeric(g, outputs, values, valid, dots)
            else:
                values, valid = (np.asarray(o)[:n] for o in out)
                if g.codec in (Codec.DOUBLE_IBM, Codec.DOUBLE_IEEE):
                    # device returns IEEE754 bit patterns (uint64); f64
                    # bitcasts on TPU round through the emulation path
                    values = values.view(np.float64)
                self._store_numeric(g, outputs, values, valid)
        return outputs

    def zero_row_outputs(self, g: _KernelGroup) -> Dict[int, dict]:
        """The output dicts of `g`'s columns over no rows at all, in the
        planes `collect_outputs` would hand out (an EBCDIC string's code
        points as wide as the code page's table asks): what a batch
        without a single row of `g`'s redefine scatters from."""
        outputs: Dict[int, dict] = {}
        shape = (0, len(g.columns))
        display = g.codec in (Codec.DISPLAY_NUM, Codec.DISPLAY_NUM_ASCII)
        dots = np.zeros(shape, dtype=np.int32) if display else None
        valid = np.zeros(shape, dtype=bool)
        if g.codec in _STRING_CODECS:
            chars = np.zeros(shape + (g.width,), dtype=(
                self.points_dtype if g.codec is Codec.EBCDIC_STRING
                else np.uint8))
            for pos, c in enumerate(g.columns):
                outputs[c.index] = {"bytes": chars[:, pos]}
        elif g.wide:
            limbs = np.zeros(shape, dtype=np.uint64)
            self._store_wide(g, outputs, limbs, limbs, valid, valid, dots)
        else:
            self._store_numeric(g, outputs, np.zeros(shape, dtype=(
                np.float64 if g.codec in _FLOAT_CODECS else np.int64)),
                valid, dots)
        return outputs

    def _run_group_jax(self, g: _KernelGroup, slab, jnp, batch_jax):
        """One group's outputs from the [n, ncols, width] slab of its
        bytes (an EBCDIC string group never comes here: its code points
        are the program's matrix)."""
        if g.codec is Codec.BINARY:
            signed, big_endian, fits32, wide = g.variant
            if wide:
                return batch_jax.decode_binary_wide(slab, signed, big_endian)
            out_dtype = jnp.int32 if fits32 else jnp.int64
            return batch_jax.decode_binary(slab, signed, big_endian, out_dtype)
        if g.codec is Codec.BCD:
            fits32, wide = g.variant
            if wide:
                return batch_jax.decode_bcd_wide(slab)
            out_dtype = jnp.int32 if fits32 else jnp.int64
            return batch_jax.decode_bcd(slab, out_dtype)
        if g.codec in (Codec.DISPLAY_NUM, Codec.DISPLAY_NUM_ASCII):
            signed, allow_dot, require_digits, fits32, sf, wide = g.variant
            dyn_sf = sf if sf < 0 else 0
            if wide:
                fn = (batch_jax.decode_display_ebcdic_wide
                      if g.codec is Codec.DISPLAY_NUM
                      else batch_jax.decode_display_ascii_wide)
                return fn(slab, signed, allow_dot, require_digits, dyn_sf)
            out_dtype = jnp.int32 if fits32 else jnp.int64
            fn = (batch_jax.decode_display_ebcdic
                  if g.codec is Codec.DISPLAY_NUM
                  else batch_jax.decode_display_ascii)
            return fn(slab, signed, allow_dot, require_digits, out_dtype,
                      dyn_sf)
        if g.codec is Codec.FLOAT_IBM:
            s = slab if g.columns[0].params.big_endian else slab[..., ::-1]
            return batch_jax.decode_ibm_float32(s)
        if g.codec is Codec.DOUBLE_IBM:
            s = slab if g.columns[0].params.big_endian else slab[..., ::-1]
            return batch_jax.decode_ibm_float64(s)
        if g.codec is Codec.FLOAT_IEEE:
            return batch_jax.decode_ieee_float(
                slab, g.columns[0].params.big_endian, double=False)
        if g.codec is Codec.DOUBLE_IEEE:
            return batch_jax.decode_ieee_float(
                slab, g.columns[0].params.big_endian, double=True)
        if g.codec is Codec.ASCII_STRING and not self.non_standard_ascii_charset:
            return (batch_jax.mask_ascii(slab),)
        return (slab,)

    # -- host fallback -----------------------------------------------------

    def _decode_host_fallback(self, arr: np.ndarray,
                              outputs: Dict[int, dict]) -> None:
        fc = fieldcost.current()
        n = arr.shape[0]
        for g in self.kernel_groups:
            if g.codec is not Codec.HOST_FALLBACK:
                continue
            for c in g.columns:
                tok = fc.begin() if fc is not None else None
                values = []
                for i in range(n):
                    chunk = arr[i, c.offset: c.offset + c.width].tobytes()
                    values.append(self.options.decode(c.dtype, chunk))
                outputs[c.index] = {"host": values}
                if tok is not None:
                    fc.commit(tok, (self.plan.cost_name(c),),
                              fieldcost.PLANE_DECODE, n * c.width, n,
                              "host")
