"""Vectorized Arrow output: kernel column arrays -> pyarrow Table.

The reference feeds Spark per-record GenericRows because a Spark source
must (SparkCobolRowType.scala:24); a columnar framework emits Arrow arrays
straight from the kernel outputs instead. Numeric columns become typed
arrays from the (values, valid) numpy pairs without touching Python
objects; Decimal columns are built as decimal128 buffers from the int
mantissas; strings come from the LUT code-point matrix through one
vectorized trim + mask gather; OCCURS arrays become ListArrays whose
offsets derive from the DEPENDING-ON counts. Schema types follow the same
mapping as the output StructType (spark-cobol schema/CobolSchema.scala:
77-173): Decimal->decimal128(p,s), Integral->int32/int64 by precision
bucket, COMP-1/2->float32/float64, RAW->binary, OCCURS->list.

The fallback for anything the vectorized path can't express (host-fallback
codecs, truncated variable-length tails, non-ASCII code points, custom
charsets) is the per-column Python value list — same values, same nulls.
"""
from __future__ import annotations

from typing import List, Optional, Sequence

import numpy as np

from .. import native
from ..copybook.ast import Group, Primitive, Statement
from ..copybook.datatypes import AlphaNumeric, Integral
from ..obs import fieldcost
from ..plan.compiler import Codec
from ..profiling import Stage
from ..copybook.datatypes import SchemaRetentionPolicy, TrimPolicy
from .columnar import (
    _FLOAT_CODECS,
    _NATIVE_TRIM_MODES,
    _STRING_CODECS,
    _dyn_scale,
    _identity_lut,
    _is_wide,
    _resolve_occurs,
    DecodedBatch,
    fixed_point_exponent,
)
from .schema import (
    ArrayType,
    Field,
    SimpleType,
    StructType,
    primitive_data_type,
)


def _pa():
    import pyarrow as pa
    return pa


def to_arrow_type(t):
    """Our schema type -> pyarrow type (decimal(p,s) strings included)."""
    pa = _pa()
    if isinstance(t, SimpleType):
        name = t.name
        if name == "string":
            return pa.string()
        if name == "integer":
            return pa.int32()
        if name == "long":
            return pa.int64()
        if name == "float":
            return pa.float32()
        if name == "double":
            return pa.float64()
        if name == "binary":
            return pa.binary()
        if name.startswith("decimal("):
            p, s = name[8:-1].split(",")
            return pa.decimal128(int(p), int(s))
        raise TypeError(f"Unknown simple type {name}")
    if isinstance(t, StructType):
        return pa.struct([(f.name, to_arrow_type(f.dtype)) for f in t.fields])
    if isinstance(t, ArrayType):
        return pa.list_(to_arrow_type(t.element))
    raise TypeError(t)


def arrow_schema(struct: StructType):
    # memoized on the StructType instance: per-chunk pipeline assembly
    # calls this once per chunk, and rebuilding a wide schema (exp1: 195
    # typed fields) is pure GIL-held overhead
    cached = getattr(struct, "_pa_schema", None)
    if cached is not None:
        return cached
    pa = _pa()
    schema = pa.schema([(f.name, to_arrow_type(f.dtype))
                        for f in struct.fields])
    try:
        struct._pa_schema = schema
    except AttributeError:  # slotted/frozen struct types stay uncached
        pass
    return schema


def _validity_buffer(valid: np.ndarray):
    pa = _pa()
    # py_buffer holds a reference to the packed array: zero-copy
    return pa.py_buffer(np.packbits(valid, bitorder="little"))


# columns a step of `_record_major`: a cache line of int32 values
_TRANSPOSE_COLUMNS = 16


def _record_major(plane: np.ndarray) -> np.ndarray:
    """The [n, k] plane flat in record-major order: as it lies where its
    rows are contiguous, one copy where they are not. A TPU hands its
    planes back column-major, and numpy's copy of such a matrix walks
    the destination's rows, taking one element from each of k far-apart
    columns; a few columns at a time it reads whole cache lines down the
    columns instead. On the chip's host, a [6500, 2000] plane: int32 154
    to 99 ms, bool 95 to 25 ms (PERF.md section 6, PR 29)."""
    if plane.ndim != 2 or abs(plane.strides[1]) <= abs(plane.strides[0]):
        return np.ascontiguousarray(plane).reshape(-1)
    out = np.empty(plane.shape, dtype=plane.dtype)
    for s in range(0, plane.shape[1], _TRANSPOSE_COLUMNS):
        out[:, s:s + _TRANSPOSE_COLUMNS] = plane[:, s:s + _TRANSPOSE_COLUMNS]
    return out.reshape(-1)


def _packed_validity(valid: np.ndarray):
    """(Arrow validity bitmap, null count) of a contiguous bool plane
    (None: nothing is null): no bitmap where nothing is null (the usual
    case, and `all` reads a byte plane several times faster than packing
    it), else packed once."""
    if valid is None or valid.all():
        return None, 0
    packed = native.pack_validity(valid.view(np.uint8))
    if packed is None:  # no native library
        return (_validity_buffer(valid),
                valid.size - int(np.count_nonzero(valid)))
    bitmap, nulls = packed
    return _pa().py_buffer(bitmap), nulls


def _decimal128_from_mantissa(mantissa: np.ndarray, valid: np.ndarray,
                              pa_type):
    """decimal128 array with the int64 mantissa as the unscaled value
    (`valid` None: nothing is null)."""
    pa = _pa()
    n = len(mantissa)
    le = np.zeros((n, 2), dtype="<i8")
    le[:, 0] = mantissa
    le[:, 1] = mantissa >> 63  # sign extension of the high limb
    vbuf = (None if valid is None or valid.all()
            else _validity_buffer(valid))
    return pa.Array.from_buffers(pa_type, n, [vbuf, pa.py_buffer(le)])


def _static_decimal_shift(spec, pa_type) -> Optional[int]:
    """Mantissa power-of-ten shift for a fixed-exponent decimal column
    (None when out of the exact-int64 0..18 window). The single source of
    the rule for the per-column, flat-OCCURS, and native-limb paths."""
    shift = pa_type.scale + fixed_point_exponent(spec)
    return shift if 0 <= shift <= 18 else None


def _numpy_dtype_for(pa_type):
    """pa numeric type -> the numpy dtype the kernels' outputs cast to."""
    pa = _pa()
    if pa.types.is_floating(pa_type):
        return np.float32 if pa.types.is_float32(pa_type) else np.float64
    return np.int32 if pa.types.is_int32(pa_type) else np.int64


# Java String.trim strips everything <= ' ' on both sides; left/right trim
# strip " \t" (scalar_decoders._trim parity)
_JAVA_TRIM = "".join(map(chr, range(0x21)))
_LR_TRIM = " \t"


def _string_from_codepoints(mat: np.ndarray, trimming: TrimPolicy):
    """[n, w] code points (masked ASCII bytes, or a LUT's output: uint8
    where the code page's table fits a byte, uint16 otherwise) -> Arrow
    string array. Requires every code point <= 0x7F, whatever the dtype,
    so UTF-8 bytes == code points (the caller tests the values and falls
    back otherwise); the fixed-width matrix becomes one zero-gather string
    buffer with uniform offsets, and trimming runs in Arrow's C++
    kernels."""
    import pyarrow.compute as pc

    pa = _pa()
    n, w = mat.shape
    data = np.ascontiguousarray(mat.astype(np.uint8, copy=False))
    big = n * w > 2**31 - 8
    off_t, s_t = ("<i8", pa.large_string()) if big else ("<i4", pa.string())
    offsets = np.arange(n + 1, dtype=off_t) * w
    arr = pa.Array.from_buffers(
        s_t, n, [None, pa.py_buffer(offsets), pa.py_buffer(data)])
    if trimming is TrimPolicy.BOTH:
        arr = pc.utf8_trim(arr, characters=_JAVA_TRIM)
    elif trimming is TrimPolicy.LEFT:
        arr = pc.utf8_ltrim(arr, characters=_LR_TRIM)
    elif trimming is TrimPolicy.RIGHT:
        arr = pc.utf8_rtrim(arr, characters=_LR_TRIM)
    if big:
        arr = arr.cast(pa.string())
    return arr


_PA_LAZY_WARMED = False


def _warm_pa_lazy_imports() -> None:
    """Trigger pyarrow's lazy pandas-shim import outside any attribution
    region. The first masked `pa.array` call in a process imports pandas
    (~0.8s when installed); without this warm-up that one-time cost
    lands on whichever field happens to assemble first and tops the
    explain cost table with a lie. Only called when attribution is on —
    plain reads keep pyarrow's lazy behavior."""
    global _PA_LAZY_WARMED
    if _PA_LAZY_WARMED:
        return
    _PA_LAZY_WARMED = True
    pa = _pa()
    pa.array(np.zeros(1, dtype=np.int64), mask=np.array([True]))


def _asm_descriptor(spec, pa_type):
    """(kind, flags, dyn_sf, out_kind, dec_mode, shift, maxd) descriptor
    for the fused native decode->Arrow kernel, or None when the column's
    shape must keep its existing path. The rules mirror the per-column
    assembly routes byte for byte: same decode variants, same decimal
    shift/precision bounds (decimal128_batch), same fallback windows."""
    pa = _pa()
    codec = spec.codec
    p = spec.params
    wide = _is_wide(spec)
    if codec is Codec.BINARY:
        kind = (native.ASM_KIND_BINARY_WIDE if wide
                else native.ASM_KIND_BINARY)
        flags = int(bool(p.signed)) | (int(bool(p.big_endian)) << 1)
        dyn_sf = 0
    elif codec is Codec.BCD:
        kind = native.ASM_KIND_BCD_WIDE if wide else native.ASM_KIND_BCD
        flags = 0
        dyn_sf = 0
    elif codec in (Codec.DISPLAY_NUM, Codec.DISPLAY_NUM_ASCII):
        base = (native.ASM_KIND_DISPLAY_E if codec is Codec.DISPLAY_NUM
                else native.ASM_KIND_DISPLAY_A)
        kind = base + (4 if wide else 0)
        allow_dot = bool(p.explicit_decimal)
        # unconditional, matching columnar._variant_key: blank-filled
        # implied-point decimals decode to null, not 0.00
        require_digits = True
        flags = (int(bool(p.signed)) | (int(allow_dot) << 2)
                 | (int(require_digits) << 3))
        dyn_sf = min(p.scale_factor, 0)
    elif codec in _FLOAT_CODECS:
        kind = {Codec.FLOAT_IEEE: native.ASM_KIND_IEEE_F32,
                Codec.DOUBLE_IEEE: native.ASM_KIND_IEEE_F64,
                Codec.FLOAT_IBM: native.ASM_KIND_IBM_F32,
                Codec.DOUBLE_IBM: native.ASM_KIND_IBM_F64}[codec]
        flags = int(bool(p.big_endian)) << 1
        dyn_sf = 0
        wide = False
    else:
        return None

    dec_mode = native.ASM_DEC_STATIC
    shift = 0
    maxd = 0
    if pa.types.is_decimal(pa_type):
        if codec in _FLOAT_CODECS:
            return None
        out_kind = native.ASM_OUT_DECIMAL128
        if p.explicit_decimal or _dyn_scale(spec):
            if codec in (Codec.DISPLAY_NUM, Codec.DISPLAY_NUM_ASCII):
                # per-value exponent from the decoded dot_scale plane
                dec_mode = native.ASM_DEC_DOTS
                shift = pa_type.scale
            elif codec is Codec.BINARY and p.scale_factor < 0:
                # binary PIC P: exponent = |sf| + decimal digit count of
                # the value (columnar._binary_dyn_dots / _wide_dyn_dots)
                dec_mode = native.ASM_DEC_DIGIT_COUNT
                shift = pa_type.scale + p.scale_factor
            else:
                return None
        else:
            shift = pa_type.scale + fixed_point_exponent(spec)
            if not 0 <= shift <= 38:
                return None  # the per-column fallback owns this window
        # the same precision-bound rule as decimal128_batch callers: wide
        # limbs and >18-digit mantissas bound by the declared precision
        # (overflow -> exact-Decimal fallback); narrow <=18 stays unbounded
        maxd = pa_type.precision if (wide or pa_type.precision > 18) else 0
    elif pa.types.is_integer(pa_type):
        if wide or codec in _FLOAT_CODECS:
            return None
        if not (pa.types.is_int32(pa_type) or pa.types.is_int64(pa_type)):
            return None
        out_kind = (native.ASM_OUT_INT32 if pa.types.is_int32(pa_type)
                    else native.ASM_OUT_INT64)
    elif pa.types.is_floating(pa_type):
        if codec not in _FLOAT_CODECS:
            return None
        is_f32 = pa.types.is_float32(pa_type)
        # the decode width must match the output width exactly (the
        # kernel writes the decoded float in its natural precision)
        if is_f32 != (codec in (Codec.FLOAT_IEEE, Codec.FLOAT_IBM)):
            return None
        out_kind = (native.ASM_OUT_FLOAT32 if is_f32
                    else native.ASM_OUT_FLOAT64)
    else:
        return None
    return (kind, flags, dyn_sf, out_kind, dec_mode, shift, maxd)


class ArrowBatchBuilder:
    """Builds Arrow arrays for one DecodedBatch — either a single active
    segment (`active`), or a decode-once whole-plan batch where
    `redefine_masks` gates each segment redefine per row via the struct
    validity bitmap (inactive rows' decoded bytes are garbage, but a null
    parent struct masks its children by Arrow semantics)."""

    def __init__(self, batch: DecodedBatch, active: Optional[str],
                 redefine_masks: Optional[dict] = None,
                 nested: Optional[dict] = None):
        self.batch = batch
        # id(statement) -> its array, built elsewhere: an array of
        # variable arrays, from rows of its own (`nested_list`)
        self.nested = nested or {}
        self.decoder = batch.decoder
        self.active = active
        self.redefine_masks = redefine_masks
        self.n = batch.n_records
        # per-field cost attribution (None = off): the per-column
        # assembly step is timed at column granularity; nested regions
        # (string transcode / decimal128 group builds triggered inside
        # a column's build) charge their own time, not the column's.
        # Taken from the BATCH (captured at decode time), not the obs
        # context — sequential `to_arrow` runs after the read's context
        # deactivated, and CobolData's pooled table builds run on
        # threads that never activated it
        self.fc = batch.field_costs
        if self.fc is not None:
            _warm_pa_lazy_imports()
        # the read's DeviceStats, captured by the batch the same way: the
        # `assemble.*` stages below count on it (None outside a read)
        self.stats = batch.stage_stats

    # -- leaves ------------------------------------------------------------

    def leaf_strings_at(self, sts, positions: np.ndarray) -> dict:
        """String-leaf values built AT `positions`, for EVERY eligible
        statement of one struct in ONE subset kernel call (stage
        `assemble.string`), from what the batch holds: the raw file
        image, or else the matrix of 8-bit code points a device program
        handed back (`char_plane`), read as an image of rows of its
        width with a table that maps a code point to itself, since the
        chip has done the lookup. Returns {id(st): pa.StringArray} for
        the leaves it could build; callers fall back to the full-length
        build + take for the rest (non-EBCDIC codecs, 16-bit code
        points, no image or matrix, truncated rows, a column whose UTF-8
        outgrew its buffer, native library unavailable)."""
        pa = _pa()
        batch = self.batch
        rs = batch.raw_source
        trim = _NATIVE_TRIM_MODES.get(self.decoder.plan.trimming)
        if trim is None or not native.available():
            return {}
        lengths = batch.lengths if rs is None else rs[2]
        sub_lens = None if lengths is None else lengths[positions]
        matrix = None
        chosen, specs, starts = [], [], []
        for st in sts:
            col = self.decoder.slot_map.get((id(st), ()))
            if col is None:
                continue
            spec = self.decoder.plan.columns[col]
            if spec.codec is not Codec.EBCDIC_STRING:
                continue
            if sub_lens is not None and bool(
                    (sub_lens < spec.offset + spec.width).any()):
                continue  # truncated tails keep the scalar-owned path
            if rs is None:
                plane = batch._out[col].get("char_plane")
                if (plane is None or plane[0].dtype != np.uint8
                        or (matrix is not None and plane[0] is not matrix)):
                    continue
                matrix, start = plane
            else:
                start = spec.offset
            chosen.append(st)
            specs.append(spec)
            starts.append(start)
        if not specs:
            return {}
        if rs is None:
            matrix = np.ascontiguousarray(matrix)
            width = matrix.shape[1]
            buf, lut = matrix.reshape(-1), _identity_lut()
            sub_offs = positions.astype(np.int64) * width
            sub_lens = np.full(len(positions), width, dtype=np.int64)
        else:
            buf, lut = rs[0], self.decoder.lut
            sub_offs = rs[1][positions]
        with Stage("assemble.string", self.stats):
            res = native.string_cols_arrow_raw(
                buf, sub_offs, sub_lens, np.asarray(starts, dtype=np.int64),
                np.asarray([sp.width for sp in specs], dtype=np.int64),
                lut, trim)
        out = {}
        for st, r in zip(chosen, res or ()):
            if r is not None:
                offsets, data = r
                out[id(st)] = pa.Array.from_buffers(
                    pa.string(), len(positions),
                    [None, pa.py_buffer(offsets), pa.py_buffer(data)])
        return out

    def leaf_numeric_at(self, st: Primitive, positions: np.ndarray):
        """Integer/float leaf values gathered AT `positions` — the numpy
        gather happens before the Arrow build instead of a full-length
        array + take. None -> caller uses the full path (decimals, wide
        planes, truncation, host fallback)."""
        pa = _pa()
        col = self.decoder.slot_map.get((id(st), ()))
        if col is None:
            return None
        spec = self.decoder.plan.columns[col]
        pa_type = to_arrow_type(primitive_data_type(st))
        if not (pa.types.is_integer(pa_type)
                or pa.types.is_floating(pa_type)):
            return None
        if spec.codec in _STRING_CODECS:
            return None
        lengths = self.batch.lengths
        if lengths is not None and bool(
                (lengths[positions] < spec.offset + spec.width).any()):
            return None  # truncated tails keep the scalar-owned path
        out = self.batch.column_arrays(col)
        if "values" not in out or "values_hi" in out:
            return None
        values = np.asarray(out["values"])[positions]
        valid = np.asarray(out["valid"])[positions]
        return pa.array(
            values.astype(_numpy_dtype_for(pa_type), copy=False),
            mask=None if valid.all() else ~valid)

    def _relevant_of(self, spec):
        """Row-visibility mask for a column of a decode-once batch (None =
        visible everywhere)."""
        if self.redefine_masks is not None and spec.segment:
            return self.redefine_masks.get(spec.segment.upper())
        return None

    def _python_fallback(self, col: int, pa_type, relevant=None):
        pa = _pa()
        # `relevant` (decode-once batches): rows hidden by a null parent
        # struct materialize as None and skip the truncation fixups
        return pa.array(self.batch.column_values(col, relevant=relevant),
                        type=pa_type)

    # -- fused native assembly (decode -> Arrow buffers in one pass) -------

    def _asm_call(self, specs, descs, out_ptrs, out_strides, valid_ptrs,
                  valid_strides, row_masks=None, rows=None):
        """One fused-kernel invocation over prepared destinations: the
        GIL is released for the whole decode+assemble pass. `row_masks`:
        per-spec row-visibility masks (decode-once redefines) — hidden
        rows emit null in-kernel without decoding. `rows`: decode ONLY
        these record indices (compact output of len(rows) rows — the
        destinations must be sized for that). Returns the per-column ok
        array, or None when the library is unavailable."""
        batch = self.batch
        k = len(specs)
        col_offsets = np.fromiter((s.offset for s in specs), np.int64, k)
        widths = np.fromiter((s.width for s in specs), np.int32, k)
        kinds = np.fromiter((d[0] for d in descs), np.int32, k)
        flags = np.fromiter((d[1] for d in descs), np.int32, k)
        dyn_sfs = np.fromiter((d[2] for d in descs), np.int32, k)
        out_kinds = np.fromiter((d[3] for d in descs), np.int32, k)
        dec_modes = np.fromiter((d[4] for d in descs), np.int32, k)
        shifts = np.fromiter((d[5] for d in descs), np.int64, k)
        maxds = np.fromiter((d[6] for d in descs), np.int32, k)
        rs = batch.raw_source
        if rs is not None:
            src, offs, lens = rs
            extent = src.size
            if rows is not None:
                offs = offs[rows]
                lens = lens[rows]
        else:
            if rows is not None:
                return None  # packed source: subsetting would copy bytes
            src = np.ascontiguousarray(batch.data)
            offs = lens = None
            extent = src.shape[1] if src.ndim == 2 else 0
        n = self.n if rows is None else len(rows)
        ok = native.assemble_cols_arrow(
            src, offs, lens, extent, col_offsets, widths, kinds, flags,
            dyn_sfs, out_kinds, dec_modes, shifts, maxds,
            out_ptrs, out_strides, valid_ptrs, valid_strides, n,
            row_masks=row_masks)
        if ok is not None and batch.pass_counts is not None:
            batch.pass_counts.incr("fused_assembly")
        return ok

    def _native_scalar_array(self, col: int):
        """pa.Array for a scalar (non-OCCURS-slot) numeric/float column
        from the batch-wide fused assembly, or None (ineligible column,
        exact-Decimal fallback, library unavailable). The first call
        assembles EVERY eligible deferred column of the batch in one
        native pass; later leaves hit the cache."""
        cache = self.batch._asm_cache
        if cache is None:
            cache = self._build_native_scalars()
            self.batch._asm_cache = cache
        return cache.get(col)

    def _build_native_scalars(self) -> dict:
        batch = self.batch
        if not native.available():
            return {}
        entries = []
        lengths = batch.lengths
        for c in self.decoder.plan.columns:
            if c.slot_path or c.statement is None:
                continue
            out = batch._out.get(c.index)
            if out is None or "lazy_numeric" not in out:
                continue  # planes already exist: _leaf_array_impl reads them
            pa_type = to_arrow_type(primitive_data_type(c.statement))
            desc = _asm_descriptor(c, pa_type)
            if desc is None:
                continue
            relevant = self._relevant_of(c)
            if lengths is not None:
                trunc = lengths < c.offset + c.width
                if relevant is not None:
                    trunc = trunc & relevant
                if bool(trunc.any()):
                    continue  # the scalar path owns partial-field rules
            # masked columns ride the same pass: their row mask reaches
            # the kernel, which emits null for hidden rows WITHOUT
            # decoding them — so garbage under another redefine arm can
            # neither leak values nor trip the decimal exactness bail
            entries.append((c, pa_type, desc, relevant))
        if not entries:
            return {}
        fc = self.fc
        tok = fc.begin() if fc is not None else None
        arrays = self._assemble_scalar_entries(entries)
        if tok is not None:
            plan = self.decoder.plan
            # coarse per-pass timing split by bytes touched, taken in
            # Python around the GIL-released native call — explain's
            # assemble plane keeps seeing native assembly. The kernel
            # label keeps the per-codec family (the explain table's
            # "which kernel decodes this field" contract). Columns the
            # pass could NOT serve (decimal ok=False) are excluded:
            # their fallback rebuild re-times itself, and charging them
            # here too would double-count (the fieldcost discard rule)
            served = [c for c, _, _, _ in entries if c.index in arrays]
            if served:
                fc.commit_weighted(
                    tok,
                    [((plan.cost_name(c),), c.width, self.n * c.width,
                      f"{c.codec.value}/w{c.width}") for c in served],
                    fieldcost.PLANE_ASSEMBLE, self.n)
            else:
                fc.discard(tok)
        return arrays

    def _assemble_scalar_entries(self, entries) -> dict:
        pa = _pa()
        n = self.n
        k = len(entries)
        bufs, valids = [], []
        out_ptrs = np.empty(k, dtype=np.uintp)
        out_strides = np.empty(k, dtype=np.int64)
        valid_ptrs = np.empty(k, dtype=np.uintp)
        valid_strides = np.ones(k, dtype=np.int64)
        for j, (c, pa_type, d, _m) in enumerate(entries):
            out_kind = d[3]
            if out_kind == native.ASM_OUT_DECIMAL128:
                buf = np.empty((n, 16), dtype=np.uint8)
            else:
                buf = np.empty(n, dtype=native.ASM_OUT_DTYPE[out_kind])
            valid = np.empty(n, dtype=np.uint8)
            bufs.append(buf)
            valids.append(valid)
            out_ptrs[j] = buf.ctypes.data
            out_strides[j] = native.ASM_OUT_ITEMSIZE[out_kind]
            valid_ptrs[j] = valid.ctypes.data
        masks = [m for _, _, _, m in entries]
        ok = self._asm_call([c for c, _, _, _ in entries],
                            [d for _, _, d, _ in entries],
                            out_ptrs, out_strides, valid_ptrs,
                            valid_strides,
                            row_masks=(masks if any(m is not None
                                                    for m in masks)
                                       else None))
        if ok is None:
            return {}
        result = {}
        for j, (c, pa_type, d, _m) in enumerate(entries):
            if not ok[j]:
                continue  # exact-Decimal fallback rebuilds this column
            packed = native.pack_validity(valids[j])
            if packed is None:
                break
            bitmap, nulls = packed
            vbuf = None if nulls == 0 else pa.py_buffer(bitmap)
            result[c.index] = pa.Array.from_buffers(
                pa_type, n, [vbuf, pa.py_buffer(bufs[j])],
                null_count=nulls)
        return result

    def _native_flat_values(self, st, cols, spec0, pa_type, max_size: int,
                            row_mask=None, compact_rows=None):
        """Record-major flat values array for ALL slots of one OCCURS
        numeric leaf via the fused kernel: every slot column writes into
        one shared buffer (slot s of row i at i*S+s) with one shared
        validity plane — the per-slot stack/astype/pack glue disappears.
        `row_mask`: decode-once row visibility for the owning segment
        (hidden rows emit null in-kernel, never decoded). `compact_rows`:
        decode ONLY these visible rows into a len(rows)*S values array —
        the caller gives hidden rows empty lists under their null parent
        struct, so the kernel never touches (or sizes buffers for) them.
        None -> caller's existing paths."""
        batch = self.batch
        if not native.available():
            return None
        outm = batch._out
        for c in cols:
            o = outm.get(c)
            if o is None or "lazy_numeric" not in o:
                return None  # planes exist: _plane_flat_values serves them
        key = (id(st), cols[0], compact_rows is None)
        cached = batch._asm_flat_cache.get(key)
        if cached is not None:
            return cached
        desc = _asm_descriptor(spec0, pa_type)
        if desc is None:
            return None
        pa = _pa()
        n = self.n if compact_rows is None else len(compact_rows)
        total = n * max_size
        out_kind = desc[3]
        item = native.ASM_OUT_ITEMSIZE[out_kind]
        if out_kind == native.ASM_OUT_DECIMAL128:
            flat = np.empty((total, 16), dtype=np.uint8)
        else:
            flat = np.empty(total, dtype=native.ASM_OUT_DTYPE[out_kind])
        valid = np.empty(total, dtype=np.uint8)
        k = len(cols)
        base = int(flat.ctypes.data)
        vbase = int(valid.ctypes.data)
        out_ptrs = np.fromiter((base + j * item for j in range(k)),
                               np.uintp, k)
        out_strides = np.full(k, max_size * item, dtype=np.int64)
        valid_ptrs = np.fromiter((vbase + j for j in range(k)),
                                 np.uintp, k)
        valid_strides = np.full(k, max_size, dtype=np.int64)
        specs = [self.decoder.plan.columns[c] for c in cols]
        fc = self.fc
        tok = fc.begin() if fc is not None else None
        ok = self._asm_call(specs, [desc] * k, out_ptrs, out_strides,
                            valid_ptrs, valid_strides,
                            row_masks=([row_mask] * k
                                       if row_mask is not None else None),
                            rows=compact_rows)
        arr = None
        if ok is not None and bool(ok.all()):
            packed = native.pack_validity(valid)
            if packed is not None:
                bitmap, nulls = packed
                vb = None if nulls == 0 else pa.py_buffer(bitmap)
                arr = pa.Array.from_buffers(
                    pa_type, total, [vb, pa.py_buffer(flat)],
                    null_count=nulls)
        if tok is not None:
            if arr is not None:
                fc.commit(tok, (self.decoder.plan.cost_name(spec0),),
                          fieldcost.PLANE_ASSEMBLE, n * spec0.width * k,
                          n * k, f"{spec0.codec.value}/w{spec0.width}")
            else:
                # failed fused attempt: the fallback path re-times this
                # plane; charging both would double-count it
                fc.discard(tok)
        if arr is not None:
            batch._asm_flat_cache[key] = arr
        return arr

    def _leaf_array(self, st: Primitive, slot_path):
        pa = _pa()
        pa_type = to_arrow_type(primitive_data_type(st))
        col = self.decoder.slot_map.get((id(st), slot_path))
        if col is None:
            return pa.nulls(self.n, type=pa_type)
        spec = self.decoder.plan.columns[col]
        if slot_path:
            # a slot of an OCCURS element: the enclosing `assemble.list`
            # stage covers it (exp3's per-slot route comes here 4,000
            # times a batch)
            return self._timed_leaf(st, col, spec, pa_type)
        name = ("assemble.string" if spec.codec in _STRING_CODECS
                else "assemble.scalar")
        with Stage(name, self.stats):
            return self._timed_leaf(st, col, spec, pa_type)

    def _timed_leaf(self, st: Primitive, col: int, spec, pa_type):
        fc = self.fc
        if fc is None:
            return self._leaf_array_impl(st, col, spec, pa_type)
        tok = fc.begin()
        arr = self._leaf_array_impl(st, col, spec, pa_type)
        # seconds only: the field's bytes/values were already counted by
        # the decode (or string-transcode) call that produced the planes
        fc.commit(tok, (self.decoder.plan.cost_name(spec),),
                  fieldcost.PLANE_ASSEMBLE, 0, 0)
        return arr

    def _leaf_array_impl(self, st: Primitive, col: int, spec, pa_type):
        pa = _pa()
        # rows where this column is visible: in a decode-once batch a
        # redefine-gated column only matters where its segment is active
        # (elsewhere the parent struct is null and the decoded bytes are
        # garbage by design)
        relevant = None
        if self.redefine_masks is not None and spec.segment:
            relevant = self.redefine_masks.get(spec.segment.upper())
        lengths = self.batch.lengths
        if lengths is not None:
            trunc = lengths < spec.offset + spec.width
            if relevant is not None:
                trunc = trunc & relevant
            if bool(trunc.any()):
                # truncated variable-length tails: the scalar path owns
                # the partial-field rules
                return self._python_fallback(col, pa_type, relevant)
        if spec.codec not in _STRING_CODECS:
            # fused one-pass native assembly: deferred numeric columns
            # decode straight into this column's Arrow buffers
            arr = self._native_scalar_array(col)
            if arr is not None:
                return arr
        if spec.codec in _STRING_CODECS:
            # one-pass native transcode+trim straight into Arrow buffers
            # (no code-point matrix, no Arrow trim kernel)
            bufs = self.batch.string_arrow_buffers(
                spec, relevant_of=self._relevant_of)
            if bufs is not None:
                offsets, data = bufs
                return pa.Array.from_buffers(
                    pa.string(), self.n,
                    [None, pa.py_buffer(offsets), pa.py_buffer(data)])
        out = self.batch.column_arrays(col)
        if "host" in out:
            return self._python_fallback(col, pa_type, relevant)
        if "values_hi" in out:
            # wide uint128-limb columns: native decimal128 build from the
            # limbs (one batched call per kernel group when possible);
            # exact-Decimal fallback when any value needs rounding or
            # outruns the declared precision
            arr = self._decimal_group_array(spec, pa_type)
            if arr is None:
                arr = self._decimal128_native(spec, out, pa_type, relevant,
                                              wide=True)
            if arr is not None:
                return arr
            return self._python_fallback(col, pa_type, relevant)
        if spec.codec in _STRING_CODECS:
            return self._string_array(spec, out, pa_type, relevant)
        if spec.codec in _FLOAT_CODECS:
            values = np.asarray(out["values"])
            valid = np.asarray(out["valid"])
            return pa.array(
                values.astype(_numpy_dtype_for(pa_type), copy=False),
                mask=~valid if not valid.all() else None)
        # fixed-point
        values = np.asarray(out["values"])
        valid = np.asarray(out["valid"])
        mask = None if valid.all() else ~valid
        if pa.types.is_integer(pa_type):
            return pa.array(
                values.astype(_numpy_dtype_for(pa_type), copy=False),
                mask=mask)
        if pa.types.is_decimal(pa_type):
            arr = self._decimal_group_array(spec, pa_type)
            if arr is not None:
                return arr
            if pa_type.precision > 18:
                # int64 mantissa widened into 128-bit limbs natively
                arr = self._decimal128_native(spec, out, pa_type, relevant,
                                              wide=False)
                if arr is not None:
                    return arr
                return self._python_fallback(col, pa_type, relevant)
            mantissa = values.astype(np.int64, copy=False)
            if spec.params.explicit_decimal or _dyn_scale(spec):
                shift = pa_type.scale - np.asarray(out["dot_scale"],
                                                   dtype=np.int64)
                shift = np.broadcast_to(shift, mantissa.shape)
                if relevant is not None:
                    # garbage dot-scale planes in hidden rows must neither
                    # force the fallback nor feed negative powers below
                    shift = np.where(relevant, shift, 0)
                if np.any((shift < 0) | (shift > 18)):
                    return self._python_fallback(col, pa_type, relevant)
            else:
                shift = _static_decimal_shift(spec, pa_type)
                if shift is None:
                    return self._python_fallback(col, pa_type, relevant)
            mantissa = mantissa * 10 ** shift
            return _decimal128_from_mantissa(mantissa, valid, pa_type)
        return self._python_fallback(col, pa_type, relevant)

    def _decimal_group_array(self, spec, pa_type):
        """Per-column decimal128 array served from ONE native build per
        kernel group (native.decimal128_batch): the group's column planes
        are stacked and shifted/packed in a single call, replacing
        per-column wrapper calls + strided copies — the dominant GIL-held
        assembly cost on decimal-heavy profiles, and what lets pipeline
        workers overlap instead of serializing on the interpreter. None ->
        caller's per-column paths (masked rows, host fallback, ok=0
        exact-fallback columns, native library unavailable)."""
        from .. import native

        if not native.available():
            return None
        if self._relevant_of(spec) is not None:
            return None
        g = self.decoder.group_of_col.get(spec.index)
        if g is None or len(g.columns) < 2:
            return None  # single column: the per-column kernel is enough
        cache = self.batch._arrow_dec_cache
        entry = cache.get(id(g))
        if entry is None:
            entry = self._build_decimal_group(g)
            cache[id(g)] = entry
        return entry.get(spec.index)

    def _build_decimal_group(self, g) -> dict:
        """{col index -> pa.Array | None} for every decimal-typed column
        of one kernel group, via one decimal128_batch call."""
        fc = self.fc
        with Stage("assemble.decimal", self.stats):
            if fc is None:
                return self._build_decimal_group_impl(g)
            tok = fc.begin()
            entry = self._build_decimal_group_impl(g)
            plan = self.decoder.plan
            names = tuple(plan.cost_name(c) for c in g.columns
                          if c.index in entry) or g.names
            fc.commit(tok, names, fieldcost.PLANE_ASSEMBLE, 0, 0, g.label)
            return entry

    def _build_decimal_group_impl(self, g) -> dict:
        from .. import native

        pa = _pa()
        entry: dict = {}
        chosen = []
        for c in g.columns:
            if c.statement is None:
                continue
            if self.redefine_masks is not None and c.segment:
                continue  # masked columns keep the per-column path
            pa_t = to_arrow_type(primitive_data_type(c.statement))
            if not pa.types.is_decimal(pa_t):
                continue
            out = self.batch._out.get(c.index)
            if out is None or "values" not in out or "host" in out:
                continue
            use_dots = bool(c.params.explicit_decimal or _dyn_scale(c))
            if use_dots and "dot_scale" not in out:
                continue
            chosen.append((c, pa_t, out, use_dots))
        if not chosen:
            return entry
        n = self.n
        k = len(chosen)
        wide = "values_hi" in chosen[0][2]
        valid = np.stack([np.asarray(o["valid"])
                          for _, _, o, _ in chosen]).astype(np.uint8,
                                                            copy=False)
        if wide:
            hi = np.stack([np.asarray(o["values_hi"], dtype=np.uint64)
                           for _, _, o, _ in chosen])
            lo = np.stack([np.asarray(o["values"], dtype=np.uint64)
                           for _, _, o, _ in chosen])
            neg = np.stack([np.asarray(o["negative"])
                            for _, _, o, _ in chosen]).astype(np.uint8,
                                                              copy=False)
            values = None
        else:
            hi = lo = neg = None
            values = np.stack([np.asarray(o["values"])
                               for _, _, o, _ in chosen]).astype(
                np.int64, copy=False)
        use_dots_arr = np.asarray([ud for _, _, _, ud in chosen],
                                  dtype=np.uint8)
        dots = None
        if use_dots_arr.any():
            dots = np.zeros((k, n), dtype=np.int64)
            for j, (_, _, o, ud) in enumerate(chosen):
                if ud:
                    dots[j] = np.asarray(o["dot_scale"], dtype=np.int64)
        shifts = np.asarray(
            [pa_t.scale if ud
             else pa_t.scale + fixed_point_exponent(c)
             for c, pa_t, _, ud in chosen], dtype=np.int64)
        # precision bounds mirror the per-column paths exactly: wide limbs
        # and >18-digit narrow columns went through the native kernel with
        # max_digits=precision (overflow -> exact fallback, which
        # surfaces it); only the <=18 narrow numpy-mantissa path never
        # bounded, so maxd=0 keeps that behavior there
        maxd = np.asarray(
            [pa_t.precision if (wide or pa_t.precision > 18) else 0
             for _, pa_t, _, _ in chosen], dtype=np.int32)
        res = native.decimal128_batch(hi, lo, values, neg, valid, dots,
                                      use_dots_arr, shifts, maxd)
        if res is None:
            return entry
        data, ok = res
        for j, (c, pa_t, _, _) in enumerate(chosen):
            if not ok[j]:
                entry[c.index] = None
                continue
            vcol = valid[j].view(bool)
            vbuf = None if vcol.all() else _validity_buffer(vcol)
            entry[c.index] = pa.Array.from_buffers(
                pa_t, n, [vbuf, pa.py_buffer(data[j])])
        return entry

    def _decimal128_native(self, spec, out, pa_type, relevant, wide: bool):
        """decimal128 buffers straight from the kernel outputs via the
        native 128-bit shift-and-pack; None -> caller falls back to exact
        Decimal materialization."""
        from .. import native

        pa = _pa()
        if not native.available() or not pa.types.is_decimal(pa_type):
            return None
        valid = np.asarray(out["valid"])
        if relevant is not None:
            valid = valid & relevant
        if wide:
            hi = np.asarray(out["values_hi"])
            lo = np.asarray(out["values"])
            neg = np.asarray(out["negative"])
        else:
            v = np.asarray(out["values"]).astype(np.int64, copy=False)
            neg = v < 0
            # |INT64_MIN| wraps under int64 abs; the uint64 view of the
            # wrapped value is the correct 2^63 magnitude
            lo = np.abs(v).view(np.uint64)
            hi = np.zeros_like(lo)
        if spec.params.explicit_decimal or _dyn_scale(spec):
            shifts = pa_type.scale - np.asarray(out["dot_scale"],
                                                dtype=np.int64)
        else:
            shifts = np.int64(pa_type.scale + fixed_point_exponent(spec))
        res = native.decimal128_from_limbs(hi, lo, neg, valid, shifts,
                                           max_digits=pa_type.precision)
        if res is None:
            return None
        data, ok = res
        if not bool(ok.all()):
            return None
        vbuf = None if valid.all() else _validity_buffer(valid)
        return pa.Array.from_buffers(pa_type, len(valid),
                                     [vbuf, pa.py_buffer(data)])

    def _string_array(self, spec, out, pa_type, relevant=None):
        pa = _pa()
        if not self.batch._vectorizable_string(spec):
            # UTF-16 / HEX / RAW / custom charsets: per-value host decode
            return self._python_fallback(spec.index, pa_type, relevant)
        mat = out["bytes"]
        if mat.ndim != 2 or mat.shape[1] == 0:
            return pa.array([""] * self.n, type=pa_type)
        if relevant is not None and not relevant.all():
            # hidden rows' garbage code points must not poison the
            # column (their >0x7F values would truncate to invalid
            # UTF-8 below) — blank them; the null parent struct hides
            # whatever value they produce
            mat = mat.copy()
            mat[~relevant] = 0x20
        if bool((mat > 0x7F).any()):
            # non-ASCII code points need real UTF-8 encoding, 8-bit ones
            # (Latin-1 from cp037, cp500, cp1047) as much as 16-bit ones
            return self._python_fallback(spec.index, pa_type, relevant)
        return _string_from_codepoints(mat, self.decoder.plan.trimming)

    # -- arrays / groups ---------------------------------------------------

    def _occurs_counts(self, st: Statement) -> Optional[np.ndarray]:
        """Per-record element counts, or None when constant max size."""
        if st.depending_on is None:
            return None
        dep_col = self.decoder.dependee_columns.get(st.depending_on)
        if dep_col is None:
            return None
        values = self.batch.column_values(dep_col)
        if st.depending_on_handlers or any(
                not isinstance(v, (int, np.integer)) for v in values):
            return np.asarray([_resolve_occurs(st, v) for v in values],
                              dtype=np.int64)
        v = np.asarray(values, dtype=np.int64)
        return np.where((v >= st.array_min_size) & (v <= st.array_max_size),
                        v, st.array_max_size)

    def _slots_truncated(self, cols, counts, relevant=None) -> bool:
        """Whether a row that shows the slot columns `cols` of one OCCURS
        leaf ends before the last slot it shows: its first `counts`
        slots (all of them where `counts` is None). A record that is
        short by its own count alone, as every record of a file of
        variable-size OCCURS is, is whole."""
        lengths = self.batch.lengths
        if lengths is None:
            return False
        columns = self.decoder.plan.columns
        ends = np.asarray([0] + [columns[c].offset + columns[c].width
                                 for c in cols], dtype=np.int64)
        trunc = lengths < (ends[-1] if counts is None else ends[counts])
        if relevant is not None:
            trunc = trunc & relevant
        return bool(trunc.any())

    def _flat_slot_values(self, st: Primitive, slot_path, max_size: int,
                          compact_mask=None, compact_rows=None,
                          counts=None):
        """One record-major flat array covering every OCCURS slot of a
        numeric leaf (the slots live in one kernel group; per-slot
        pa.array calls would dominate wide-OCCURS materialization —
        exp3's 2000-element plane is 4000 such calls otherwise). One
        algorithm fed from two sources: bytes not yet decoded go through
        the fused native kernel (`_native_flat_values`, the host
        backends), decoded planes give their group matrix's rows
        (`_plane_flat_values`, the device backends); both serve the
        compact and the positional shape.
        `compact_mask`/`compact_rows` (decode-once): build values for
        ONLY the visible rows — the caller verified hidden rows are
        nulled at an enclosing struct, where child buffers are invisible.
        `counts`: the elements each row shows (DEPENDING ON; None: all).
        None -> caller uses the per-slot path (strings outside a struct
        element, wide or dynamic-scale decimals, a row that ends inside
        an element it shows, a leaf of another segment arm, slots
        without a shared plane)."""
        pa = _pa()
        pa_type = to_arrow_type(primitive_data_type(st))
        is_decimal = pa.types.is_decimal(pa_type)
        if not (pa.types.is_integer(pa_type) or pa.types.is_floating(pa_type)
                or is_decimal):
            return None
        cols = [self.decoder.slot_map.get((id(st), slot_path + (k,)))
                for k in range(max_size)]
        if any(c is None for c in cols):
            return None
        spec0 = self.decoder.plan.columns[cols[0]]
        relevant = self._relevant_of(spec0)
        if compact_mask is not None and relevant is not compact_mask:
            return None  # leaf belongs to a different segment arm
        if is_decimal and (spec0.params.explicit_decimal
                           or _dyn_scale(spec0)):
            return None  # per-value exponent planes stay per slot
        if self._slots_truncated(cols, counts, relevant):
            return None  # truncated tails own the partial-field rules
        # bytes not yet decoded (the host backends defer): one fused
        # native pass from the record image into the flat buffers
        arr = self._native_flat_values(
            st, cols, spec0, pa_type, max_size,
            row_mask=None if compact_rows is not None else relevant,
            compact_rows=compact_rows)
        if arr is None:
            # already decoded (a device backend's planes, or a deferred
            # group the fused pass declined): the group matrix's own rows
            arr = self._plane_flat_values(cols, spec0, pa_type, relevant,
                                          compact_rows)
        return arr

    def _slot_plane(self, cols, compact_mask=None):
        """(values matrix, valid matrix, column slice, whether the
        matrices hold the rows of `compact_mask` alone) when the slot
        columns `cols` of one OCCURS leaf, in slot order, are evenly
        spaced columns of ONE decoded group matrix (`plane` of
        columnar._store_numeric: consecutive where the leaf has the
        group's columns to itself within an element, strided where
        sibling leaves of the same kernel group sit between its slots).
        A group that the device decoded for the rows of `compact_mask`
        alone hands its subset matrices over unscattered
        (DecodedBatch.plane_of). None where a slot has no such plane:
        wide limbs, host-fallback values, strings."""
        found = [self.batch.plane_of(c, compact_mask) for c in cols]
        planes = [p for p, _ in found]
        if any(p is None for p in planes):
            return None
        subset = found[0][1]
        values, valid, p0 = planes[0]
        step = planes[1][2] - p0 if len(planes) > 1 else 1
        stop = p0 + len(planes) * step
        if (step < 1 or any(p[0] is not values for p in planes)
                or [p[2] for p in planes] != list(range(p0, stop, step))):
            return None
        return values, valid, slice(p0, stop, step), subset

    def _plane_flat_values(self, cols, spec0, pa_type, relevant,
                           compact_rows):
        """Record-major flat values array for all slots of one OCCURS
        numeric leaf whose columns are ALREADY decoded into one group
        matrix [n, ncols] (every device-backend batch): the matrix's rows
        are the record-major order a list's values want, so the slots'
        columns are sliced out of it in one piece — no per-slot arrays,
        no stack, no interleaving take. `compact_rows` (the rows of
        `relevant`): one ascending row gather keeps only the visible
        rows (the caller gives hidden rows empty lists), and no gather
        at all where the device decoded the group for those rows alone:
        the subset matrix is the visible rows. Otherwise positional, and
        `relevant` (rows
        that cannot be dropped) nulls the hidden rows' slots, as the
        fused native pass does. The matrix may have any strides: the
        host kernels' and XLA:CPU's are row-major, a TPU hands its
        planes back column-major (the program's output layout), where
        the one copy below is a transposing one. None -> the per-slot
        path (no shared plane, or a decimal wider than an exact int64
        mantissa)."""
        pa = _pa()
        is_decimal = pa.types.is_decimal(pa_type)
        if is_decimal and pa_type.precision > 18:
            return None
        shift = _static_decimal_shift(spec0, pa_type) if is_decimal else 0
        if shift is None:
            return None
        plane = self._slot_plane(
            cols, relevant if compact_rows is not None else None)
        if plane is None:
            return None
        values, valid, slots, subset = plane
        values, valid = values[:, slots], valid[:, slots]
        if compact_rows is not None:
            # a subset matrix's rows are `compact_rows`, in their order
            if not subset:
                values, valid = values[compact_rows], valid[compact_rows]
        elif relevant is not None:
            valid = valid & relevant[:, None]
        # one copy into record-major order; none where every row stays
        # and the leaf's slots are the whole of a row-major matrix. The
        # validity plane is copied only where something is null: `all`
        # reads it in whatever order it lies
        flat = _record_major(values)
        fvalid = None if valid.all() else _record_major(valid)
        if self.batch.pass_counts is not None:
            self.batch.pass_counts.incr("plane_list")
        if is_decimal:
            mantissa = flat.astype(np.int64, copy=False) * 10 ** shift
            return _decimal128_from_mantissa(mantissa, fvalid, pa_type)
        flat = flat.astype(_numpy_dtype_for(pa_type), copy=False)
        vbuf, nulls = _packed_validity(fvalid)
        return pa.Array.from_buffers(
            pa_type, len(flat), [vbuf, pa.py_buffer(flat)],
            null_count=nulls)

    def _flat_string_values(self, st: Primitive, slot_path, max_size: int,
                            counts, keep):
        """Record-major string values of all OCCURS slots of one string
        leaf, from the code points of its kernel group seen as
        [rows x max, width] (the slots are columns of one group matrix),
        cut to the rows of `keep` (flat bool over rows x max; None:
        all) before any string is built. None -> the per-slot path (a
        codec without a code-point matrix, code points past ASCII, a row
        that ends inside an element it shows)."""
        batch = self.batch
        cols = [self.decoder.slot_map.get((id(st), slot_path + (k,)))
                for k in range(max_size)]
        if any(c is None for c in cols):
            return None
        spec0 = self.decoder.plan.columns[cols[0]]
        if not batch._vectorizable_string(spec0) or not spec0.width \
                or self._relevant_of(spec0) is not None \
                or self._slots_truncated(cols, counts):
            return None
        planes = [batch.column_arrays(c).get("char_plane") for c in cols]
        if any(p is None for p in planes):
            return None
        # the slots as evenly spaced runs of one [n, k] matrix of code
        # points (a kernel group's own, or a device program's)
        chars, p0 = planes[0]
        width = spec0.width
        step = planes[1][1] - p0 if len(planes) > 1 else width
        stop = p0 + len(planes) * step
        if (step < width or any(p[0] is not chars for p in planes)
                or [p[1] for p in planes] != list(range(p0, stop, step))):
            return None
        row, item = chars.strides
        slots = np.lib.stride_tricks.as_strided(
            chars[:, p0:], (self.n, max_size, width),
            (row, step * item, item), writeable=False)
        # one copy either way: the elements kept, or all of them
        mat = (slots.reshape(self.n * max_size, width) if keep is None
               else slots[keep.reshape(self.n, max_size)])
        if bool((mat > 0x7F).any()):
            return None  # real UTF-8 encoding: per value
        return _string_from_codepoints(mat, self.decoder.plan.trimming)

    def _flat_struct_values(self, group: Group, slot_path, max_size: int,
                            compact_mask=None, compact_rows=None,
                            counts=None):
        """Record-major flat StructArray over all OCCURS slots of a group
        element whose fields are numeric leaves (exp3's STRATEGY-DETAIL)
        and, where no row is hidden by a redefine, strings: each leaf one
        record-major array of rows x max values. `counts` (DEPENDING
        ON): the struct holds only the elements each row shows, in
        record order. None -> per-slot path (nested groups or arrays in
        the element, and what its leaves' own routes decline)."""
        pa = _pa()
        keep = indices = None
        if counts is not None:
            mask = np.arange(max_size)[None, :] < counts[:, None]
            if not bool(mask.all()):
                keep = mask.reshape(-1)
                indices = pa.array(np.flatnonzero(keep))
        names, children = [], []
        strings = 0
        for child in group.children:
            if child.is_filler:
                continue
            if isinstance(child, Group) or child.is_array:
                return None
            if isinstance(child.dtype, AlphaNumeric):
                if compact_mask is not None:
                    return None
                flat = self._flat_string_values(child, slot_path, max_size,
                                                counts, keep)
                strings += 1
            else:
                flat = self._flat_slot_values(child, slot_path, max_size,
                                              compact_mask=compact_mask,
                                              compact_rows=compact_rows,
                                              counts=counts)
                if flat is not None and indices is not None:
                    # one ascending gather: a sequential copy
                    flat = flat.take(indices)
            if flat is None:
                return None
            names.append(child.name)
            children.append(flat)
        if not children:
            return None
        if (strings or counts is not None) \
                and self.batch.pass_counts is not None:
            self.batch.pass_counts.incr("struct_list")
        return pa.StructArray.from_arrays(children, names=names)

    def _list_array(self, st: Statement, slot_path):
        """OCCURS -> ListArray, one per batch under `assemble.list`."""
        fc = self.fc
        with Stage("assemble.list", self.stats):
            if fc is None:
                return self._list_array_impl(st, slot_path)
            tok = fc.begin()
            arr = self._list_array_impl(st, slot_path)
            # list glue (offsets, interleave take) charged to the array
            # field itself; element builds are nested regions with their
            # own charges — the OCCURS slots share the statement name, so
            # the whole array still reads as one cost row
            cols = self.decoder.plan.columns_for(st)
            name = (self.decoder.plan.cost_name(cols[0]) if cols
                    else st.name)
            fc.commit(tok, (name,), fieldcost.PLANE_ASSEMBLE, 0, 0)
            return arr

    def _subtree_planned(self, st: Statement) -> bool:
        """True when any leaf under `st` has a compiled column. False
        means the whole subtree was pruned by the projection and its
        output is pure nulls — buildable without walking slots."""
        planned = getattr(self.decoder, "planned_statement_ids", None)
        if planned is None:
            return True
        if id(st) in planned:
            return True
        if isinstance(st, Group):
            return any(self._subtree_planned(c) for c in st.children)
        return False

    def _flat_null_values(self, st: Statement, max_size: int):
        """Record-major all-null values array for a PRUNED constant-size
        OCCURS subtree (primitive elements, or a group of primitive
        non-array children) — shape-identical to what the per-slot walk
        would build (valid structs, null leaves), at O(fields) cost
        instead of O(slots). None -> caller takes the slow exact path."""
        pa = _pa()
        total = self.n * max_size
        if not isinstance(st, Group):
            return pa.nulls(total,
                            type=to_arrow_type(primitive_data_type(st)))
        names, children = [], []
        for child in st.children:
            if child.is_filler:
                continue
            if isinstance(child, Group) or child.is_array:
                return None
            names.append(child.name)
            children.append(pa.nulls(
                total, type=to_arrow_type(primitive_data_type(child))))
        if not children:
            return None
        return pa.StructArray.from_arrays(children, names=names)

    def _compact_visibility(self, st: Statement):
        """Row mask under which `st` (a decode-once OCCURS subtree) is
        visible, IF the hidden rows are guaranteed nulled at an enclosing
        segment-redefine struct: there, child buffers are logically
        invisible, so values need building only for the visible rows
        (exp3: the 2000-slot STRATEGY plane shrinks from every record to
        just the C records). None = no mask, or no null-struct
        guarantee — callers must then build positionally."""
        if self.redefine_masks is None:
            return None
        node, redef = st.parent, None
        while node is not None:
            if getattr(node, "is_segment_redefine", False):
                redef = node
                break
            node = node.parent
        # the redefine root only receives its struct null mask when it is
        # built as a row-level struct: itself not an array, and not
        # nested inside one (element structs are built unmasked)
        if redef is None or redef.is_array:
            return None
        p = redef.parent
        while p is not None:
            if p.is_array:
                return None
            p = p.parent
        mask = self.redefine_masks.get(redef.name.upper())
        if mask is None or bool(mask.all()):
            return None  # fully visible: the positional path IS compact
        return mask

    def _list_array_impl(self, st: Statement, slot_path):
        pa = _pa()
        n, max_size = self.n, st.array_max_size
        counts_probe = self._occurs_counts(st)
        if n and max_size and n * max_size < 2**31 - 1:
            # position-addressed assembly: ONE flat record-major values
            # array (slot s of record i at i*S+s), built by the fused
            # native kernel from bytes not yet decoded, and from the
            # decoded group matrix's own rows otherwise (a device
            # backend's planes) — never the slot-major concat +
            # random-access take interleave below
            flat = None
            if not self._subtree_planned(st):
                # projection pruned the whole plane: zero assembly —
                # the pushdown claim that an unselected wide OCCURS
                # (exp3's 2000-element STRATEGY) costs nothing
                flat = self._flat_null_values(st, max_size)
            if flat is None and counts_probe is None:
                # decode-once + segment mask: values for visible rows
                # only; hidden rows get EMPTY lists, invisible under
                # their null redefine struct (Arrow equality and every
                # consumer read nulls logically)
                cmask = self._compact_visibility(st)
                if cmask is not None:
                    rows = np.nonzero(cmask)[0]
                    cflat = (self._flat_struct_values(
                                 st, slot_path, max_size,
                                 compact_mask=cmask, compact_rows=rows)
                             if isinstance(st, Group)
                             else self._flat_slot_values(
                                 st, slot_path, max_size,
                                 compact_mask=cmask, compact_rows=rows))
                    if cflat is not None:
                        offsets = np.zeros(n + 1, dtype=np.int32)
                        np.cumsum(np.where(cmask, max_size, 0),
                                  out=offsets[1:])
                        return pa.ListArray.from_arrays(pa.array(offsets),
                                                        cflat)
            if flat is None and isinstance(st, Group):
                flat = self._flat_struct_values(st, slot_path, max_size,
                                                counts=counts_probe)
                if flat is not None and counts_probe is not None:
                    # the struct's leaves came cut to the elements each
                    # row shows
                    offsets = np.zeros(n + 1, dtype=np.int32)
                    np.cumsum(counts_probe, out=offsets[1:])
                    return pa.ListArray.from_arrays(pa.array(offsets),
                                                    flat)
            elif flat is None:
                flat = self._flat_slot_values(st, slot_path, max_size,
                                              counts=counts_probe)
            if flat is not None:
                if counts_probe is None:
                    # constant-size OCCURS: uniform offsets, zero copies
                    offsets = np.arange(n + 1, dtype=np.int32) * max_size
                    return pa.ListArray.from_arrays(pa.array(offsets),
                                                    flat)
                # DEPENDING ON: drop the unused tail slots with one
                # ASCENDING-index gather over the record-major array (a
                # sequential copy, not the interleave the slot-major
                # shape forced); no gather at all when every record is
                # full
                counts = counts_probe
                mask = np.arange(max_size)[None, :] < counts[:, None]
                if bool(mask.all()):
                    values = flat
                else:
                    indices = (np.arange(n, dtype=np.int64)[:, None]
                               * max_size
                               + np.arange(max_size,
                                           dtype=np.int64)[None, :])[mask]
                    values = flat.take(pa.array(indices))
                offsets = np.zeros(n + 1, dtype=np.int32)
                np.cumsum(counts, out=offsets[1:])
                return pa.ListArray.from_arrays(pa.array(offsets), values)
        # what only the slots can do: an OCCURS of strings, nested groups
        # or arrays in the element, wide or dynamic-scale decimals, a row
        # that ends inside an element it shows, host-fallback columns
        with Stage("assemble.list.slots", self.stats):
            return self._slot_major_list(st, slot_path, counts_probe)

    def _slot_major_list(self, st: Statement, slot_path, counts):
        """One array per slot, concatenated slot-major and interleaved
        back into record order by one take."""
        pa = _pa()
        n, max_size = self.n, st.array_max_size
        elems = [self._statement_array(st, slot_path + (k,), as_element=True)
                 for k in range(max_size)]
        if n == 0 or max_size == 0:
            value_type = (elems[0].type if elems
                          else to_arrow_type(self._element_schema_type(st)))
            return pa.ListArray.from_arrays(
                pa.array(np.zeros(n + 1, dtype=np.int32)),
                pa.nulls(0, type=value_type))
        # element k of record i sits at position k*n + i of the concatenation
        idx = (np.arange(max_size)[None, :] * n
               + np.arange(n)[:, None])
        if counts is None:
            lengths = np.full(n, max_size, dtype=np.int64)
            indices = idx.ravel()
        else:
            mask = np.arange(max_size)[None, :] < counts[:, None]
            lengths = counts
            indices = idx[mask]
        values = pa.concat_arrays(elems).take(indices)
        offsets = np.zeros(n + 1, dtype=np.int32)
        np.cumsum(lengths, out=offsets[1:])
        return pa.ListArray.from_arrays(pa.array(offsets), values)

    def _element_schema_type(self, st: Statement):
        if isinstance(st, Group):
            return StructType(self._group_fields(st))
        return primitive_data_type(st)

    def _group_fields(self, group: Group) -> List[Field]:
        fields = []
        for child in group.children:
            if child.is_filler:
                continue
            if isinstance(child, Group):
                if child.parent_segment is not None:
                    continue
                t = self._element_schema_type(child)
                fields.append(Field(
                    child.name, ArrayType(t) if child.is_array else t))
            else:
                t = primitive_data_type(child)
                fields.append(Field(
                    child.name, ArrayType(t) if child.is_array else t))
        return fields

    def _struct_array(self, group: Group, slot_path, null_mask=None):
        pa = _pa()
        names, children = [], []
        for child in group.children:
            if child.is_filler:
                continue
            if isinstance(child, Group) and child.parent_segment is not None:
                continue  # hierarchical child segments never reach this path
            names.append(child.name)
            children.append(self._statement_array(child, slot_path))
        if not children:
            return pa.nulls(self.n, type=pa.struct([]))
        return pa.StructArray.from_arrays(
            children, names=names,
            mask=None if null_mask is None else pa.array(null_mask))

    def _statement_array(self, st: Statement, slot_path,
                         as_element: bool = False):
        pa = _pa()
        if id(st) in self.nested:
            return self.nested[id(st)]
        if st.is_array and not as_element:
            return self._list_array(st, slot_path)
        if isinstance(st, Group):
            if st.is_segment_redefine and not as_element:
                if self.redefine_masks is not None:
                    mask = self.redefine_masks.get(st.name.upper())
                    if mask is None or not mask.any():
                        t = to_arrow_type(StructType(self._group_fields(st)))
                        return pa.nulls(self.n, type=t)
                    return self._struct_array(st, slot_path,
                                              null_mask=~mask)
                if (self.active is None
                        or st.name.upper() != self.active.upper()):
                    t = to_arrow_type(StructType(self._group_fields(st)))
                    return pa.nulls(self.n, type=t)
            return self._struct_array(st, slot_path)
        return self._leaf_array(st, slot_path)

    # -- top level ---------------------------------------------------------

    def body_columns(self, policy: SchemaRetentionPolicy):
        """(name, array) pairs for the record body, matching
        CobolOutputSchema._create_schema ordering."""
        out = []
        for root in self.decoder.copybook.ast.children:
            if not isinstance(root, Group):
                continue
            if policy is SchemaRetentionPolicy.COLLAPSE_ROOT:
                for child in root.children:
                    if child.is_filler:
                        continue
                    if isinstance(child, Group) and child.parent_segment is not None:
                        continue
                    out.append((child.name, self._statement_array(child, ())))
            else:
                out.append((root.name, self._statement_array(root, ())))
        return out


def segment_table(batch: DecodedBatch,
                  active: Optional[str],
                  output_schema,
                  file_id: int,
                  record_ids: Optional[np.ndarray],
                  seg_level_ids: Optional[Sequence[Sequence[object]]],
                  input_file_name: str = "",
                  redefine_masks: Optional[dict] = None,
                  corrupt_reasons: Optional[Sequence] = None,
                  nested: Optional[dict] = None):
    """One Arrow table for one decoded batch (single active segment, or a
    decode-once batch with per-row redefine masks), with generated columns
    prepended per the output schema. `corrupt_reasons`: per-row values of
    the trailing corrupt-record debug column (None entries = clean).
    `nested`: arrays the batch does not hold, by id(statement)."""
    pa = _pa()
    builder = ArrowBatchBuilder(batch, active, redefine_masks, nested)
    n = batch.n_records
    schema = output_schema.schema

    def seg_arrays():
        from .result import SegLevelColumns

        levels = output_schema.generate_seg_id_field_count
        if not levels:
            return []
        with Stage("assemble.seg_id", batch.stage_stats):
            out = []
            for lvl in range(levels):
                if isinstance(seg_level_ids, SegLevelColumns):
                    ab = seg_level_ids.arrow_level(lvl)
                    if ab is not None:
                        # native int-formatted Seg_Id buffers — no Python
                        # strings at all
                        offsets, data, valid = ab
                        vbuf = (None if valid.all()
                                else _validity_buffer(valid))
                        out.append(pa.Array.from_buffers(
                            pa.string(), n,
                            [vbuf, pa.py_buffer(offsets),
                             pa.py_buffer(data)]))
                        continue
                    # per-level object column straight into Arrow (no
                    # per-row list materialization)
                    vals = (seg_level_ids.levels[lvl]
                            if lvl < len(seg_level_ids.levels)
                            else [None] * n)
                elif seg_level_ids is not None:
                    vals = [row[lvl] if row is not None and lvl < len(row)
                            else None for row in seg_level_ids]
                else:
                    vals = [None] * n
                out.append(pa.array(vals, type=pa.string()))
        return out

    # Generated columns in ROW order (extractors._apply_post_processing /
    # reference RecordExtractors.applyRecordPostProcessing): with record
    # ids the file name goes before the Seg_Id levels; without, after.
    # The declared schema prepends the file-name field before the Seg_Id
    # fields in BOTH cases (CobolSchema.scala:99-103) — the reference binds
    # Spark Rows positionally, so that (reference) misalignment is parity;
    # columns here are therefore labeled positionally, exactly like rows.
    def file_name_col():
        # constant string column straight into Arrow buffers (native
        # memcpy fill) — never n Python string objects
        bufs = native.const_string_col(n, input_file_name)
        if bufs is not None:
            offsets, data = bufs
            return pa.Array.from_buffers(
                pa.string(), n,
                [None, pa.py_buffer(offsets), pa.py_buffer(data)])
        return pa.array([input_file_name] * n, type=pa.string())

    cols: List[object] = []
    if output_schema.generate_record_id:
        cols.append(pa.array(np.full(n, file_id, dtype=np.int32)))
        rids = (np.asarray(record_ids, dtype=np.int64) if record_ids is not None
                else np.arange(n, dtype=np.int64))
        cols.append(pa.array(rids))
        if output_schema.input_file_name_field:
            cols.append(file_name_col())
        cols.extend(seg_arrays())
    else:
        cols.extend(seg_arrays())
        if output_schema.input_file_name_field:
            cols.append(file_name_col())
    cols.extend(arr for _, arr in builder.body_columns(output_schema.policy))
    if getattr(output_schema, "corrupt_record_field", ""):
        cols.append(pa.nulls(n, pa.string()) if corrupt_reasons is None
                    else pa.array(list(corrupt_reasons), type=pa.string()))
    target = arrow_schema(schema)
    if len(cols) != len(target):
        raise ValueError(
            f"Arrow column count mismatch: built {len(cols)}, "
            f"schema {len(target)}")
    arrays = [c.cast(target.field(i).type)
              if c.type != target.field(i).type else c
              for i, c in enumerate(cols)]
    return pa.Table.from_arrays(arrays, schema=target)


def nested_list(batch: DecodedBatch, group: Group, counts: np.ndarray):
    """The list column of an array of variable arrays whose elements are
    the rows of `batch` (reader/element_rows.py), `counts` of them a
    record, in order: the element struct built once over every element,
    its own lists beneath it, wrapped by the counts' offsets."""
    pa = _pa()
    with Stage("assemble.list.nested", batch.stage_stats):
        structs = ArrowBatchBuilder(batch, None)._struct_array(group, ())
        offsets = np.zeros(len(counts) + 1, dtype=np.int32)
        np.cumsum(counts, out=offsets[1:])
        return pa.ListArray.from_arrays(pa.array(offsets), structs)


def rows_to_table(rows: List[List[object]], struct: StructType):
    """Fallback: build a typed table from materialized Python rows (host
    backend, hierarchical assemblies). Same declared types as the fast
    path, so both produce schema-identical tables."""
    pa = _pa()
    target = arrow_schema(struct)
    arrays = []
    for i, f in enumerate(struct.fields):
        col = [row[i] for row in rows]
        arrays.append(pa.array(_normalize_objects(col, f.dtype),
                               type=target.field(i).type))
    return pa.Table.from_arrays(arrays, schema=target)


def _normalize_objects(values, dtype):
    """Tuples (group values) -> dicts keyed by field name so pa.array can
    build struct arrays from the nested row shape."""
    if isinstance(dtype, StructType):
        names = [f.name for f in dtype.fields]
        return [None if v is None else
                {nm: nv for nm, nv in zip(
                    names, (_normalize_objects([x], f.dtype)[0]
                            for x, f in zip(v, dtype.fields)))}
                for v in values]
    if isinstance(dtype, ArrayType):
        return [None if v is None else _normalize_objects(list(v), dtype.element)
                for v in values]
    return list(values)
