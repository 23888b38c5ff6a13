"""Fused Pallas TPU kernel for the decode hot plane.

The decode of a record batch has two parts: byte *layout* (pulling each
field's bytes out of the `[batch, record_len]` byte matrix) and byte
*arithmetic* (turning those bytes into typed values + validity — the
reference's per-field hot loop, RecordExtractors.scala:49 +
BinaryNumberDecoders.scala:21, BCDNumberDecoders.scala:29,
StringDecoders.scala:154).

Arithmetic is the Pallas kernel: binary two's complement, packed BCD, and
zoned DISPLAY (the overpunch state machine as int32 VPU compare/select
math), element-wise on whatever slabs it is handed. Values wider than 32
bits (10-18 digit fields, and the 19-38 digit BigDecimal plane) are
accumulated in base-2^16 limbs held in int32 lanes (TPUs have no native
int64) and assembled into int64 / uint64-pair outputs by XLA after the
kernel, so every fused group returns exactly the tuples the plain XLA
route produces (`columnar._run_group_jax` contracts). String groups stay
on that route (static slices and an element-wise lookup,
`batch_jax.transcode_ebcdic`); floats and host-fallback columns are the
only other non-fused planes.

Layout is XLA's, and the kernel has two orientations. Which one a group
takes is read off the plan, its column count against the 128 lanes
(`LANE_FILL_MIN`); a program holding both kinds makes two pallas_calls.

* **Row tiles** (`_fused_kernel`), for a group whose columns fill the
  lanes (OCCURS arrays: exp3's `STRATEGY-DETAIL OCCURS 2000`,
  TestDataGen4CompaniesWide.scala:37-54): `[BATCH_TILE, count]` tiles,
  32 rows in the sublanes and the group's columns in the lanes. Byte
  ``j`` of every column is one strided slice `data[:, base+j::stride]`
  (one gather `data[:, offsets + j]` where the offsets are no
  progression): Mosaic supports neither strided lane slices nor u8 lane
  gathers inside a kernel, so the byte planes are computed in XLA and
  flow in side by side.
* **Rows in the lanes** (`_lane_kernel`), for every narrower group (1 to
  15 columns in exp1, 1 to 4 in the TPC-H queries): with columns in the
  lanes such a group used 1 to 15 lanes of 128 and a launch of 524,288
  rows made 16,384 grid steps of 32. Here the bytes the groups read are
  transposed once in XLA to `[bytes, B / 128, 128]`, plane-major, and a
  grid step takes `LANE_TILE` = 4,096 rows: a byte of a field is one
  native `[32, 128]` uint8 tile, a value four whole int32 registers,
  however few columns the group has. A field's bytes are adjacent
  leading indices of that array whatever its offset in the record, so no
  layout is irregular and nothing is gathered. A group is one loop over
  its columns (the first plane of each from a table in scalar memory),
  its body traced and compiled once. The `[columns, B / 128, 128]`
  outputs go back to the contract's `[b, count]` planes in XLA.

Measured on a TPU v5e (PERF.md section 6, PR 31): the whole exp1 program
at `65536x1493` 352 ms a launch with every group in row tiles, 14 ms with
its 61 groups' rows in the lanes; the TPC-H programs at `524288` rows 44
to 1.9 and 3.3 ms. exp3's two groups (2,001 and 2,000 columns of four
bytes, 48 KB read and written a row) would need 197 MB of vector memory
a buffer at 4,096 rows a step, of a v5e's 128 MiB; they fill the lanes
as they are and keep the row tiles.

Parity of both orientations is pinned by tests/test_pallas_kernels.py
against the numpy blueprint kernels through the interpreter,
tests/test_tpu_compile.py compiles the Mosaic kernels for a described
v5e at the benchmark's launch shapes, and chip_smoke.py runs them on the
chip against the host kernels and the scalar oracle.
"""
from __future__ import annotations

import functools
from typing import List, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from ..plan.compiler import merged_spans, packed_position

BATCH_TILE = 32  # uint8 sublane tile: rows a grid step, row-tile kernel
LANES = 128
# rows a grid step of the rows-in-lanes kernel: [32, 128] uint8, one native
# tile a byte of the record
LANE_TILE = 32 * LANES
# a group of this many columns fills the lanes with columns and keeps the
# row-tile kernel; a narrower one puts the batch's rows in the lanes
LANE_FILL_MIN = LANES
# the rows-in-lanes kernel's blocks are double-buffered in vector memory
# (128 MiB on a v5e, Mosaic's default limit 16 MiB): exp1's 1,457 byte
# planes, 631 int32 and 249 bool columns are 4,977 B a row, 40.8 MB at
# LANE_TILE rows, and stay one call; a program past 6 KiB a row (48 MiB)
# is cut into several
LANE_ROW_BYTES_MAX = 6 * 1024
LANE_VMEM_LIMIT_BYTES = 96 * 1024 * 1024

# 16-bit limbs in int32 lanes: 4 limbs = one 64-bit value, 8 = 128-bit
_LIMBS = {"i32": 1, "i64": 4, "wide": 8}


class StridedGroup:
    """Static decode spec for one fused kernel group.

    base/stride/count describe the offset progression when regular;
    `offsets` carries the raw offsets for irregular groups (in row tiles
    the byte planes are then XLA gathers; with the rows in the lanes no
    layout is irregular). width is the field byte width; kind is
    "binary", "bcd", "display_ebcdic" or "display_ascii"; `out` selects
    the value plane: "i32" (native int32 lanes), "i64" (4x16-bit limbs),
    or "wide" (8x16-bit limbs, the uint128 BigDecimal plane).
    """

    def __init__(self, offsets: Sequence[int], width: int, kind: str,
                 out: str = "i32", signed: bool = False,
                 big_endian: bool = True, allow_dot: bool = False,
                 require_digits: bool = True, dyn_sf: int = 0):
        self.offsets = [int(o) for o in offsets]
        self.count = len(self.offsets)
        self.width = width
        self.kind = kind
        self.out = out
        self.signed = signed
        self.big_endian = big_endian
        self.allow_dot = allow_dot
        self.require_digits = require_digits
        self.dyn_sf = dyn_sf
        self.progression = offsets_progression(self.offsets)

    @property
    def end(self) -> int:
        return max(self.offsets) + self.width

    @property
    def is_display(self) -> bool:
        return self.kind.startswith("display")


def offsets_progression(offsets: Sequence[int]) -> Optional[Tuple[int, int]]:
    """(base, stride) if `offsets` is an increasing arithmetic progression,
    else None. A single column is a progression of stride 0."""
    offs = list(int(o) for o in offsets)
    if not offs:
        return None
    if len(offs) == 1:
        return offs[0], 0
    stride = offs[1] - offs[0]
    if stride <= 0:
        return None
    for a, b in zip(offs, offs[1:]):
        if b - a != stride:
            return None
    return offs[0], stride


def _byte_planes(data, g: StridedGroup):
    """XLA-side layout: byte j of every field in the group, j=0..width-1.
    Strided slice for regular layouts, gather for irregular ones."""
    planes = []
    if g.progression is not None:
        base, stride = g.progression
        for j in range(g.width):
            start = base + j
            if g.count == 1:
                planes.append(jax.lax.slice_in_dim(
                    data, start, start + 1, axis=1))
            else:
                limit = start + (g.count - 1) * stride + 1
                planes.append(jax.lax.slice_in_dim(
                    data, start, limit, stride=stride, axis=1))
    else:
        offs = jnp.asarray(g.offsets, dtype=jnp.int32)
        for j in range(g.width):
            planes.append(jnp.take(data, offs + j, axis=1))
    return planes


# ---------------------------------------------------------------------------
# in-kernel limb arithmetic (base 2^16 in int32 lanes)
# ---------------------------------------------------------------------------

def _limbs_zero(n, shape):
    return [jnp.zeros(shape, dtype=jnp.int32) for _ in range(n)]


def _limbs_mul10_add(limbs, digit, cond=None):
    """limbs <- limbs * 10 + digit, optionally only where `cond`."""
    out = []
    carry = digit
    for l in limbs:
        t = l * 10 + carry          # <= 655350 + 9: fits int32 exactly
        nl = t & 0xFFFF
        carry = t >> 16
        out.append(jnp.where(cond, nl, l) if cond is not None else nl)
    return out


def _limbs_shl8_or(limbs, byte):
    """limbs <- (limbs << 8) | byte (LSB-first limb order)."""
    out = []
    carry = byte
    for l in limbs:
        out.append(((l << 8) | carry) & 0xFFFF)
        carry = l >> 8
    return out


# ---------------------------------------------------------------------------
# in-kernel decode per kind
# ---------------------------------------------------------------------------

def _decode_binary_i32(planes, g: StridedGroup):
    w = g.width
    order = range(w) if g.big_endian else range(w - 1, -1, -1)
    acc = None
    for j in order:
        b = planes[j].astype(jnp.uint32)
        acc = b if acc is None else (acc << 8) | b
    nbits = 8 * w
    valid = jnp.ones(acc.shape, dtype=jnp.bool_)
    if g.signed:
        if nbits == 32:
            values = jax.lax.bitcast_convert_type(acc, jnp.int32)
        else:
            ivals = acc.astype(jnp.int32)
            sign_bit = jnp.uint32(1 << (nbits - 1))
            values = jnp.where((acc & sign_bit) != 0,
                               ivals - jnp.int32(1 << nbits), ivals)
    else:
        # unsigned with the top bit set exceeds the declared precision
        # bucket -> null (BinaryNumberDecoders.scala unsigned-overflow rule)
        if w == 4:
            valid = (acc >> 31) == 0
        # bitcast + typed zero: keeps Mosaic off the x64-promoted int64
        # conversion path; valid values have the top bit clear
        values = jnp.where(valid, jax.lax.bitcast_convert_type(
            acc, jnp.int32), jnp.int32(0))
    return [values, valid]


def _decode_binary_limbs(planes, g: StridedGroup):
    """Two's complement in 16-bit limbs; sign extension at init."""
    n = _LIMBS[g.out]
    w = g.width
    order = range(w) if g.big_endian else range(w - 1, -1, -1)
    first = True
    limbs = _limbs_zero(n, planes[0].shape)
    for j in order:
        b = planes[j].astype(jnp.int32)
        if first and g.signed:
            ext = jnp.where((b & 0x80) != 0, jnp.int32(0xFFFF),
                            jnp.int32(0))
            limbs = [ext for _ in range(n)]
        limbs = _limbs_shl8_or(limbs, b)
        first = False
    valid = jnp.ones(planes[0].shape, dtype=jnp.bool_)
    return limbs + [valid]


def _decode_bcd(planes, g: StridedGroup):
    w = g.width
    shape = planes[0].shape
    if g.out == "i32":
        acc = jnp.zeros(shape, dtype=jnp.int32)
    else:
        limbs = _limbs_zero(_LIMBS[g.out], shape)
    digit_ok = jnp.ones(shape, dtype=jnp.bool_)
    sign = None
    for j in range(w):
        b = planes[j].astype(jnp.int32)
        high = (b >> 4) & 0x0F
        low = b & 0x0F
        digit_ok &= high < 10
        if g.out == "i32":
            acc = acc * 10 + high
        else:
            limbs = _limbs_mul10_add(limbs, high)
        if j + 1 < w:
            digit_ok &= low < 10
            if g.out == "i32":
                acc = acc * 10 + low
            else:
                limbs = _limbs_mul10_add(limbs, low)
        else:
            sign = low
    sign_ok = (sign == 0x0C) | (sign == 0x0D) | (sign == 0x0F)
    valid = digit_ok & sign_ok
    negative = (sign == 0x0D) & valid
    if g.out == "i32":
        values = jnp.where(sign == 0x0D, -acc, acc)
        return [jnp.where(valid, values, jnp.int32(0)), valid]
    limbs = [jnp.where(valid, l, jnp.int32(0)) for l in limbs]
    return limbs + [negative, valid]


def _classify_display_byte(b, ascii_mode: bool):
    """One byte plane -> (is_digit, digit_val, is_sign, is_neg_mark,
    is_dot, is_space, known) as int32/bool lanes (the per-byte rules of
    StringDecoders.decodeEbcdicNumber / decodeAsciiNumber)."""
    # typed zeros throughout: a weak Python 0 inside jnp.where traces as
    # an i64 literal under x64 and Mosaic's convert lowering recurses
    if ascii_mode:
        is_digit = (b >= 0x30) & (b <= 0x39)
        dv = jnp.where(is_digit, b - 0x30, jnp.int32(0))
        is_minus = b == 0x2D
        is_plus = b == 0x2B
        is_dot = (b == 0x2E) | (b == 0x2C)
        is_space = b <= 0x20
        neg_mark = is_minus
        sign_mark = is_minus | is_plus
    else:
        is_f = (b >= 0xF0) & (b <= 0xF9)
        is_c = (b >= 0xC0) & (b <= 0xC9)
        is_d = (b >= 0xD0) & (b <= 0xD9)
        is_digit = is_f | is_c | is_d
        dv = jnp.where(is_f, b - 0xF0,
                       jnp.where(is_c, b - 0xC0,
                                 jnp.where(is_d, b - 0xD0, jnp.int32(0))))
        is_minus = b == 0x60
        is_plus = b == 0x4E
        is_dot = (b == 0x4B) | (b == 0x6B)
        is_space = (b == 0x40) | (b == 0x00)
        neg_mark = is_d | is_minus
        sign_mark = is_c | is_d | is_minus | is_plus
    known = is_digit | sign_mark | is_dot | is_space
    return is_digit, dv, sign_mark, neg_mark, is_dot, is_space, known


def _decode_display(planes, g: StridedGroup):
    """Zoned DISPLAY numeric as VPU compare/select math — the in-kernel
    form of StringDecoders.scala:154 (overpunched signs, separate +/-,
    explicit '.', space skipping, malformed -> null)."""
    ascii_mode = g.kind == "display_ascii"
    shape = planes[0].shape
    zero = jnp.zeros(shape, dtype=jnp.int32)
    if g.out == "i32":
        acc = zero
    else:
        limbs = _limbs_zero(_LIMBS[g.out], shape)
    n_digits = zero
    n_signs = zero
    n_dots = zero
    dots_right = zero
    seen_dot = jnp.zeros(shape, dtype=jnp.bool_)
    negative = jnp.zeros(shape, dtype=jnp.bool_)
    known_all = jnp.ones(shape, dtype=jnp.bool_)

    if ascii_mode:
        # interior-space rule needs lookahead: a space with meaningful
        # bytes on both sides survives into the JVM parse and nulls it
        meaningful = []
        for j in range(g.width):
            b = planes[j].astype(jnp.int32)
            is_digit, _, _, _, is_dot, _, _ = _classify_display_byte(
                b, ascii_mode=True)
            meaningful.append(is_digit | is_dot)
        suffix = [None] * g.width
        later = jnp.zeros(shape, dtype=jnp.bool_)
        for j in range(g.width - 1, -1, -1):
            suffix[j] = later
            later = later | meaningful[j]
        seen_meaningful = jnp.zeros(shape, dtype=jnp.bool_)
        interior_space = jnp.zeros(shape, dtype=jnp.bool_)

    for j in range(g.width):
        b = planes[j].astype(jnp.int32)
        is_digit, dv, sign_mark, neg_mark, is_dot, is_space, known = \
            _classify_display_byte(b, ascii_mode)
        if ascii_mode:
            interior_space |= is_space & seen_meaningful & suffix[j]
            seen_meaningful |= meaningful[j]
        known_all &= known
        seen_dot |= is_dot
        dots_right += (is_digit & seen_dot).astype(jnp.int32)
        n_digits += is_digit.astype(jnp.int32)
        n_dots += is_dot.astype(jnp.int32)
        n_signs += sign_mark.astype(jnp.int32)
        negative |= neg_mark
        if g.out == "i32":
            acc = jnp.where(is_digit, acc * 10 + dv, acc)
        else:
            limbs = _limbs_mul10_add(limbs, dv, cond=is_digit)

    valid = known_all & (n_signs <= 1)
    if ascii_mode:
        valid &= ~interior_space
    if g.require_digits:
        valid &= n_digits >= 1
    valid &= (n_dots <= 1) if g.allow_dot else (n_dots == 0)
    if not g.signed:
        valid &= ~negative
    dots = dots_right if g.dyn_sf >= 0 else (-g.dyn_sf + n_digits)
    dots = jnp.where(valid, dots, zero)
    if g.out == "i32":
        values = jnp.where(negative, -acc, acc)
        return [jnp.where(valid, values, zero), valid, dots]
    limbs = [jnp.where(valid, l, zero) for l in limbs]
    return limbs + [negative & valid, valid, dots]


def _decode_group(planes, g: StridedGroup):
    if g.kind == "binary":
        return (_decode_binary_i32(planes, g) if g.out == "i32"
                else _decode_binary_limbs(planes, g))
    if g.kind == "bcd":
        return _decode_bcd(planes, g)
    return _decode_display(planes, g)


def _out_dtypes(g: StridedGroup):
    """Kernel output dtypes for a group, in _decode_group order."""
    limbs = _LIMBS[g.out]
    if g.kind == "binary":
        return [jnp.int32] * limbs + [jnp.bool_]
    if g.kind == "bcd":
        return ([jnp.int32, jnp.bool_] if g.out == "i32"
                else [jnp.int32] * limbs + [jnp.bool_, jnp.bool_])
    return ([jnp.int32, jnp.bool_, jnp.int32] if g.out == "i32"
            else [jnp.int32] * limbs + [jnp.bool_, jnp.bool_, jnp.int32])


def _fused_kernel(layout, in_ref, o32_ref, obool_ref):
    """The row-tile kernel: `BATCH_TILE` rows in the sublanes, a group's
    columns in the lanes. Reads each group's byte planes from the packed
    input buffer and writes its outputs into column segments of the
    packed int32 / bool output buffers. Packing matters on TPU: separate
    [batch, count] buffers with tiny counts would each pad to the 128-lane
    tile."""
    for g, in_base, slots in layout:
        planes = [in_ref[:, in_base + j * g.count:
                         in_base + (j + 1) * g.count]
                  for j in range(g.width)]
        for (space, start), arr in zip(slots, _decode_group(planes, g)):
            ref = o32_ref if space == "i32" else obool_ref
            ref[:, start:start + g.count] = arr


def _lane_kernel(layout, rows_ref, in_ref, o32_ref, obool_ref):
    """The rows-in-lanes kernel: `LANE_TILE` rows of the batch fill whole
    vector registers ([LANE_TILE / 128, 128]: one native uint8 tile a
    byte, four int32 registers a value) however few columns a group has.
    The input is plane-major, one leading index a byte of the record;
    `rows_ref` (scalar memory) holds the index of every column's first
    byte, its other bytes follow. A group is one loop over its columns,
    so its body is traced and compiled once and a column's live values
    fit the register file."""
    for g, col_base, slots in layout:

        def column(c, carry):
            first = rows_ref[col_base + c]
            planes = [in_ref[first + jnp.int32(j)] for j in range(g.width)]
            for (space, start), arr in zip(slots, _decode_group(planes, g)):
                ref = o32_ref if space == "i32" else obool_ref
                ref[jnp.int32(start) + c] = arr
            return carry

        # typed bounds: under jax_enable_x64 a Python int traces as i64
        jax.lax.fori_loop(jnp.int32(0), jnp.int32(g.count), column, None)


# ---------------------------------------------------------------------------
# XLA-side assembly of kernel outputs into the _run_group_jax contracts
# ---------------------------------------------------------------------------

def _assemble_u64(limbs):
    v = jnp.zeros(limbs[0].shape, dtype=jnp.uint64)
    for k in range(3, -1, -1):
        v = (v << 16) | limbs[k].astype(jnp.uint64)
    return v


def _assemble_u128(limbs):
    lo = _assemble_u64(limbs[:4])
    hi = _assemble_u64(limbs[4:8])
    return hi, lo


def _assemble_group(outs, g: StridedGroup):
    """Kernel buffers -> the exact tuple the plain XLA route returns for
    this group (int64 values via x64, uint64 limb pairs for wide)."""
    if g.out == "i32":
        return tuple(outs)
    limbs = outs[:_LIMBS[g.out]]
    rest = outs[_LIMBS[g.out]:]
    if g.kind == "binary":
        (valid,) = rest
        if g.out == "i64":
            v = jax.lax.bitcast_convert_type(_assemble_u64(limbs), jnp.int64)
            if not g.signed and g.width == 8:
                # unsigned 8-byte overflow -> null (JVM Long bucket)
                valid = valid & (v >= 0)
                v = jnp.where(valid, v, jnp.int64(0))
            return v, valid
        hi, lo = _assemble_u128(limbs)
        if g.signed:
            negative = (hi >> 63) != 0
            neg_lo = (~lo) + jnp.uint64(1)
            neg_hi = (~hi) + (neg_lo == 0).astype(jnp.uint64)
            hi = jnp.where(negative, neg_hi, hi)
            lo = jnp.where(negative, neg_lo, lo)
        else:
            negative = jnp.zeros(hi.shape, dtype=jnp.bool_)
        return hi, lo, negative, valid
    # bcd / display carry the magnitude in the limbs and sign separately
    if g.kind == "bcd":
        negative, valid = rest
        tail = ()
    else:
        negative, valid, dots = rest
        tail = (dots,)
    if g.out == "i64":
        # int64 multiply-add wrap semantics == mod-2^64 limb accumulation
        v = jax.lax.bitcast_convert_type(_assemble_u64(limbs), jnp.int64)
        v = jnp.where(negative, -v, v)
        return (v, valid) + tail
    hi, lo = _assemble_u128(limbs)
    return (hi, lo, negative, valid) + tail


def _output_slots(groups: Sequence[StridedGroup]):
    """Where every output of every group lies in a kernel's packed int32
    and bool buffers: [(space, first column), ...] a group, in
    `_decode_group` order, and the two buffers' column counts."""
    base = {"i32": 0, "bool": 0}
    slots_of = []
    for g in groups:
        slots = []
        for dtype in _out_dtypes(g):
            space = "bool" if dtype is jnp.bool_ else "i32"
            slots.append((space, base[space]))
            base[space] += g.count
        slots_of.append(slots)
    return slots_of, max(base["i32"], 1), max(base["bool"], 1)


def _i32_index(*dims):
    """A block index map of static zeros and the grid step. Typed zeros:
    under jax_enable_x64 a literal 0 traces as i64 and Mosaic rejects the
    (i32, i64) index tuple."""
    def index_map(i, *_):
        return tuple(i if d else jnp.int32(0) for d in dims)
    return index_map


def _build_row_tiles(groups: Sequence[StridedGroup], interpret: bool):
    """fn([B, need_len] uint8) -> one tuple a group, through the row-tile
    kernel: byte planes `[B, count]` by strided slices (gathers where the
    offsets are irregular) side by side, `BATCH_TILE` rows a grid step."""
    from jax.experimental import pallas as pl

    slots_of, total_i32, total_bool = _output_slots(groups)
    layout = []
    in_base = 0
    for g, slots in zip(groups, slots_of):
        layout.append((g, in_base, slots))
        in_base += g.width * g.count
    total_in = in_base

    def fn(data):
        b = data.shape[0]
        bpad = -b % BATCH_TILE
        if bpad:
            data = jnp.pad(data, ((0, bpad), (0, 0)))
        # scopes named by the plan, not by the order of fusion: they land
        # in every operation's `op_name`, so a trace names the step
        with jax.named_scope("cobrix.planes"):
            planes = []
            for g in groups:
                planes.extend(_byte_planes(data, g))
            packed = jnp.concatenate(planes, axis=1)
        with jax.named_scope("cobrix.kernel"):
            o32, obool = pl.pallas_call(
                functools.partial(_fused_kernel, layout),
                grid=((b + bpad) // BATCH_TILE,),
                in_specs=[pl.BlockSpec((BATCH_TILE, total_in),
                                       _i32_index(1, 0))],
                out_specs=[pl.BlockSpec((BATCH_TILE, total_i32),
                                        _i32_index(1, 0)),
                           pl.BlockSpec((BATCH_TILE, total_bool),
                                        _i32_index(1, 0))],
                out_shape=[jax.ShapeDtypeStruct((b + bpad, total_i32),
                                                jnp.int32),
                           jax.ShapeDtypeStruct((b + bpad, total_bool),
                                                jnp.bool_)],
                interpret=interpret,
            )(packed)
        results = []
        with jax.named_scope("cobrix.outputs"):
            for g, _, slots in layout:
                bufs = [(o32 if space == "i32" else obool)[
                    :b, start:start + g.count] for space, start in slots]
                results.append(tuple(_assemble_group(bufs, g)))
        return results

    return fn


def _build_rows_in_lanes(groups: Sequence[StridedGroup], interpret: bool):
    """fn([B, need_len] uint8) -> one tuple a group, through the
    rows-in-lanes kernel. The bytes the groups read (their fields' spans,
    merged) are transposed once in XLA to `[bytes, B / 128, 128]`: a
    field's bytes are adjacent leading indices whatever its offset, so no
    layout is irregular here and nothing is gathered. The kernel's
    `[columns, B / 128, 128]` outputs come back as the `[B, count]` planes
    of the `_run_group_jax` contract by a small transpose (a reshape
    where the group is one column)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    spans = merged_spans((o, o + g.width) for g in groups
                         for o in g.offsets)
    total_in = sum(hi - lo for lo, hi in spans)
    slots_of, total_i32, total_bool = _output_slots(groups)
    layout = []
    first_planes: List[int] = []
    for g, slots in zip(groups, slots_of):
        layout.append((g, len(first_planes), slots))
        first_planes.extend(packed_position(spans, o) for o in g.offsets)
    sub = LANE_TILE // LANES

    def fn(data):
        b = data.shape[0]
        bpad = -b % LANE_TILE
        folds = (b + bpad) // LANES
        with jax.named_scope("cobrix.planes"):
            parts = [jax.lax.slice_in_dim(data, lo, hi, axis=1)
                     for lo, hi in spans]
            needed = (parts[0] if len(parts) == 1
                      else jnp.concatenate(parts, axis=1))
            if bpad:
                needed = jnp.pad(needed, ((0, bpad), (0, 0)))
            planes = needed.T.reshape(total_in, folds, LANES)
        with jax.named_scope("cobrix.kernel"):
            o32, obool = pl.pallas_call(
                functools.partial(_lane_kernel, layout),
                grid_spec=pltpu.PrefetchScalarGridSpec(
                    num_scalar_prefetch=1,
                    grid=(folds // sub,),
                    in_specs=[pl.BlockSpec((total_in, sub, LANES),
                                           _i32_index(0, 1, 0))],
                    out_specs=[pl.BlockSpec((total_i32, sub, LANES),
                                            _i32_index(0, 1, 0)),
                               pl.BlockSpec((total_bool, sub, LANES),
                                            _i32_index(0, 1, 0))]),
                out_shape=[jax.ShapeDtypeStruct((total_i32, folds, LANES),
                                                jnp.int32),
                           jax.ShapeDtypeStruct((total_bool, folds, LANES),
                                                jnp.bool_)],
                compiler_params=pltpu.CompilerParams(
                    vmem_limit_bytes=LANE_VMEM_LIMIT_BYTES),
                interpret=interpret,
            )(jnp.asarray(first_planes, dtype=jnp.int32), planes)
        results = []
        with jax.named_scope("cobrix.outputs"):
            for g, _, slots in layout:
                bufs = []
                for space, start in slots:
                    src = o32 if space == "i32" else obool
                    rows = src[start:start + g.count].reshape(
                        g.count, folds * LANES)[:, :b]
                    bufs.append(rows.T)
                results.append(tuple(_assemble_group(bufs, g)))
        return results

    return fn


def _lane_calls(picked: Sequence[int], groups: Sequence[StridedGroup]):
    """`picked` (indices of rows-in-lanes groups) cut, in order, into the
    pallas_calls whose blocks fit the vector memory: one call unless the
    groups read and write more than LANE_ROW_BYTES_MAX a row."""
    calls: List[List[int]] = []
    room = 0
    for i in picked:
        g = groups[i]
        # a bool leaves the kernel as an int32 (Mosaic's memref type)
        cost = g.count * (g.width + 4 * len(_out_dtypes(g)))
        if not calls or cost > room:
            calls.append([])
            room = LANE_ROW_BYTES_MAX
        calls[-1].append(i)
        room -= cost
    return calls


def build_fused_decode(groups: Sequence[StridedGroup], record_len: int,
                       interpret: bool | None = None):
    """Returns fn(data: [B, record_len] uint8) -> [group tuples, ...] in
    the `columnar._run_group_jax` output format for each group.

    jit-traceable. Groups of at least `LANE_FILL_MIN` columns go through
    the row-tile kernel, narrower ones through the rows-in-lanes kernel:
    at most two pallas_calls a program (more only where the narrow groups
    outgrow the vector memory, `_lane_calls`), one result list in group
    order.
    `fn.rows_in_lanes` counts the groups of the second kind.

    `interpret=None` runs the kernels through the Pallas interpreter
    everywhere but on a TPU (the CPU tests' parity tool). The choice is
    recorded on the returned function as `fn.interpret`, so a caller that
    needs the Mosaic kernel can refuse anything else.
    """
    from .batch_jax import ensure_x64

    ensure_x64()  # the limb assembly builds int64/uint64 planes
    groups = list(groups)
    need_len = max([record_len] + [g.end for g in groups])
    if interpret is None:
        interpret = jax.default_backend() != "tpu"

    lane_full = [g.count >= LANE_FILL_MIN for g in groups]
    parts = []
    picked = [i for i, full in enumerate(lane_full) if full]
    if picked:
        parts.append((picked, _build_row_tiles(
            [groups[i] for i in picked], interpret)))
    for picked in _lane_calls(
            [i for i, full in enumerate(lane_full) if not full], groups):
        parts.append((picked, _build_rows_in_lanes(
            [groups[i] for i in picked], interpret)))

    def fn(data):
        lpad = need_len - data.shape[1]
        if lpad > 0:
            data = jnp.pad(data, ((0, 0), (0, lpad)))
        results: List[Optional[tuple]] = [None] * len(groups)
        for picked, part in parts:
            for i, outs in zip(picked, part(data)):
                results[i] = outs
        return results

    fn.interpret = interpret
    fn.rows_in_lanes = lane_full.count(False)
    return fn
