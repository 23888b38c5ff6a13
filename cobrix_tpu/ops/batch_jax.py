"""Batched columnar decoders — JAX implementation (the TPU compute path).

Same math as `batch_np` (the blueprint/oracle-validated module), written in
`jax.numpy` so the whole per-batch decode compiles to one XLA program:
byte slabs + vector integer/float ops that XLA fuses and tiles for
the TPU VPU. No data-dependent control flow — every branch is a `where`,
shapes are static per (batch, K, width) group, so jit tracing happens once
per plan + batch-shape bucket.

Fixed-point values accumulate in int32 when the column group's declared
precision fits (<= 9 digits) and int64 otherwise; int64 on TPU is emulated
but only pays on the wide-precision groups. Requires jax_enable_x64 for the
wide groups (enabled by `cobrix_tpu.ops.jax_setup`).
"""
from __future__ import annotations

import functools
from typing import Tuple

import numpy as np

import jax
import jax.numpy as jnp


def ensure_x64() -> None:
    """Enable 64-bit types once (wide-precision groups need int64 lanes)."""
    jax.config.update("jax_enable_x64", True)


_POW10_64 = np.array([10 ** i for i in range(19)], dtype=np.int64)
_POW10_32 = np.array([10 ** i for i in range(10)], dtype=np.int32)


def _pow10(e, dtype):
    if dtype == jnp.int32:
        return jnp.asarray(_POW10_32)[jnp.clip(e, 0, 9)]
    return jnp.asarray(_POW10_64)[jnp.clip(e, 0, 18)]


# ---------------------------------------------------------------------------
# binary (COMP/COMP-4/COMP-5/COMP-9)
# ---------------------------------------------------------------------------

def decode_binary(data: jnp.ndarray, signed: bool, big_endian: bool,
                  out_dtype=jnp.int64) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """[..., W] uint8 -> (int values, valid)."""
    w = data.shape[-1]
    use32 = out_dtype == jnp.int32 and w <= 4
    acc_dtype = jnp.uint32 if use32 else jnp.uint64
    int_dtype = jnp.int32 if use32 else jnp.int64
    acc_bits = 32 if use32 else 64
    nbits = 8 * w
    acc = jnp.zeros(data.shape[:-1], dtype=acc_dtype)
    rng = range(w) if big_endian else range(w - 1, -1, -1)
    for i in rng:
        acc = (acc << 8) | data[..., i].astype(acc_dtype)
    valid = jnp.ones(acc.shape, dtype=jnp.bool_)
    if signed:
        if nbits == acc_bits:
            values = jax.lax.bitcast_convert_type(acc, int_dtype)
        else:
            # acc < 2^(acc_bits-1): plain convert is exact, then sign-correct
            ivals = acc.astype(int_dtype)
            sign_bit = jnp.asarray(1 << (nbits - 1), dtype=acc_dtype)
            values = jnp.where((acc & sign_bit) != 0, ivals - (1 << nbits), ivals)
    else:
        if w in (4, 8):
            valid = (acc >> (nbits - 1)) == 0
        if nbits == acc_bits:
            values = jnp.where(valid,
                               jax.lax.bitcast_convert_type(acc, int_dtype), 0)
        else:
            values = jnp.where(valid, acc.astype(int_dtype), 0)
    return values.astype(out_dtype), valid


# ---------------------------------------------------------------------------
# packed BCD (COMP-3)
# ---------------------------------------------------------------------------

def decode_bcd(data: jnp.ndarray,
               out_dtype=jnp.int64) -> Tuple[jnp.ndarray, jnp.ndarray]:
    w = data.shape[-1]
    high = ((data >> 4) & 0x0F).astype(out_dtype)
    low = (data & 0x0F).astype(out_dtype)
    sign_nibble = low[..., -1]
    digit_ok = jnp.all(high < 10, axis=-1) & jnp.all(low[..., :-1] < 10, axis=-1)
    sign_ok = ((sign_nibble == 0x0C) | (sign_nibble == 0x0D)
               | (sign_nibble == 0x0F))
    acc = jnp.zeros(data.shape[:-1], dtype=out_dtype)
    for i in range(w):
        acc = acc * 10 + high[..., i]
        if i + 1 < w:
            acc = acc * 10 + low[..., i]
    values = jnp.where(sign_nibble == 0x0D, -acc, acc)
    valid = digit_ok & sign_ok
    return jnp.where(valid, values, 0), valid


# ---------------------------------------------------------------------------
# zoned decimal (DISPLAY)
# ---------------------------------------------------------------------------

# Running counts along a field's bytes are built from one elementwise step
# per byte, not from jnp.cumsum: inside the exp1 program on a TPU v5e
# (libtpu 0.0.34, PR 21's chip run) the scan XLA emits for a cumsum over
# the 28-byte DISPLAY fields miscounts from the 17th byte on — every
# other width came out right, and so did the same field compiled alone —
# which silently misplaces digits. Field widths are static and small, so
# the unrolled form costs a few dozen vector ops and nothing else.

def _digits_to_right(is_digit):
    """int32 [..., W]: how many digits follow each byte."""
    idig = is_digit.astype(jnp.int32)
    running = jnp.zeros(idig.shape[:-1], dtype=jnp.int32)
    counts = [None] * idig.shape[-1]
    for j in range(idig.shape[-1] - 1, -1, -1):
        counts[j] = running
        running = running + idig[..., j]
    return jnp.stack(counts, axis=-1)


def _digits_after_dot(is_digit, is_dot):
    """int32 [...]: digits to the right of the first decimal point."""
    seen_dot = jnp.zeros(is_dot.shape[:-1], dtype=jnp.bool_)
    total = jnp.zeros(is_dot.shape[:-1], dtype=jnp.int32)
    for j in range(is_dot.shape[-1]):
        seen_dot = seen_dot | is_dot[..., j]
        total = total + (seen_dot & is_digit[..., j]).astype(jnp.int32)
    return total


def _any_before_and_after(mask):
    """bool [..., W] pair: is any `mask` byte strictly left / strictly
    right of each position."""
    w = mask.shape[-1]
    left = [None] * w
    right = [None] * w
    seen = jnp.zeros(mask.shape[:-1], dtype=jnp.bool_)
    for j in range(w):
        left[j] = seen
        seen = seen | mask[..., j]
    seen = jnp.zeros(mask.shape[:-1], dtype=jnp.bool_)
    for j in range(w - 1, -1, -1):
        right[j] = seen
        seen = seen | mask[..., j]
    return jnp.stack(left, axis=-1), jnp.stack(right, axis=-1)


def _classify_display_ebcdic(b):
    """Shared classification of the reference zoned-decimal state machine
    (mirror of batch_np._classify_display_ebcdic). Returns
    (is_digit, digit_val, negative, dot_right, n_dots, n_digits,
    valid_base)."""
    is_f_digit = (b >= 0xF0) & (b <= 0xF9)
    is_c_digit = (b >= 0xC0) & (b <= 0xC9)
    is_d_digit = (b >= 0xD0) & (b <= 0xD9)
    is_minus = b == 0x60
    is_plus = b == 0x4E
    is_dot = (b == 0x4B) | (b == 0x6B)
    is_space = (b == 0x40) | (b == 0x00)
    is_digit = is_f_digit | is_c_digit | is_d_digit
    known = is_digit | is_minus | is_plus | is_dot | is_space
    sign_marks = is_c_digit | is_d_digit | is_minus | is_plus
    n_signs = sign_marks.sum(axis=-1)
    n_dots = is_dot.sum(axis=-1)
    n_digits = is_digit.sum(axis=-1)
    digit_val = jnp.where(
        is_f_digit, b - 0xF0,
        jnp.where(is_c_digit, b - 0xC0,
                  jnp.where(is_d_digit, b - 0xD0, 0)))
    negative = (is_d_digit | is_minus).any(axis=-1)
    dot_right = _digits_after_dot(is_digit, is_dot)
    valid_base = jnp.all(known, axis=-1) & (n_signs <= 1)
    return is_digit, digit_val, negative, dot_right, n_dots, n_digits, \
        valid_base


def _classify_display_ascii(b):
    """Mirror of batch_np._classify_display_ascii."""
    is_digit = (b >= 0x30) & (b <= 0x39)
    is_minus = b == 0x2D
    is_plus = b == 0x2B
    is_dot = (b == 0x2E) | (b == 0x2C)
    is_space = b <= 0x20
    known = is_digit | is_minus | is_plus | is_dot | is_space
    n_signs = (is_minus | is_plus).sum(axis=-1)
    n_dots = is_dot.sum(axis=-1)
    n_digits = is_digit.sum(axis=-1)
    left_has, right_has = _any_before_and_after(is_digit | is_dot)
    interior_space = (is_space & left_has & right_has).any(axis=-1)
    digit_val = jnp.where(is_digit, b - 0x30, 0)
    negative = is_minus.any(axis=-1)
    dot_right = _digits_after_dot(is_digit, is_dot)
    valid_base = jnp.all(known, axis=-1) & (n_signs <= 1) & ~interior_space
    return is_digit, digit_val, negative, dot_right, n_dots, n_digits, \
        valid_base


def _display_valid(valid_base, n_digits, n_dots, negative, signed,
                   allow_dot, require_digits):
    valid = valid_base
    if require_digits:
        valid &= n_digits >= 1
    valid &= (n_dots <= 1) if allow_dot else (n_dots == 0)
    if not signed:
        valid &= ~negative
    return valid


def _decode_display(classify, data, signed, allow_dot, require_digits,
                    out_dtype, dyn_sf):
    (is_digit, digit_val, negative, dot_right, n_dots, n_digits,
     valid_base) = classify(data)
    digits_right = _digits_to_right(is_digit)
    mantissa = jnp.sum(digit_val.astype(out_dtype)
                       * _pow10(digits_right, out_dtype), axis=-1)
    mantissa = jnp.where(negative, -mantissa, mantissa)
    valid = _display_valid(valid_base, n_digits, n_dots, negative,
                           signed, allow_dot, require_digits)
    if dyn_sf < 0:
        dot_right = -dyn_sf + n_digits
    return (jnp.where(valid, mantissa, 0), valid,
            jnp.where(valid, dot_right, 0).astype(jnp.int32))


def decode_display_ebcdic(data: jnp.ndarray, signed: bool, allow_dot: bool,
                          require_digits: bool = True, out_dtype=jnp.int64,
                          dyn_sf: int = 0):
    return _decode_display(_classify_display_ebcdic, data, signed,
                           allow_dot, require_digits, out_dtype, dyn_sf)


def decode_display_ascii(data: jnp.ndarray, signed: bool, allow_dot: bool,
                         require_digits: bool = True, out_dtype=jnp.int64,
                         dyn_sf: int = 0):
    return _decode_display(_classify_display_ascii, data, signed,
                           allow_dot, require_digits, out_dtype, dyn_sf)


# ---------------------------------------------------------------------------
# wide (>18-digit) exact numerics: uint128 magnitude as two uint64 limbs
# (blueprint: batch_np decode_*_wide — same math, jnp ops; requires x64)
# ---------------------------------------------------------------------------

def _mul64to128(a, c: int):
    a = a.astype(jnp.uint64)
    m32 = jnp.uint64(0xFFFFFFFF)
    a_lo, a_hi = a & m32, a >> 32
    c_lo, c_hi = jnp.uint64(c & 0xFFFFFFFF), jnp.uint64(c >> 32)
    ll = a_lo * c_lo
    lh = a_lo * c_hi
    hl = a_hi * c_lo
    hh = a_hi * c_hi
    t = (lh & m32) + (hl & m32) + (ll >> 32)
    lo = (ll & m32) | ((t & m32) << 32)
    hi = hh + (lh >> 32) + (hl >> 32) + (t >> 32)
    return hi, lo


def _add128(hi, lo, add_hi, add_lo):
    l = lo + add_lo
    return hi + add_hi + (l < lo).astype(jnp.uint64), l


def _chunks_to_u128(chunks):
    chunk_base = 10 ** 18
    hi = jnp.zeros_like(chunks[0], dtype=jnp.uint64)
    lo = chunks[0].astype(jnp.uint64)
    for c in chunks[1:]:
        mul_hi, mul_lo = _mul64to128(lo, chunk_base)
        hi = mul_hi + hi * jnp.uint64(chunk_base)
        lo = mul_lo
        hi, lo = _add128(hi, lo, jnp.uint64(0), c.astype(jnp.uint64))
    return hi, lo


def _digit_chunks(digit_val, digits_right, max_digits: int):
    chunks = []
    n_chunks = (max_digits + 17) // 18
    for k in range(n_chunks - 1, -1, -1):
        in_chunk = (digits_right >= 18 * k) & (digits_right < 18 * (k + 1))
        rel = jnp.where(in_chunk, digits_right - 18 * k, 0)
        part = jnp.sum(jnp.where(in_chunk, digit_val, 0)
                       * _pow10(rel, jnp.int64), axis=-1)
        chunks.append(part.astype(jnp.uint64))
    return chunks


def decode_bcd_wide(data: jnp.ndarray):
    """Wide COMP-3 -> (hi, lo, negative, valid); uint128 magnitude limbs."""
    w = data.shape[-1]
    high = ((data >> 4) & 0x0F).astype(jnp.int64)
    low = (data & 0x0F).astype(jnp.int64)
    sign_nibble = low[..., -1]
    digit_ok = jnp.all(high < 10, axis=-1) & jnp.all(low[..., :-1] < 10,
                                                     axis=-1)
    sign_ok = ((sign_nibble == 0x0C) | (sign_nibble == 0x0D)
               | (sign_nibble == 0x0F))
    digits = jnp.concatenate(
        [jnp.stack([high[..., :-1], low[..., :-1]], axis=-1).reshape(
            data.shape[:-1] + (2 * (w - 1),)),
         high[..., -1:]], axis=-1)
    d_total = 2 * w - 1
    pos_right = jnp.broadcast_to(
        jnp.arange(d_total - 1, -1, -1, dtype=jnp.int64), digits.shape)
    hi, lo = _chunks_to_u128(_digit_chunks(digits, pos_right, d_total))
    negative = sign_nibble == 0x0D
    valid = digit_ok & sign_ok
    zero = jnp.uint64(0)
    return (jnp.where(valid, hi, zero), jnp.where(valid, lo, zero),
            negative & valid, valid)


def decode_binary_wide(data: jnp.ndarray, signed: bool, big_endian: bool):
    """9-16 byte two's complement -> (hi, lo, negative, valid)."""
    w = data.shape[-1]
    b = data.astype(jnp.uint64)
    order = range(w) if big_endian else range(w - 1, -1, -1)
    hi = jnp.zeros(data.shape[:-1], dtype=jnp.uint64)
    lo = jnp.zeros(data.shape[:-1], dtype=jnp.uint64)
    first = True
    for i in order:
        byte = b[..., i]
        if first and signed:
            ext = jnp.where((byte & jnp.uint64(0x80)) != 0,
                            jnp.uint64(0xFFFFFFFFFFFFFFFF), jnp.uint64(0))
            hi = ext
            lo = ext
        hi = (hi << 8) | (lo >> 56)
        lo = (lo << 8) | byte
        first = False
    if signed:
        negative = (hi >> 63) != 0
    else:
        negative = jnp.zeros(data.shape[:-1], dtype=jnp.bool_)
    neg_lo = (~lo) + jnp.uint64(1)
    neg_hi = (~hi) + (neg_lo == 0).astype(jnp.uint64)
    hi = jnp.where(negative, neg_hi, hi)
    lo = jnp.where(negative, neg_lo, lo)
    valid = jnp.ones(data.shape[:-1], dtype=jnp.bool_)
    return hi, lo, negative, valid


def _decode_display_wide(classify, data, signed, allow_dot, require_digits,
                         dyn_sf: int = 0):
    (is_digit, digit_val, negative, dot_right, n_dots, n_digits,
     valid_base) = classify(data)
    digits_right = _digits_to_right(is_digit).astype(jnp.int64)
    hi, lo = _chunks_to_u128(
        _digit_chunks(digit_val.astype(jnp.int64), digits_right,
                      data.shape[-1]))
    valid = _display_valid(valid_base, n_digits, n_dots, negative,
                           signed, allow_dot, require_digits)
    if dyn_sf < 0:
        dot_right = -dyn_sf + n_digits
    zero = jnp.uint64(0)
    return (jnp.where(valid, hi, zero), jnp.where(valid, lo, zero),
            negative & valid, valid,
            jnp.where(valid, dot_right, 0).astype(jnp.int32))


def decode_display_ebcdic_wide(data: jnp.ndarray, signed: bool,
                               allow_dot: bool, require_digits: bool = True,
                               dyn_sf: int = 0):
    return _decode_display_wide(_classify_display_ebcdic, data, signed,
                                allow_dot, require_digits, dyn_sf)


def decode_display_ascii_wide(data: jnp.ndarray, signed: bool,
                              allow_dot: bool, require_digits: bool = True,
                              dyn_sf: int = 0):
    return _decode_display_wide(_classify_display_ascii, data, signed,
                                allow_dot, require_digits, dyn_sf)


# ---------------------------------------------------------------------------
# floating point
# ---------------------------------------------------------------------------

def decode_ieee_float(data: jnp.ndarray, big_endian: bool, double: bool):
    """For `double`, returns the IEEE754 *bit pattern* as uint64 — the host
    views it as float64 after transfer. TPUs have no native f64; a device-side
    bitcast to f64 rounds through the emulation path and loses the last ULP."""
    w = 8 if double else 4
    slab = data[..., :w]
    if not big_endian:
        slab = slab[..., ::-1]
    acc_dtype = jnp.uint64 if double else jnp.uint32
    acc = jnp.zeros(slab.shape[:-1], dtype=acc_dtype)
    for i in range(w):
        acc = (acc << 8) | slab[..., i].astype(acc_dtype)
    if double:
        return acc, jnp.ones(acc.shape, dtype=jnp.bool_)
    values = jax.lax.bitcast_convert_type(acc, jnp.float32)
    return values, jnp.ones(values.shape, dtype=jnp.bool_)


def decode_ibm_float32(data: jnp.ndarray):
    """IBM hex float -> IEEE float32 with the reference's sign-mask-as-
    exponent-mask quirk and Java int32 shifts (see batch_np.decode_ibm_float32)."""
    b = data.astype(jnp.int64)
    mantissa = (b[..., 0] << 24) | (b[..., 1] << 16) | (b[..., 2] << 8) | b[..., 3]
    mantissa = ((mantissa + (1 << 31)) % (1 << 32)) - (1 << 31)
    sign = mantissa & ~0x7FFFFFFF
    fracture = mantissa & 0x00FFFFFF
    exponent = jnp.where(sign != 0, -512, 0).astype(jnp.int64)

    is_zero = fracture == 0
    for _ in range(6):
        top = fracture & 0x00F00000
        shift = (top == 0) & ~is_zero
        fracture = jnp.where(shift, (fracture << 4) & 0xFFFFFFFF, fracture)
        exponent = jnp.where(shift, exponent - 4, exponent)
    top = fracture & 0x00F00000
    leading = (0x55AF >> (top >> 19)) & 3
    fracture = (fracture << leading) & 0xFFFFFFFF
    conv_exp = exponent + 131 - leading

    ieee = jnp.zeros(mantissa.shape, dtype=jnp.int64)
    normal = (conv_exp >= 0) & (conv_exp < 254)
    ieee = jnp.where(normal, sign + (conv_exp << 23) + fracture, ieee)
    inf = conv_exp > 254
    sub = (conv_exp < 0) & (conv_exp >= -32)
    sh = jnp.clip(-1 - conv_exp, 0, 62)
    mask = (~(jnp.asarray(-3, dtype=jnp.int64) << sh)) & 0xFFFFFFFF
    round_up = ((fracture & mask) > 0).astype(jnp.int64)
    conv_fract = ((fracture >> sh) + round_up) >> 1
    ieee = jnp.where(sub, sign + conv_fract, ieee)
    ieee = jnp.where(is_zero, 0, ieee)
    ieee = jnp.where(inf, 0x7F800000, ieee)

    u32 = (ieee & 0xFFFFFFFF).astype(jnp.uint32)
    return jax.lax.bitcast_convert_type(u32, jnp.float32), \
        jnp.ones(mantissa.shape, dtype=jnp.bool_)


def decode_ibm_float64(data: jnp.ndarray):
    acc = jnp.zeros(data.shape[:-1], dtype=jnp.uint64)
    for i in range(8):
        acc = (acc << 8) | data[..., i].astype(jnp.uint64)
    sign_bit = (acc >> 63) != 0
    fracture = (acc & 0x00FFFFFFFFFFFFFF).astype(jnp.int64)
    exponent = ((acc >> 54) & 0x1FC).astype(jnp.int64)

    is_zero = fracture == 0
    for _ in range(14):
        top = fracture & 0x00F0000000000000
        shift = (top == 0) & ~is_zero
        fracture = jnp.where(shift, fracture << 4, fracture)
        exponent = jnp.where(shift, exponent - 4, exponent)
    top = fracture & 0x00F0000000000000
    leading = (0x55AF >> (top >> 51)) & 3
    fracture = fracture << leading
    conv_exp = exponent + 765 - leading
    round_up = ((fracture & 0xB) > 0).astype(jnp.int64)
    conv_fract = ((fracture >> 2) + round_up) >> 1
    ieee = (conv_exp << 52) + conv_fract
    ieee_u = ieee.astype(jnp.uint64) | (sign_bit.astype(jnp.uint64) << 63)
    ieee_u = jnp.where(is_zero, jnp.uint64(0), ieee_u)
    # return raw IEEE754 bits; the host bitcasts after transfer (TPUs round
    # device-side f64 bitcasts through the emulation path)
    return ieee_u, jnp.ones(ieee_u.shape, dtype=jnp.bool_)


# ---------------------------------------------------------------------------
# strings
# ---------------------------------------------------------------------------

def lookup_segments(lut: np.ndarray):
    """A byte table as the fewest runs that are each one constant or one
    constant offset from the byte: [(first byte, slope 0|1, intercept)],
    table[b] == slope * b + intercept over the run. Greedy from the left
    is optimal: every part of a run is a run. `common` has 60 runs,
    cp1047_extended 147, cp875 (code points up to 8367) 94."""
    table = np.asarray(lut).astype(np.int64)
    segments = []
    lo = 0
    while lo < table.size:
        tail = table[lo:]
        constant = int(np.argmax(np.append(tail != tail[0], True)))
        offset = tail - np.arange(lo, table.size)
        shifted = int(np.argmax(np.append(offset != offset[0], True)))
        if constant >= shifted:
            segments.append((lo, 0, int(tail[0])))
        else:
            segments.append((lo, 1, int(offset[0])))
        lo += max(constant, shifted)
    return segments


def transcode_ebcdic(data: jnp.ndarray, lut_u16: np.ndarray,
                     dtype=jnp.uint16) -> jnp.ndarray:
    """uint8 bytes -> code points through `lut_u16`, a table known
    at trace time, without a gather (the TPU runs one element by
    element): one compare and one select per run of `lookup_segments`,
    on a constant that packs the run's slope and intercept, then one
    multiply-free finish. Element-wise, so XLA fuses it and GSPMD keeps
    the batch axis; exact for any table of code points that `dtype`
    holds (uint8 where the table's largest fits a byte)."""
    x = data.astype(jnp.int32)
    # intercepts lie in [-255, 65535]: biased by 256, slope in bit 0
    packed = None
    for lo, slope, intercept in lookup_segments(lut_u16):
        const = jnp.int32(((intercept + 256) << 1) | slope)
        packed = const if packed is None else jnp.where(x >= lo, const,
                                                        packed)
    out = (packed >> 1) - 256 + jnp.where((packed & 1) != 0, x, 0)
    return out.astype(dtype)


def mask_ascii(data: jnp.ndarray) -> jnp.ndarray:
    return jnp.where((data < 32) | (data >= 0x80),
                     jnp.uint8(0x20), data).astype(jnp.uint8)
