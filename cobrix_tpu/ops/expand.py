"""Variable-size OCCURS records laid to the static decode program.

Under `variable_size_occurs` an OCCURS DEPENDING ON array takes
`count x element` bytes of its record and everything behind it moves
(reference VarOccursRecordExtractor; RecordExtractors.extractArray walks it
record by record). The batch decoders keep ONE static layout, every array
at its maximum size (plan/compiler.py), and bring the packed rows to it in
two passes over the `[rows, extent]` byte matrix:

1. the counts: each region's dependee, at its static offset once the
   regions before it are laid out, decoded by the batch kernel of its codec
   and clamped as the record walk clamps it (a count outside the array's
   bounds, or one that does not decode, takes the maximum);
2. the expansion: the bytes behind region k move right by
   `(max_k - count_k) x element_k`. The shift is a multiple of the element
   size below `max_k - min_k + 1`, so it is one static shift of the row a
   bit of `max_k - count_k` and a select on that bit: no gather. Slots
   past the count hold whatever lay there; the count gates them, as in the
   fixed-size layout.

A region is a `plan.compiler.VariableRegion` (its start, element size,
bounds and scope, and how to decode its dependee). One array-level
function over the array module: numpy with `batch_np` for
the host kernels, `jax.numpy` with `batch_jax` inside the device program
(scope `cobrix.expand`), so both execute the same algorithm and the scalar
record walk (`backend="host"`) stays independent of it.
"""
from __future__ import annotations

from typing import Sequence, Tuple


def region_counts(xp, kernels, rows, region):
    """Element count of `region` in every row of `rows` ([n, extent]
    uint8, the regions before it already laid out): the dependee by
    `kernels`' decoder of its codec (ops.batch_np or ops.batch_jax),
    then the record walk's clamp."""
    values, valid = dependee_values(
        kernels, rows[:, region.depend_offset:
                      region.depend_offset + region.depend_width], region)
    return clamped_counts(xp, values, valid, region)


def clamped_counts(xp, values, valid, region):
    """The record walk's clamp: a count outside the array's bounds, or
    one that does not decode, takes the maximum."""
    in_bounds = (valid & (values >= region.min_size)
                 & (values <= region.max_size))
    return xp.where(in_bounds, values, region.max_size).astype(xp.int32)


def dependee_values(kernels, slab, region):
    """(values, valid) of `region`'s dependee in the [n, width] bytes
    `slab`, by `kernels`' decoder of its codec."""
    if region.depend_kind == "binary":
        values, valid = kernels.decode_binary(slab, region.signed,
                                              region.big_endian)
    elif region.depend_kind == "bcd":
        values, valid = kernels.decode_bcd(slab)
    else:
        decode = (kernels.decode_display_ebcdic
                  if region.depend_kind == "display_ebcdic"
                  else kernels.decode_display_ascii)
        values, valid, _ = decode(slab, region.signed, False,
                                  require_digits=True)
    return values, valid


def expand_rows(xp, kernels, rows, regions: Sequence):
    """`rows` ([n, extent] uint8: each record from its first byte, zeros
    behind its last) with every region at its maximum size, and the
    counts read on the way ([n, regions] int32). The matrix keeps its
    shape: what a shift pushes past the extent (or past the region's
    scope) is beyond what the plan reads."""
    n, extent = rows.shape
    counts = []
    for region in regions:
        count = region_counts(xp, kernels, rows, region)
        counts.append(count)
        bound = extent if region.scope_end is None \
            else min(region.scope_end, extent)
        if region.end >= bound or not region.max_shift:
            continue  # nothing the plan reads lies behind the array
        missing = region.max_size - count
        # the window a shifted byte can come from or go to: from the
        # array's shortest end to the end of its scope
        tail = rows[:, region.end - region.max_shift:bound]
        width = tail.shape[1]
        for bit in range((region.max_size - region.min_size).bit_length()):
            amount = region.element_size << bit
            if amount >= width:
                shifted = xp.zeros_like(tail)
            else:
                shifted = xp.concatenate(
                    [xp.zeros((n, amount), dtype=tail.dtype),
                     tail[:, :width - amount]], axis=1)
            take = ((missing >> bit) & 1).astype(bool)
            tail = xp.where(take[:, None], shifted, tail)
        rows = xp.concatenate(
            [rows[:, :region.end], tail[:, region.max_shift:],
             rows[:, bound:]], axis=1)
    return rows, xp.stack(counts, axis=1)


def expanded_lengths(xp, lengths, counts, regions: Sequence,
                     extent: int) -> Tuple[object, object]:
    """(each row's length in the expanded layout, the bytes its shifts
    moved it by in all). A record's bytes behind region k lie
    `(max_k - count_k) x element_k` further right, so its end does too,
    but only if the record reaches the array's end (a string that
    starts right where its record ends is empty, not null: the end
    moves with the field): one that is truly short of its walked
    length stays a truncated row, by the rules the fixed-size layout
    has."""
    shifted = xp.zeros(lengths.shape, dtype=xp.int64)
    lengths = lengths.astype(xp.int64)
    for k, region in enumerate(regions):
        count = counts[:, k].astype(xp.int64)
        shift = (region.max_size - count) * region.element_size
        shifted = shifted + shift
        compact_end = region.start + count * region.element_size
        moved = lengths + shift
        if region.scope_end is not None:
            # the scope keeps its static size in the record: what lies
            # behind it never moved
            moved = xp.where(lengths < region.scope_end,
                             xp.minimum(moved, region.scope_end), lengths)
        lengths = xp.where(lengths >= compact_end, moved, lengths)
    return xp.minimum(lengths, extent), shifted
