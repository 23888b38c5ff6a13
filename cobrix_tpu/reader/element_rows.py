"""Records of a variable array of variable arrays, decoded as two row kinds.

Under `variable_size_occurs` a record such as a customer with a counted
table of orders, each order with a counted table of lines, has no static
layout worth decoding: forty slots of the largest order a row. So a
shard's records are cut into rows of two kinds (plan/compiler.py,
`rows_of`), each decoded by its own cached program:

- the OWNER row, one a record: what lies before the array, then what lies
  behind it, [records, prefix + suffix];
- the ELEMENT row, one an element of the array: the element from its
  first byte, zero-padded to the largest element, behind the bytes of the
  record's prefix that its arrays depend on (where they do). Its plan is
  the element as a record, its own variable arrays regions laid out by the
  single-level expansion (ops/expand.py) as any such record's are.

`frame_elements` finds the elements: one step an outer slot over the
shard's records, reading only the counts (the outer one, then each
element's own), by the record walk's rules (reader.extractors): a count
outside its array's bounds, or one that does not decode, takes the
maximum. A record that ends inside what its counts say it holds, or whose
element count does not decode where an earlier element's did (the walk
then keeps the earlier value), is decoded by the record walk alone and
counted (`odo_nested_fallback_records`), never dropped.

`elements_table` builds the array's column as one list of structs: every
leaf of the element rows once over all elements, the structs' own lists
(`struct_list`) beneath them, wrapped by offsets from the outer counts;
the owner rows' table takes it in the array's place, and the walked
records' rows are put back in file order.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import NamedTuple, Optional, Tuple

import numpy as np

from .. import native
from ..copybook.copybook import Copybook
from ..obs.context import current as obs_current
from ..ops import batch_np
from ..ops.expand import clamped_counts, dependee_values
from ..plan.compiler import (VariableRegion, _DEPENDEE_KINDS,
                             _region_dependee_fault, array_of_arrays)
from ..profiling import Stage
from .extractors import DecodeOptions, extract_record


@dataclass(frozen=True)
class ElementRows:
    """How a record is cut into its two row kinds. `outer`: the array at
    its static place (`start`, the largest element `element_size`, its
    bounds) and its dependee as the owner plan reads it; `element_offset`:
    the record's prefix bytes an element row carries ahead of the element;
    `element_regions`: the element plan's regions, in element-row
    offsets; `suffix`: the static bytes behind the array."""

    outer: VariableRegion
    element_offset: int
    element_regions: Tuple[VariableRegion, ...]
    suffix: int


def route(copybook: Copybook, params, reason: Optional[str]) -> dict:
    """`var_len_reader.variable_occurs_route` for a copybook with an
    array of variable arrays: "elements" with the ElementRows, or "rows"
    and why the records are walked."""
    from ..plan.cache import cached_compile_plan

    outer, _ = array_of_arrays(copybook)
    if reason is None:
        if copybook.is_hierarchical:
            reason = "a hierarchical copybook is assembled record by record"
        elif not params.supports_fast_framing:
            reason = ("without RDW headers (or with a custom framing) a "
                      "record's length is only known by walking it")
        elif params.multisegment is not None:
            reason = (f"{outer.name} holds variable arrays in a "
                      "multisegment read")
        elif params.select or params.filter:
            reason = (f"{outer.name} holds variable arrays: select= and "
                      "filter= are applied by the record walk")
    layout, regions = None, {}
    if reason is None:
        owner, element = (cached_compile_plan(copybook, None,
                                              variable_size_occurs=True,
                                              rows_of=(kind, outer.name))
                          for kind in ("owner", "element"))
        start = outer.binary_properties.offset
        dep = next((c for c in owner.columns
                    if c.name == outer.depending_on and not c.slot_path),
                   None)
        fault = (None if dep is None
                 else _region_dependee_fault(dep, start, None))
        if dep is None:
            reason = (f"{outer.name} depends on {outer.depending_on}, "
                      "which the record does not hold before it")
        elif fault is not None:
            reason = (f"{outer.name} depends on {outer.depending_on}, "
                      f"which {fault}")
        elif element.row_path_reason is not None:
            reason = element.row_path_reason
        else:
            size = outer.binary_properties.data_size
            layout = ElementRows(
                outer=VariableRegion(
                    name=outer.name, depend_col=dep.index,
                    depend_offset=dep.offset, depend_width=dep.width,
                    depend_kind=_DEPENDEE_KINDS[dep.codec],
                    signed=dep.params.signed,
                    big_endian=dep.params.big_endian, start=start,
                    element_size=size, min_size=outer.array_min_size,
                    max_size=outer.array_max_size),
                element_offset=element.record_size - size,
                element_regions=element.regions,
                suffix=owner.record_size - start)
            regions = {"": [(layout.outer, dep.name)],
                       outer.name: [(r, element.columns[r.depend_col].name)
                                    for r in element.regions]}
    return {"route": "rows" if reason else "elements",
            "reason": reason or (f"{outer.name} holds variable arrays: an "
                                 "element is a row of its own"),
            "elements": layout,
            "regions": {key: [{"array": r.name, "depending_on": name,
                               "start": r.start,
                               "element_size": r.element_size,
                               "min": r.min_size, "max": r.max_size}
                              for r, name in rs]
                        for key, rs in regions.items()}}


class FramedElements(NamedTuple):
    """What `frame_elements` found in a shard: `whole` [n] the records
    cut into rows; for those, in file order, `counts` (their elements),
    `suffix_at` (where the part behind the array starts in the image);
    `element_at` / `element_len` [elements], record by record."""

    whole: np.ndarray
    counts: np.ndarray
    suffix_at: np.ndarray
    element_at: np.ndarray
    element_len: np.ndarray


def _bytes_at(buf: np.ndarray, at: np.ndarray, width: int) -> np.ndarray:
    """[len(at), width] bytes of `buf` from each of `at` (clipped to the
    image: a caller has already judged a row whose bytes it is not)."""
    at = np.clip(at, 0, max(buf.size - width, 0))
    return buf[at[:, None] + np.arange(width)]


def _counts(rows: np.ndarray, region: VariableRegion):
    values, valid = dependee_values(batch_np, rows, region)
    return clamped_counts(np, values, valid, region).astype(np.int64), valid


def frame_elements(buf: np.ndarray, offsets: np.ndarray,
                   lengths: np.ndarray, layout: ElementRows,
                   start_offset: int = 0) -> FramedElements:
    """The elements of a shard's records (module docstring): each record
    from `offsets` + `start_offset`, `lengths` long from `offsets`."""
    outer, eo = layout.outer, layout.element_offset
    base = offsets.astype(np.int64) + start_offset
    end = offsets.astype(np.int64) + lengths
    ok = end - base >= outer.start
    n = len(base)
    counts, _ = _counts(_bytes_at(buf, base + outer.depend_offset,
                                  outer.depend_width), outer)
    # a region whose count the record's prefix holds: read once a record
    from_prefix = {r.name: _counts(_bytes_at(buf, base + r.depend_offset,
                                             r.depend_width), r)[0]
                   for r in layout.element_regions if r.depend_offset < eo}
    registered = {r.name: np.zeros(n, dtype=bool)
                  for r in layout.element_regions if r.depend_offset >= eo}
    slots = int(counts[ok].max()) if ok.any() else 0
    at = np.zeros((n, slots), dtype=np.int64)
    size_of = np.zeros((n, slots), dtype=np.int64)
    pos = base + outer.start
    for k in range(slots):
        rows = np.flatnonzero(ok & (counts > k))
        here, stop = pos[rows], end[rows]
        size = np.full(len(rows), outer.element_size, dtype=np.int64)
        bad = np.zeros(len(rows), dtype=bool)
        shifts = []     # (region, bytes it gave up) for the regions met
        for r in layout.element_regions:
            if r.depend_offset < eo:
                count = from_prefix[r.name][rows]
            else:
                # where the dependee lies in the compact element: behind
                # the regions before it by what they gave up
                rel = r.depend_offset - eo - sum(
                    (gave for j, gave in shifts if j.end <= r.depend_offset),
                    np.zeros(len(rows), dtype=np.int64))
                dep_at = here + rel
                bad |= dep_at + r.depend_width > stop
                count, valid = _counts(
                    _bytes_at(buf, dep_at, r.depend_width), r)
                # an unreadable count keeps the value the walk read last
                # in the record: the expansion would take the maximum
                seen = registered[r.name]
                bad |= ~valid & seen[rows]
                seen[rows] |= valid
            gave = (r.max_size - count) * r.element_size
            shifts.append((r, gave))
            size -= gave
        bad |= here + size > stop
        ok[rows[bad]] = False
        at[rows, k], size_of[rows, k] = here, size
        pos[rows] = here + size
    ok &= pos + layout.suffix <= end
    whole = np.flatnonzero(ok)
    counts = counts[whole]
    shown = np.arange(slots)[None, :] < counts[:, None]
    return FramedElements(ok, counts, pos[whole], at[whole][shown],
                          size_of[whole][shown])


def _spliced(buf: np.ndarray, head_at: np.ndarray, head: int,
             tail_at: np.ndarray, tail_len: np.ndarray,
             extent: int) -> np.ndarray:
    """[n, extent] rows: `head` bytes from each of `head_at`, then up to
    `extent - head` of the `tail_len` bytes from each of `tail_at`,
    zeros behind."""
    if not head:
        return native.pack_records(buf, tail_at, tail_len, extent)
    rows = np.empty((len(head_at), extent), dtype=np.uint8)
    rows[:, :head] = native.pack_records(
        buf, head_at, np.full(len(head_at), head), head)
    if extent > head:
        rows[:, head:] = native.pack_records(buf, tail_at, tail_len,
                                             extent - head)
    return rows


class ShardRows(NamedTuple):
    """A shard read by element rows: the decoded owner and element
    batches, the outer counts, the records' places among the shard's
    (`kept`) and those the record walk takes (`walked`)."""

    owner: object
    elements: object
    counts: np.ndarray
    kept: np.ndarray
    walked: np.ndarray


def decode_shard(reader, data, offsets: np.ndarray, lengths: np.ndarray,
                 backend: str) -> ShardRows:
    """Frame, pack and decode one shard's records as element rows."""
    layout = reader.element_rows
    start = reader.params.start_offset
    buf = np.frombuffer(data, dtype=np.uint8) \
        if not isinstance(data, np.ndarray) else data
    with Stage("frame.elements"):
        framed = frame_elements(buf, offsets, lengths, layout, start)
    owner_dec = reader._element_rows_decoder("owner", backend)
    element_dec = reader._element_rows_decoder("element", backend)
    base = offsets[framed.whole].astype(np.int64) + start
    extent, eo = element_dec.plan.max_extent, layout.element_offset
    element_lengths = np.minimum(framed.element_len + eo, extent)
    with Stage("pack"):
        # the prefix, then the part behind the array; the element, behind
        # the prefix bytes its arrays' counts lie in
        owner = _spliced(buf, base, min(layout.outer.start,
                                        owner_dec.plan.max_extent),
                         framed.suffix_at, np.full(len(base), layout.suffix),
                         owner_dec.plan.max_extent)
        elements = _spliced(buf, np.repeat(base, framed.counts), eo,
                            framed.element_at, framed.element_len, extent)
    kept = np.flatnonzero(framed.whole)
    walked = np.flatnonzero(~framed.whole)
    obs = obs_current()
    if obs is not None and obs.device_stats is not None:
        obs.device_stats.note_nested(records=len(kept),
                                     elements=len(elements),
                                     fallback_records=len(walked))
    return ShardRows(owner_dec.decode(owner),
                     element_dec.decode(elements, lengths=element_lengths),
                     framed.counts, kept, walked)


def walk_rows(reader, data, offsets: np.ndarray, lengths: np.ndarray,
              positions, file_id: int, start_record_id: int,
              input_file_name: str) -> list:
    """The rows of the records at `positions` by the record walk."""
    params = reader.params
    options = DecodeOptions.from_copybook(reader.copybook)
    buf = memoryview(data)
    return [extract_record(
        reader.copybook.ast,
        bytes(buf[int(offsets[p]):int(offsets[p]) + int(lengths[p])]),
        offset_bytes=params.start_offset, policy=params.schema_policy,
        variable_length_occurs=True,
        generate_record_id=params.generate_record_id, file_id=file_id,
        record_id=start_record_id + int(p),
        generate_input_file_field=bool(params.input_file_name_column),
        input_file_name=input_file_name, options=options)
        for p in positions]


def elements_table(reader, shard: ShardRows, output_schema, walked_rows,
                   file_id: int, start_record_id: int, input_file_name: str,
                   corrupt_reasons: Optional[dict]):
    """The shard's Arrow table in file order (module docstring):
    `walked_rows`, the record walk's rows of `shard.walked`."""
    import pyarrow as pa

    from .arrow_out import nested_list, rows_to_table, segment_table
    from .result import _record_order_indices

    layout = reader.element_rows
    outer = next(st for st in reader.copybook.ast.walk()
                 if st.name == layout.outer.name)
    stats = shard.owner.stage_stats
    reasons = None
    if corrupt_reasons is not None:
        reasons = [corrupt_reasons.get(int(p)) for p in shard.kept]
        for p, row in zip(shard.walked, walked_rows):
            row.append(corrupt_reasons.get(int(p)))
    with Stage("assemble.table", stats):
        array = nested_list(shard.elements, outer, shard.counts)
        table = segment_table(
            shard.owner, None, output_schema, file_id=file_id,
            record_ids=start_record_id + shard.kept.astype(np.int64),
            seg_level_ids=None, input_file_name=input_file_name,
            corrupt_reasons=reasons, nested={id(outer): array})
    if not len(shard.walked):
        return table
    walked = rows_to_table(walked_rows, output_schema.schema)
    table = pa.concat_tables([table, walked])
    return table.take(_record_order_indices(
        np.concatenate([shard.kept, shard.walked])))
