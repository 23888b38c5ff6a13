"""Variable-length reader: framing + decode for RDW/length-field/text files,
multisegment filtering, Seg_Id generation, hierarchical assembly, and the
batched columnar path.

Mirrors the reference core reader semantics
(reader/VarLenNestedReader.scala:46: record extractor choice :60-79, RDW
header parser config :267, generateIndex :125-180, iterator choice :89;
reader/iterator/VarLenNestedIterator.scala:43-148;
reader/iterator/VarLenHierarchicalIterator.scala:43-162;
reader/iterator/SegmentIdAccumulator.scala:19-86) — but the decode plane is
columnar: records framed on the host are packed per active-segment into
padded `[batch, max_len]` blocks and decoded by the TPU kernels
(reader/columnar.py), with the per-record host walk kept as the oracle path.
"""
from __future__ import annotations

import time
from dataclasses import replace
from typing import Dict, Iterator, List, Optional, Sequence, Tuple

import numpy as np

from ..copybook.ast import Group, Primitive
from ..copybook.copybook import Copybook
from ..plan.cache import copybook_for_params, decoder_cache_for
from ..obs.context import count_pass, current as obs_current
from ..profiling import Stage, timed_stage
from .columnar import ColumnarDecoder, decoder_for_segment
from .extractors import (
    DecodeOptions,
    extract_hierarchical_record,
    extract_record,
)
from .header_parsers import (
    FixedLengthHeaderParser,
    RdwHeaderParser,
    RecordHeaderParser,
    create_record_header_parser,
)
from .index import FramedRecords, SparseIndexEntry, sparse_index_generator
from .parameters import (
    DEFAULT_FILE_RECORD_ID_INCREMENT,
    DEFAULT_INDEX_ENTRY_SIZE_MB,
    MEGABYTE,
    ReaderParameters,
)
from .result import FileResult, SegmentBatch
from .raw_extractors import (
    RawRecordContext,
    TextRecordExtractor,
    VarOccursRecordExtractor,
    create_raw_record_extractor,
)
from .stream import SimpleStream
from .vrl_reader import (
    SegmentIds,
    VRLRecordReader,
    decode_segment_id_bytes,
    resolve_segment_id_field,
)


class SegmentIdAccumulator:
    """Generates Seg_Id0..N values: root = `prefix_fileId_recordIndex`,
    children `<root>_L<level>_<counter>` (reference SegmentIdAccumulator)."""

    def __init__(self, segment_ids: Sequence[str], segment_id_prefix: str,
                 file_id: int):
        self._ids = [s.split(",") for s in segment_ids]
        self._count = len(segment_ids)
        self._acc = [0] * (self._count + 1)
        self._current_level = -1
        self._current_root = ""
        self.prefix = segment_id_prefix
        self.file_id = file_id

    def acquired_segment_id(self, segment_id: str, record_index: int) -> None:
        if self._count == 0:
            return
        level = None
        for i, ids in enumerate(self._ids):
            if segment_id in ids:
                level = i
                break
        if level is None:
            return
        self._current_level = level
        if level == 0:
            self._current_root = f"{self.prefix}_{self.file_id}_{record_index}"
            self._acc = [0] * len(self._acc)
        else:
            self._acc[level] += 1

    def get_segment_level_id(self, level: int) -> Optional[str]:
        if 0 <= level <= self._current_level:
            if level == 0:
                return self._current_root
            return f"{self._current_root}_L{level}_{self._acc[level]}"
        return None


def default_segment_id_prefix() -> str:
    return time.strftime("%Y%m%d%H%M%S")


# What a window of `VarLenReader.frame_index_fast` reads past the byte at
# which its cut is due: room for the record that closes the entry, at
# roots for the records up to the next root, and for the one record a
# window gives up. A window that finds no cut is walked again, longer.
INDEX_WINDOW_SLACK = MEGABYTE
# The records whose ids that pass decodes at a time where it looks for the
# root at which to cut: the rest of a window's ids wait for the shard.
ROOT_SEARCH_RECORDS = 4096


class _IndexCuts:
    """The split arithmetic of the vectorized sparse index, a cut a call,
    shared by `generate_index_fast` (the whole file's records at once)
    and `frame_index_fast` (the records walked so far). Split semantics
    (including the invalid-record counting and size-drift quirks) mirror
    sparse_index_generator exactly — pinned by tests against it.
    `split_mb`: the split `index.index_split` set, over the options'."""

    def __init__(self, params: ReaderParameters, split_mb=None):
        self.per = params.input_split_records
        self.mb = ((split_mb or params.input_split_size_mb
                    or DEFAULT_INDEX_ENTRY_SIZE_MB) * MEGABYTE)
        # the file-header region is consumed as one counted invalid record
        # (IndexGenerator.scala:117-120 counts unconditionally)
        self.base = 1 if params.file_start_offset > 0 else 0
        self.subtracted = 0
        self.chunk_start_counted = 0
        # a first-chunk split at record 0 is possible (header counted)
        self.last = -1

    def next_cut(self, starts: np.ndarray, first: int, next_root,
                 last_candidate: int) -> Optional[int]:
        """The record that opens the next entry, or None where none up
        to `last_candidate` does. `starts[k]` is the byte at which
        record `first + k` starts (its RDW header), `first` no later
        than the last cut; `next_root(i)` is the first record from `i`
        on that may open an entry (a root, where shards are cut at
        roots), or None."""
        if self.per is not None:
            cand = self.chunk_start_counted + self.per - self.base
        else:
            target = self.subtracted + self.mb
            cand = first + int(np.searchsorted(starts, target, side="left"))
        split_at = next_root(max(cand, self.last + 1))
        if split_at is None or split_at > last_candidate:
            return None
        if self.per is not None:
            self.chunk_start_counted = split_at + self.base
        else:
            self.subtracted += self.mb
        self.last = split_at
        return split_at


def _segment_level_ids_vectorized(segment_ids: Sequence[str],
                                  level_defs: Sequence[str], prefix: str,
                                  file_id: int, start_record_id: int):
    """Vectorized SegmentIdAccumulator over a framed shard: Seg_Id0..N as
    per-level columns with the exact state semantics of the per-record
    accumulator (forward-filled current level/root, per-level counters
    reset at roots, empty root prefix before the first root). Returns
    (SegLevelColumns, no_match_yet_mask)."""
    from .result import SegLevelColumns

    n = len(segment_ids)
    level_lists = [s.split(",") for s in level_defs]
    level_count = len(level_lists)
    sid_level = {}
    for i, ids in enumerate(level_lists):
        for sid in ids:
            sid_level.setdefault(sid, i)
    if isinstance(segment_ids, SegmentIds):
        # one level lookup per DISTINCT id, broadcast by the codes
        lvl_uniq = np.asarray([sid_level.get(u, -1)
                               for u in segment_ids.uniq], dtype=np.int32)
        lvl = (lvl_uniq[segment_ids.codes] if len(lvl_uniq)
               else np.full(n, -1, dtype=np.int32))
    else:
        get_level = sid_level.get
        lvl = np.fromiter((get_level(s, -1) for s in segment_ids),
                          dtype=np.int32, count=n)

    # int32 state: the plane is memory-bandwidth bound; only root_rid
    # widens to int64 at the end. Explicit bound instead of silent wrap
    if n >= 2 ** 31:
        raise ValueError(
            f"shard of {n} records exceeds the 2^31 seg-id plane bound; "
            "split the input (hosts/input_split options)")
    idx = np.arange(n, dtype=np.int32)
    # forward-filled current level (last matched record's level; -1 = none)
    last_match = np.where(lvl >= 0, idx, np.int32(-1))
    np.maximum.accumulate(last_match, out=last_match)
    cur_level = np.where(last_match >= 0, lvl[np.maximum(last_match, 0)], -1)
    no_match_yet = last_match < 0
    # forward-filled root position (-1 before the first root: the
    # accumulator's empty pre-root prefix)
    root_pos = np.where(lvl == 0, idx, np.int32(-1))
    np.maximum.accumulate(root_pos, out=root_pos)
    root_rid = np.where(root_pos >= 0,
                        start_record_id + root_pos.astype(np.int64),
                        np.int64(-1))

    # per-level child counters (cumulative count since the current root)
    counters: List[Optional[np.ndarray]] = [None]
    for k in range(1, level_count):
        c = np.cumsum(lvl == k, dtype=np.int32)
        at_root = np.where(root_pos >= 0, c[np.maximum(root_pos, 0)],
                           np.int32(0))
        counters.append(c - at_root)
    valids = [cur_level >= k for k in range(level_count)]
    coded = dict(root_rid=root_rid, counters=counters, valids=valids,
                 prefix=f"{prefix}_{file_id}_", level_count=level_count)
    return SegLevelColumns(coded=coded), no_match_yet


def variable_occurs_route(copybook: Copybook,
                          params: ReaderParameters) -> Optional[dict]:
    """How a read decodes records whose DEPENDING ON arrays take their
    count's size (`variable_size_occurs`); None where the option is off
    or the copybook has no such array. `route` "batched": the plans name
    the arrays as regions and the decoders lay the rows to the static
    layout in batches (ops/expand.py), on every backend; "rows": the
    host walks every record, and `reason` says why (plan/compiler.py
    says which layouts lay out; the file has to be RDW-framed and not
    hierarchical); "elements": a variable array whose elements hold
    variable arrays, its records cut into two row kinds
    (reader/element_rows.py; `elements` the ElementRows). `regions`:
    active segment redefine ("" for the rows under none; for "elements",
    the array's name for its element's regions) -> its plan's regions."""
    from ..plan.cache import cached_compile_plan
    from ..plan.compiler import array_of_arrays
    from . import element_rows

    if not params.variable_size_occurs or not any(
            st.is_array and st.depending_on is not None
            for st in copybook.ast.walk()):
        return None
    outer, reason = array_of_arrays(copybook)
    if outer is not None:
        return element_rows.route(copybook, params, reason)
    seg = params.multisegment
    actives = {""} | set((seg.segment_id_redefine_map or {}).values()
                         if seg else ())
    plans = {active: cached_compile_plan(
                 copybook, active or None, select=params.select,
                 variable_size_occurs=True)
             for active in sorted(actives)}
    reason = None
    if copybook.is_hierarchical:
        reason = "a hierarchical copybook is assembled record by record"
    elif not params.supports_fast_framing:
        reason = ("without RDW headers (or with a custom framing) a "
                  "record's length is only known by walking it")
    else:
        seg_field = resolve_segment_id_field(params, copybook)
        for plan in plans.values():
            if plan.row_path_reason is not None:
                reason = plan.row_path_reason
            elif seg_field is not None and any(
                    r.start <= seg_field.binary_properties.offset
                    for r in plan.regions):
                reason = (f"the segment id field {seg_field.name} lies "
                          "behind a variable array")
            if reason is not None:
                break
    return {"route": "rows" if reason else "batched", "reason": reason,
            "elements": None,
            "regions": {active: [
                {"array": r.name,
                 "depending_on": plan.columns[r.depend_col].name,
                 "start": r.start, "element_size": r.element_size,
                 "min": r.min_size, "max": r.max_size}
                for r in plan.regions] for active, plan in plans.items()}}


def hierarchy_maps(copybook: Copybook, params: ReaderParameters):
    """(segment id -> redefine group, parent -> child groups, root group
    names) of a hierarchical read: one source for the scalar walk, the
    columnar assembly and `hierarchical_route`, so that they cannot
    disagree on the hierarchy."""
    seg = params.multisegment
    redefine_map = seg.segment_id_redefine_map if seg else {}
    segment_redefines = {g.name: g
                         for g in copybook.get_all_segment_redefines()}
    sid_map = {sid: segment_redefines[name]
               for sid, name in redefine_map.items()
               if name in segment_redefines}
    parent_child_map = copybook.get_parent_children_segment_map()
    root_names = {g.name for g in segment_redefines.values()
                  if g.parent_segment is None}
    return sid_map, parent_child_map, root_names


def hierarchical_route(copybook: Copybook,
                       params: ReaderParameters) -> Optional[dict]:
    """How a read assembles a hierarchical copybook's rows (`segment-
    children`); None for any other copybook. `route` "columnar": one
    decode-once batch a shard and `hierarchical_table`'s array assembly;
    "batched_rows": the batch's values with the nesting walked record by
    record (the table declined, `decline_reason`); "rows": the scalar
    record walk, values and all (`_hierarchical_columnar_setup` declined).
    `reason` says why a read is not "columnar". What the walks assemble is
    counted in `hier_row_path_roots`."""
    from .hierarchical_arrow import decline_reason

    if not copybook.is_hierarchical:
        return None
    route, reason = "rows", None
    if params.variable_size_occurs:
        reason = "variable_size_occurs records are walked one by one"
    elif resolve_segment_id_field(params, copybook) is None:
        reason = "no segment id field is resolved"
    elif params.select:
        # the scalar oracle ignores column projection; a projected
        # columnar decode would silently change hierarchical rows
        reason = "a select= projection is applied by the record walk"
    elif params.start_offset:
        # the oracle reads CHILD records at the field's plain offset,
        # without the record start offset (extract_children / reference
        # extractChildren) — the uniform decode_raw shift cannot
        # reproduce that
        reason = "record_start_offset shifts root records only"
    elif not params.supports_fast_framing:
        reason = ("without RDW headers (or with a custom framing) a "
                  "record's length is only known by walking it")
    else:
        reason = decline_reason(copybook, *hierarchy_maps(copybook, params))
        route = "batched_rows" if reason else "columnar"
    return {"route": route, "reason": reason}


class VarLenReader:
    """Core variable-length reader bound to one copybook + parameters."""

    def __init__(self, copybook_contents, params: ReaderParameters):
        seg = params.multisegment
        # fingerprint-keyed parse cache (plan/cache.py): repeated scans of
        # the same copybook/options share the Copybook object and its
        # compiled plans/decoders — per-chunk pipeline decodes never
        # re-derive them
        self.copybook = copybook_for_params(copybook_contents, params)
        # stable copybook identity for the persisted sparse-index key
        # (io.index_store): survives process restarts, unlike id()
        from ..plan.cache import parse_fingerprint

        self.copybook_fingerprint = parse_fingerprint(copybook_contents,
                                                      params)
        self.params = params
        self.segment_redefine_map = dict(
            seg.segment_id_redefine_map) if seg else {}
        self._decoders: Dict[str, ColumnarDecoder] = \
            decoder_cache_for(self.copybook)
        # predicate pushdown (query/pushdown.py): bound once per reader,
        # shared (with its counters) by every shard/chunk of the read
        from ..query.pushdown import BoundFilter

        self.pushdown = BoundFilter.build(params.filter, self.copybook,
                                          params)
        # variable-size OCCURS: the records hold each DEPENDING ON array
        # at its count's size. The plans name the arrays as regions and
        # the decoders lay the rows to the static layout in batches
        # (ops/expand.py); `row_path_reason` says why a layout, or the
        # way the file is framed, leaves the records to the host's walk
        # (`dynamic_occurs_layout`)
        route = variable_occurs_route(self.copybook, params)
        self.variable_arrays = route is not None
        self.row_path_reason = (route["reason"]
                                if route and route["route"] == "rows"
                                else None)
        # an array of variable arrays: its records are cut into owner and
        # element rows (reader/element_rows.py)
        self.element_rows = route["elements"] if route else None
        # a hierarchical copybook: assembled in columns, or left to one
        # of the record walks, and why (`hier_row_path_roots`)
        self.hier_route = hierarchical_route(self.copybook, params)

    @property
    def dynamic_occurs_layout(self) -> bool:
        """Whether the records' variable arrays leave them to the host's
        record walk (`row_path_reason` says why)."""
        return self.row_path_reason is not None

    # -- plumbing ----------------------------------------------------------

    def record_extractor(self, starting_record_number: int,
                         stream: SimpleStream):
        """reference VarLenNestedReader.recordExtractor (:60-79)."""
        ctx = RawRecordContext(starting_record_number, stream, self.copybook,
                               self.params.re_additional_info)
        if self.params.record_extractor:
            return create_raw_record_extractor(self.params.record_extractor, ctx)
        if self.params.is_text:
            return TextRecordExtractor(ctx)
        if self.params.variable_size_occurs \
                and not self.params.is_record_sequence \
                and not self.params.length_field_name:
            return VarOccursRecordExtractor(ctx)
        return None

    def record_header_parser(self) -> RecordHeaderParser:
        """reference VarLenNestedReader.getDefaultRecordHeaderParser (:267)."""
        if self.params.record_header_parser:
            parser = create_record_header_parser(
                self.params.record_header_parser,
                record_size=self.copybook.record_size,
                file_header_bytes=self.params.file_start_offset,
                file_footer_bytes=self.params.file_end_offset,
                rdw_adjustment=self.params.rdw_adjustment)
        elif self.params.is_record_sequence:
            adjustment = self.params.rdw_adjustment
            if self.params.is_rdw_part_of_record_length:
                adjustment -= 4
            parser = RdwHeaderParser(self.params.is_rdw_big_endian,
                                     self.params.file_start_offset,
                                     self.params.file_end_offset,
                                     adjustment)
        else:
            # record_length override wins over the copybook size (same
            # semantics as FixedLenReader.record_size: the override is the
            # full on-disk record, offsets not re-added)
            record_size = (self.params.record_length_override
                           or self.copybook.record_size
                           + self.params.start_offset
                           + self.params.end_offset)
            parser = FixedLengthHeaderParser(
                record_size,
                self.params.file_start_offset, self.params.file_end_offset)
        if self.params.rhp_additional_info is not None:
            parser.on_receive_additional_info(self.params.rhp_additional_info)
        return parser

    # -- index -------------------------------------------------------------

    def _index_split_config(self):
        """Validated (records_per_entry, size_mb) + root-boundary config
        (reference VarLenNestedReader.generateIndex :125-180: splits align
        to root-segment boundaries whenever Seg_Id generation or a
        parent-child segment map is requested, so per-shard Seg_Id
        accumulators restart exactly at a root)."""
        params = self.params
        if params.input_split_records is not None and not (
                1 <= params.input_split_records <= 1_000_000_000):
            raise ValueError(
                "Invalid input split size. The requested number of records "
                f"is {params.input_split_records}.")
        if params.input_split_size_mb is not None and not (
                1 <= params.input_split_size_mb <= 2000):
            raise ValueError(
                f"Invalid input split size of {params.input_split_size_mb} MB.")
        seg = params.multisegment
        is_hierarchical = bool(seg and (seg.segment_level_ids
                                        or seg.field_parent_map))
        root_segment_id = ""
        if seg:
            if seg.field_parent_map and self.segment_redefine_map:
                # every root id is a valid split boundary (multi-root files,
                # reference Test12MultiRootSparseIndex)
                root_segment_id = ",".join(self.copybook.get_root_segment_ids(
                    self.segment_redefine_map, seg.field_parent_map))
            elif seg.segment_level_ids:
                root_segment_id = seg.segment_level_ids[0]
        return is_hierarchical, root_segment_id

    def generate_index(self, stream: SimpleStream, file_id: int
                       ) -> List[SparseIndexEntry]:
        """reference VarLenNestedReader.generateIndex (:125-180)."""
        params = self.params
        seg_field = resolve_segment_id_field(params, self.copybook)
        is_hierarchical, root_segment_id = self._index_split_config()
        return sparse_index_generator(
            file_id,
            stream,
            record_header_parser=self.record_header_parser(),
            record_extractor=self.record_extractor(0, stream),
            records_per_index_entry=params.input_split_records,
            size_per_index_entry_mb=params.input_split_size_mb,
            copybook=self.copybook,
            segment_field=seg_field,
            is_hierarchical=is_hierarchical,
            root_segment_id=root_segment_id,
            record_error_policy=params.record_error_policy,
            resync_window_bytes=params.resync_window_bytes)

    def generate_index_fast(self, data, file_id: int, split_mb=None
                            ) -> Optional[List[SparseIndexEntry]]:
        """Vectorized sparse index for plain RDW files: one native scan of
        the file image + split arithmetic over the offset arrays instead of
        the per-record Python pass. Returns None when the configuration
        needs the generic generator (custom extractors/parsers, text mode,
        length fields). Split semantics (including the
        invalid-record counting and size-drift quirks) mirror
        sparse_index_generator exactly — pinned by tests against it.
        `split_mb`: the split `index.index_split` set, over the options'."""
        from .. import native

        if not self.supports_fast_framing:
            return None
        p = self.params
        adjustment = self._rdw_length_adjustment()
        # every header of the file image read once, on this one thread
        with Stage("plan_index.scan"):
            if p.is_permissive:
                # same skip decisions as the shard scan so split offsets
                # land on records the shard framers will actually find;
                # the ledger here is a throwaway (the decode pass records
                # the incidents)
                from .recovery import rdw_scan_permissive

                offsets, lengths, _ = rdw_scan_permissive(
                    data, p.is_rdw_big_endian, adjustment,
                    p.file_start_offset, p.file_end_offset,
                    p.record_error_policy, p.resync_window_bytes,
                    p.new_diagnostics())
            else:
                offsets, lengths = native.rdw_scan(
                    data, p.is_rdw_big_endian, adjustment,
                    p.file_start_offset, p.file_end_offset)
        n = len(offsets)
        starts = offsets - 4  # RDW header precedes the payload

        is_hierarchical, root_segment_id = self._index_split_config()
        seg_field = resolve_segment_id_field(p, self.copybook)
        root_indices: Optional[np.ndarray] = None
        if is_hierarchical and seg_field is not None:
            root_ids = set(root_segment_id.split(","))
            with Stage("plan_index.seg_ids"):
                sids = self._segment_ids_vectorized(data, offsets, lengths,
                                                    seg_field)
            root_indices = np.nonzero(sids.mask_of(root_ids))[0]

        def next_root(i: int) -> Optional[int]:
            if root_indices is None:
                return i
            k = np.searchsorted(root_indices, i, side="left")
            if k >= len(root_indices):
                return None
            return int(root_indices[k])

        cuts = _IndexCuts(p, split_mb)
        entries = [SparseIndexEntry(0, -1, file_id, 0)]
        # processing the last record ends the stream before the split check
        # (IndexGenerator loop order) — unless a footer region follows it,
        # which is consumed as one more counted iteration
        last_candidate = n - 1 if p.file_end_offset > 0 else n - 2
        while (split_at := cuts.next_cut(starts, 0, next_root,
                                         last_candidate)) is not None:
            entries[-1] = replace(entries[-1],
                                  offset_to=int(starts[split_at]))
            entries.append(SparseIndexEntry(
                int(starts[split_at]), -1, file_id, split_at + cuts.base))
        return entries

    def _rdw_length_adjustment(self) -> int:
        p = self.params
        return p.rdw_adjustment - (4 if p.is_rdw_part_of_record_length
                                   else 0)

    def _rdw_walk(self, data, file_header: int, file_footer: int,
                  seg_field: Optional[Primitive]):
        """The strict RDW scan of a file image or a part of one that
        starts at a record: (offsets, lengths, seg_bytes). With a segment
        id field, the fused frame + segment-id gather: one native walk
        emits the record table AND each record's id-field bytes,
        replacing rdw_scan + a whole-file pack_records re-walk
        (`seg_bytes` None = no field, or no native library)."""
        from .. import native

        p = self.params
        adjustment = self._rdw_length_adjustment()
        if seg_field is not None:
            fused = native.rdw_scan_segids(
                data, p.is_rdw_big_endian,
                p.start_offset + seg_field.binary_properties.offset,
                seg_field.binary_properties.actual_size,
                adjustment, file_header, file_footer)
            if fused is not None:
                count_pass("fused_frame")
                return fused
        offsets, lengths = native.rdw_scan(
            data, p.is_rdw_big_endian, adjustment, file_header, file_footer)
        return offsets, lengths, None

    def mean_record_length(self, head, whole: bool) -> Optional[float]:
        """Mean payload length by the RDW headers of `head`, the first
        bytes of a file (`whole`: all of them): the density that
        `index.preframed_route` and `index.index_split` read. None where
        the walk finds no whole
        record or a header it cannot follow (the index pass says which)."""
        from .. import native

        p = self.params
        try:
            _, lengths = native.rdw_scan(
                head, p.is_rdw_big_endian, self._rdw_length_adjustment(),
                p.file_start_offset, p.file_end_offset if whole else 0)
        except ValueError:
            return None
        if not whole:
            lengths = lengths[:-1]  # cut short where the head ends
        return float(lengths.mean()) if len(lengths) else None

    def frame_index_fast(self, data, file_id: int, split_mb=None
                         ) -> Iterator[Tuple[SparseIndexEntry, FramedRecords]]:
        """`generate_index_fast`'s entries, each with the tables of its
        records, each yielded as soon as its cut is known: the index pass
        as the one framing of a file (`index.preframed_route` says of
        which). The file image is walked in windows by the scan that
        `_frame_fast` gives a shard (one native walk for the record table
        and the id bytes), each from the last cut to INDEX_WINDOW_SLACK
        past where the next is due, so a table is a slice of one window's
        arrays and what is walked twice is the slack. A read is as long
        as this pass and its last shard, so the pass does only what a
        cut waits for: the ids are decoded where a root is looked for,
        ROOT_SEARCH_RECORDS at a time, and coded whole by the shard's
        own thread. Not for a permissive policy, nor where
        `supports_fast_framing` is False. `split_mb` as in
        `generate_index_fast`."""
        from .. import native

        p = self.params
        buf = np.frombuffer(data, dtype=np.uint8)
        size = buf.size
        body_end = (size - p.file_end_offset
                    if 0 < p.file_end_offset < size else size)
        seg_field = resolve_segment_id_field(p, self.copybook)
        is_hierarchical, root_segment_id = self._index_split_config()
        root_ids = (set(root_segment_id.split(","))
                    if is_hierarchical and seg_field is not None else None)
        cuts = _IndexCuts(p, split_mb)
        # the open entry: its first byte, and its first record's number
        # in the file and in the index
        opened, first, record_index = 0, 0, 0
        record_bytes = 0.0  # a record of the last window; none yet: 0
        span = 0
        while True:
            if cuts.per is None:
                due = cuts.subtracted + cuts.mb - opened
            else:
                due = (cuts.chunk_start_counted + cuts.per - cuts.base
                       - first) * record_bytes
            span = max(max(int(due), 0) + INDEX_WINDOW_SLACK, 2 * span)
            final = opened + span >= body_end
            window = buf[opened:] if final else buf[opened:opened + span]
            header = p.file_start_offset if opened == 0 else 0
            footer = p.file_end_offset if final else 0
            try:
                with Stage("plan_index.scan"):
                    offsets, lengths, seg_bytes = self._rdw_walk(
                        window, header, footer, seg_field)
            except ValueError:
                # a header the walk cannot follow, at a place counted from
                # the window's start: the walk of the whole image, which
                # is generate_index_fast's, meets it and says where
                native.rdw_scan(data, p.is_rdw_big_endian,
                                self._rdw_length_adjustment(),
                                p.file_start_offset, p.file_end_offset)
                raise
            if not final:
                # the window may end inside its last record: the next
                # window, or this one walked again, reads it whole
                offsets, lengths = offsets[:-1], lengths[:-1]
                if seg_bytes is not None:
                    seg_bytes = seg_bytes[:-1]
            n = len(offsets)
            starts = offsets + (opened - 4)  # RDW header precedes the payload
            window_at, window_first = opened, first

            def next_root(i: int) -> Optional[int]:
                if root_ids is None:
                    return i
                for k in range(i - window_first, n, ROOT_SEARCH_RECORDS):
                    to = k + ROOT_SEARCH_RECORDS
                    with Stage("plan_index.seg_ids"):
                        ids = self._segment_ids_vectorized(
                            window, offsets[k:to], lengths[k:to], seg_field,
                            field_bytes=(None if seg_bytes is None
                                         else seg_bytes[k:to]))
                    roots = np.nonzero(ids.mask_of(root_ids))[0]
                    if len(roots):
                        return window_first + k + int(roots[0])
                return None

            def framed(a: int, b: int) -> FramedRecords:
                # offsets from the entry's first byte: the window's, or
                # the header of the record that opened it
                shift = int(starts[a]) - window_at if a else 0
                return FramedRecords(
                    offsets[a:b] - shift if shift else offsets[a:b],
                    lengths[a:b],
                    None if seg_bytes is None else seg_bytes[a:b])

            # every record of a window that gave one up may open an
            # entry; in the file's last window the rule of
            # generate_index_fast holds
            last_candidate = first + n - 1
            if final and p.file_end_offset <= 0:
                last_candidate -= 1
            a = 0
            while (split_at := cuts.next_cut(starts, first, next_root,
                                             last_candidate)) is not None:
                b = split_at - first
                cut = int(starts[b])
                yield (SparseIndexEntry(opened, cut, file_id, record_index),
                       framed(a, b))
                a, opened, record_index = b, cut, split_at + cuts.base
            if final:
                yield (SparseIndexEntry(opened, -1, file_id, record_index),
                       framed(a, n))
                return
            first += a
            if n > 1:
                record_bytes = (int(starts[-1]) - int(starts[0])) / (n - 1)
            if a:
                span = 0  # the entry now open gets a window of its own

    # -- framing -----------------------------------------------------------

    def make_record_reader(self, stream: SimpleStream,
                           start_record_id: int = 0,
                           starting_file_offset: int = 0,
                           ledger=None) -> VRLRecordReader:
        """The per-record framing iterator (policy-aware; `ledger` carries
        the error ledger across shards of one read)."""
        return VRLRecordReader(
            self.copybook, stream, self.params, self.record_header_parser(),
            self.record_extractor(start_record_id, stream),
            start_record_id, starting_file_offset, ledger=ledger)

    def frame_records(self, stream: SimpleStream, start_record_id: int = 0,
                      starting_file_offset: int = 0, ledger=None
                      ) -> Iterator[Tuple[int, str, bytes]]:
        """Yield (record_index, segment_id, record_bytes)."""
        reader = self.make_record_reader(stream, start_record_id,
                                         starting_file_offset, ledger)
        while reader.has_next():
            index = reader.record_index + 1
            segment_id, data = next(reader)
            yield index, segment_id, data

    # -- row iteration (host oracle path) -----------------------------------

    def iter_rows(self, stream: SimpleStream, file_id: int = 0,
                  start_record_id: int = 0, starting_file_offset: int = 0,
                  segment_id_prefix: Optional[str] = None,
                  ledger=None,
                  corrupt_reasons_out: Optional[dict] = None
                  ) -> Iterator[List[object]]:
        if self.copybook.is_hierarchical:
            # hierarchical assemblies carry no per-row corruption
            # attribution (the ledger still records every incident)
            yield from self._iter_rows_hierarchical(
                stream, file_id, start_record_id, starting_file_offset,
                ledger=ledger)
            return
        params = self.params
        seg = params.multisegment
        prefix = segment_id_prefix or default_segment_id_prefix()
        accumulator = (SegmentIdAccumulator(seg.segment_level_ids, prefix, file_id)
                       if seg else None)
        level_count = len(seg.segment_level_ids) if seg else 0
        segment_filter = set(seg.segment_id_filter) if seg and seg.segment_id_filter else None
        options = DecodeOptions.from_copybook(self.copybook)
        generate_input_file = bool(params.input_file_name_column)

        record_reader = self.make_record_reader(
            stream, start_record_id, starting_file_offset, ledger)
        row_position = 0
        while record_reader.has_next():
            record_index = record_reader.record_index + 1
            segment_id, data = next(record_reader)
            level_ids: List[Optional[str]] = []
            if level_count and accumulator is not None:
                accumulator.acquired_segment_id(segment_id, record_index)
                level_ids = [accumulator.get_segment_level_id(i)
                             for i in range(level_count)]
            if level_ids and level_ids[0] is None:
                continue  # before the first root segment
            if segment_filter is not None and segment_id not in segment_filter:
                continue
            if corrupt_reasons_out is not None:
                # the reader ledgers a kept-malformed record during its
                # prefetch, so the entry exists by the time it is emitted
                reason = record_reader.corrupt_reasons.get(record_index)
                if reason is not None:
                    corrupt_reasons_out[row_position] = reason
            row_position += 1
            active_redefine = self.segment_redefine_map.get(segment_id, "")
            yield extract_record(
                self.copybook.ast,
                data,
                offset_bytes=params.start_offset,
                policy=params.schema_policy,
                variable_length_occurs=params.variable_size_occurs,
                generate_record_id=params.generate_record_id,
                segment_level_ids=level_ids,
                file_id=file_id,
                record_id=record_index,
                active_segment_redefine=active_redefine,
                generate_input_file_field=generate_input_file,
                input_file_name=stream.input_file_name,
                options=options)

    def _iter_rows_hierarchical(self, stream: SimpleStream, file_id: int,
                                start_record_id: int,
                                starting_file_offset: int,
                                ledger=None) -> Iterator[List[object]]:
        """Buffer one root record plus its children, then assemble
        (reference VarLenHierarchicalIterator.fetchNext :99)."""
        params = self.params
        segment_id_redefine_map, parent_child_map, root_names = \
            hierarchy_maps(self.copybook, params)
        options = DecodeOptions.from_copybook(self.copybook)
        generate_input_file = bool(params.input_file_name_column)

        buffer: List[Tuple[str, bytes]] = []
        root_record_index = 0

        def flush():
            return extract_hierarchical_record(
                self.copybook.ast,
                buffer,
                segment_id_redefine_map,
                parent_child_map,
                offset_bytes=params.start_offset,
                policy=params.schema_policy,
                variable_length_occurs=params.variable_size_occurs,
                generate_record_id=params.generate_record_id,
                file_id=file_id,
                record_id=root_record_index,
                generate_input_file_field=generate_input_file,
                input_file_name=stream.input_file_name,
                options=options)

        # Record_Id parity quirk: the reference's hierarchical iterator
        # stamps each assembled row with the raw record index of the record
        # that TRIGGERS the flush — the next root (or the total record
        # count at end of stream), VarLenHierarchicalIterator.scala:99-135
        last_index = start_record_id - 1
        for record_index, segment_id, data in self.frame_records(
                stream, start_record_id, starting_file_offset,
                ledger=ledger):
            redefine = segment_id_redefine_map.get(segment_id)
            is_root = redefine is not None and redefine.name in root_names
            if is_root:
                if buffer:
                    root_record_index = record_index
                    yield flush()
                buffer = [(segment_id, data)]
            elif buffer:
                buffer.append((segment_id, data))
            last_index = record_index
        if buffer:
            root_record_index = last_index + 1
            yield flush()

    def _hierarchical_columnar_setup(self, stream: SimpleStream,
                                     backend: str,
                                     ledger=None,
                                     stage_times=None) -> Optional[dict]:
        """Frame + decode-once setup shared by the hierarchical row and
        Arrow paths. Returns None when the configuration needs the
        generic scalar path (`hierarchical_route` says why) — every bail
        happens BEFORE framing consumes the stream, so the caller's
        fallback can still read it."""
        if self.hier_route["route"] == "rows":
            return None
        fast = self._frame_fast(stream, ledger=ledger,
                                stage_times=stage_times)
        # both guaranteed by the route: it leaves a read without fast
        # framing or without a segment id field to the scalar walk
        assert fast is not None
        data, _base, offsets, rec_lengths, segment_ids, _reasons = fast
        assert segment_ids is not None
        n = len(offsets)

        sid_map, parent_child_map, root_names = hierarchy_maps(
            self.copybook, self.params)
        name_of_sid = {sid: g.name for sid, g in sid_map.items()}
        # per-redefine row masks: a redefine's columns are read only on
        # its own segment's records, so whole-column materialization (and
        # the truncation fixups of OTHER segments' shorter records) is
        # skipped outside the mask
        seg_masks = {name: segment_ids.mask_of_mapped(name_of_sid, name)
                     for name in {g.name for g in sid_map.values()}}
        # dictionary-coded segment names: one name per DISTINCT sid plus
        # the int32 code vector — the Arrow assembly's membership tests
        # run on the codes, never on per-row Python strings
        uniq_named = [name_of_sid.get(u) for u in segment_ids.uniq]
        segment_names = (uniq_named, segment_ids.codes)
        decoder = self._decoder_for_segment("", backend)
        # masked decode: each segment's numeric groups run only on its
        # own rows (hidden rows come back invalid, which the assembly and
        # the nesting walk treat exactly like the garbage they replace)
        with timed_stage(stage_times, "decode"):
            batch = (decoder.decode_raw(data, offsets, rec_lengths,
                                        segment_row_masks=seg_masks) if n
                     else None)
        root_uniq = np.asarray([nm in root_names for nm in uniq_named])
        n_roots = (int(root_uniq[segment_ids.codes].sum())
                   if len(uniq_named) else 0)
        return dict(batch=batch, segment_names=segment_names,
                    segment_ids=segment_ids, sid_map=sid_map,
                    parent_child_map=parent_child_map,
                    root_names=root_names, seg_masks=seg_masks,
                    decoder=decoder, n=n, n_roots=n_roots,
                    input_file_name=stream.input_file_name)

    def _hierarchical_table(self, ctx: dict, output_schema, file_id: int,
                            start_record_id: int):
        """A shard's Arrow table from its decode-once batch
        (`hierarchical_table`), or None where that declines and the
        nesting is walked record by record over the batch's values
        (`FileResult.to_arrow` then asks the rows factory): counted. The
        table guards itself with the rule the route asked ahead of the
        data (`decline_reason`), so on a "batched_rows" route it declines
        for the route's reason, and on a "columnar" one only where its
        columns do not fit the output schema."""
        from .hierarchical_arrow import hierarchical_table

        if not ctx["n"]:
            return None
        table = hierarchical_table(
            ctx["batch"], ctx["segment_names"], self.copybook,
            output_schema, ctx["sid_map"], ctx["parent_child_map"],
            ctx["root_names"], file_id=file_id,
            start_record_id=start_record_id,
            input_file_name=ctx["input_file_name"])
        stats = ctx["batch"].stage_stats
        if table is None and stats is not None:
            stats.note_hier(
                row_path_roots=ctx["n_roots"],
                reason=self.hier_route["reason"]
                or "the assembled columns do not fit the output schema")
        return table

    def _read_rows_hierarchical_columnar(self, ctx: dict, file_id: int,
                                         start_record_id: int
                                         ) -> List[List[object]]:
        """Hierarchical rows with batched value decode: every record's
        fields come from ONE full-plan columnar batch (kernels, not the
        per-field scalar walk); only the parent/child nesting assembly
        runs per record, mirroring extract_hierarchical_record's scan
        semantics exactly (forward scan per child segment, stop when a
        parent id reappears, flush-trigger Record_Id)."""
        from .extractors import _apply_post_processing
        from .columnar import _resolve_occurs

        params = self.params
        n = ctx["n"]
        if n == 0:
            return []
        batch = ctx["batch"]
        segment_ids = ctx["segment_ids"].tolist()
        sid_map = ctx["sid_map"]
        parent_child_map = ctx["parent_child_map"]
        root_names = ctx["root_names"]
        seg_masks = ctx["seg_masks"]
        decoder = ctx["decoder"]
        stream_name = ctx["input_file_name"]
        slot_map = decoder.slot_map
        col_values: Dict[int, list] = {}

        def values_of(col):
            lst = col_values.get(col)
            if lst is None:
                spec = decoder.plan.columns[col]
                # dependee columns are READ at every row — the walk runs
                # non-emitted parts to register DEPENDING-ON counters from
                # whatever bytes overlay them (oracle parity) — so they
                # must never be masked
                is_dependee = (spec.statement is not None
                               and spec.statement.is_dependee)
                mask = (seg_masks.get(spec.segment)
                        if spec.segment is not None and not is_dependee
                        else None)
                lst = batch.column_values(col, relevant=mask)
                col_values[col] = lst
            return lst

        # the walk compiles once per (group, slot_path) into closures over
        # the column value lists — per-record work is list indexing, not
        # slot-map dict lookups per element (the hierarchical twin of
        # ColumnarDecoder._row_maker)
        maker_cache: Dict[tuple, object] = {}

        def build_group(group, slot_path):
            key = (id(group), slot_path)
            maker = maker_cache.get(key)
            if maker is not None:
                return maker
            parts = []  # (emit, fn) — fn(i, scan_i, span_end, pids, depend)
            for st in group.children:
                emit = not st.is_filler and not st.is_child_segment
                if st.is_array:
                    if isinstance(st, Group):
                        elems = [build_group(st, slot_path + (k,))
                                 for k in range(st.array_max_size)]
                        fn = (lambda i, s, e, pd, dep, st=st, el=elems:
                              [mk(i, s, e, pd, dep)
                               for mk in el[:_resolve_occurs(
                                   st, dep.get(st.depending_on))]])
                    else:
                        cols = [slot_map.get((id(st), slot_path + (k,)))
                                for k in range(st.array_max_size)]
                        lists = [None if c is None else values_of(c)
                                 for c in cols]
                        fn = (lambda i, s, e, pd, dep, st=st, ls=lists:
                              [None if l is None else l[i]
                               for l in ls[:_resolve_occurs(
                                   st, dep.get(st.depending_on))]])
                elif isinstance(st, Group):
                    fn = build_group(st, slot_path)
                else:
                    col = slot_map.get((id(st), slot_path))
                    if col is None:
                        fn = lambda i, s, e, pd, dep: None
                    elif st.is_dependee:
                        lst = values_of(col)
                        name = st.name
                        def fn(i, s, e, pd, dep, lst=lst, name=name):
                            value = lst[i]
                            if value is not None:
                                dep[name] = (value if isinstance(value, str)
                                             else int(value))
                            return value
                    else:
                        lst = values_of(col)
                        fn = lambda i, s, e, pd, dep, lst=lst: lst[i]
                parts.append((emit, fn))
            children_groups = (tuple(parent_child_map.get(group.name, ()))
                               if group.is_segment_redefine else ())

            def maker(i, scan_i, span_end, parent_ids, depend,
                      parts=tuple(parts), children_groups=children_groups):
                # declaration order throughout: dependees must register
                # before any later OCCURS resolves, emitted or not
                fields = []
                for emit, fn in parts:
                    value = fn(i, scan_i, span_end, parent_ids, depend)
                    if emit:
                        fields.append(value)
                for child in children_groups:
                    fields.append(extract_children(
                        child, scan_i + 1, span_end, parent_ids, depend))
                return tuple(fields)

            maker_cache[key] = maker
            return maker

        def extract_children(field, from_i, span_end, parent_ids, depend):
            child_maker = build_group(field, ())
            children = []
            j = from_i
            while j < span_end:
                sid_j = segment_ids[j]
                redefine = sid_map.get(sid_j)
                if redefine is not None and redefine.name == field.name:
                    children.append(child_maker(
                        j, j, span_end, [sid_j] + parent_ids, depend))
                elif sid_j in parent_ids:
                    break
                j += 1
            return children

        roots = [p for p in range(n)
                 if (g := sid_map.get(segment_ids[p])) is not None
                 and g.name in root_names]
        generate_input_file = bool(params.input_file_name_column)
        ast_roots = [r for r in self.copybook.ast.children
                     if isinstance(r, Group) and r.parent_segment is None]
        rows = []
        for ri, p in enumerate(roots):
            span_end = roots[ri + 1] if ri + 1 < len(roots) else n
            # Record_Id parity quirk: the id of the record that TRIGGERS
            # the flush — the next root, or one past the last record at
            # end of stream (VarLenHierarchicalIterator.scala:99-135)
            trigger_id = start_record_id + span_end
            depend: Dict[str, object] = {}
            records = [build_group(root, ())(p, p, span_end,
                                             [segment_ids[p]], depend)
                       for root in ast_roots]
            rows.append(_apply_post_processing(
                records, params.schema_policy, params.generate_record_id,
                [], file_id, trigger_id, generate_input_file,
                stream_name))
        return rows

    # -- columnar batch path -------------------------------------------------

    def _decoder_for_segment(self, active_segment: str,
                             backend: str) -> ColumnarDecoder:
        return decoder_for_segment(self._decoders, self.copybook,
                                   active_segment, backend,
                                   select=self.params.select,
                                   variable_size_occurs=self.variable_arrays)

    def _element_rows_decoder(self, kind: str,
                              backend: str) -> ColumnarDecoder:
        """The decoder of one of the two row kinds ("owner", "element")
        of `element_rows`."""
        return decoder_for_segment(self._decoders, self.copybook, "",
                                   backend, variable_size_occurs=True,
                                   rows_of=(kind,
                                            self.element_rows.outer.name))

    def _read_result_elements(self, result: "FileResult", data, offsets,
                              lengths, file_id: int, backend: str,
                              start_record_id: int,
                              corrupt_reasons: Optional[dict]) -> None:
        """A shard of an array of variable arrays, by element rows
        (reader/element_rows.py): decoded now, assembled when asked."""
        from . import element_rows

        if corrupt_reasons:
            result.corrupt_row_reasons = dict(corrupt_reasons)
        shard = element_rows.decode_shard(self, data, offsets, lengths,
                                          backend)
        name = result.input_file_name
        walked = element_rows.walk_rows(
            self, data, offsets, lengths, shard.walked, file_id,
            start_record_id, name)
        reasons = (result.corrupt_row_reasons or {}) \
            if result.corrupt_record_field else None
        result.n_rows = len(offsets)
        result.rows_factory = lambda: element_rows.walk_rows(
            self, data, offsets, lengths, range(len(offsets)), file_id,
            start_record_id, name)
        result.arrow_factory = lambda output_schema: \
            element_rows.elements_table(
                self, shard, output_schema, walked, file_id,
                start_record_id, name, reasons)

    # -- vectorized fast framing (native scan) ------------------------------

    @property
    def supports_fast_framing(self) -> bool:
        """True when whole-shard vectorized RDW framing applies (no custom
        extractors/parsers, no text mode, no length fields)."""
        return self.params.supports_fast_framing

    def _frame_fast(self, stream: SimpleStream, ledger=None,
                    stage_times=None,
                    framed: Optional[FramedRecords] = None):
        """Whole-shard RDW framing via the native scanner. Returns
        (data, base_offset, offsets, lengths, segment_ids, corrupt_reasons)
        or None when the configuration needs the generic per-record
        reader. `corrupt_reasons` maps kept malformed record positions to
        reasons (permissive policy only; empty otherwise). `stage_times`:
        optional StageTimes — the bulk byte materialization is attributed
        to "read", the header scan + segment-id decode to "frame".
        `framed`: the shard's record table and id bytes where the index
        pass walked them (`frame_index_fast`): the scan is skipped, all
        else is done."""
        if not self.supports_fast_framing:
            return None
        p = self.params
        base = stream.offset
        with timed_stage(stage_times, "read"):
            data = stream.next_view(stream.size() - base)
        # the file-header region rule only applies at the file start, the
        # footer rule only when this shard reaches the file's true end (an
        # indexed shard ending mid-file has a data tail, not a footer)
        file_header = p.file_start_offset if base == 0 else 0
        file_footer = (p.file_end_offset
                       if stream.size() >= stream.true_size else 0)
        corrupt_reasons: dict = {}
        with timed_stage(stage_times, "frame"):
            seg_field = resolve_segment_id_field(p, self.copybook)
            seg_bytes = None
            if framed is not None:
                offsets, lengths, seg_bytes = (
                    framed.offsets, framed.lengths, framed.seg_bytes)
            elif p.is_permissive:
                from .recovery import rdw_scan_permissive

                offsets, lengths, corrupt_reasons = rdw_scan_permissive(
                    data, p.is_rdw_big_endian,
                    self._rdw_length_adjustment(), file_header,
                    file_footer, p.record_error_policy,
                    p.resync_window_bytes,
                    ledger if ledger is not None else p.new_diagnostics(),
                    file_name=stream.input_file_name, base_offset=base)
            else:
                offsets, lengths, seg_bytes = self._rdw_walk(
                    data, file_header, file_footer, seg_field)
            segment_ids: Optional[List[str]] = None
            if seg_field is not None:
                segment_ids = self._segment_ids_vectorized(
                    data, offsets, lengths, seg_field,
                    field_bytes=seg_bytes)
        obs = obs_current()
        if obs is not None and obs.metrics is not None and len(lengths):
            # record-length distribution (one vectorized bucket count per
            # shard, never a per-record loop)
            obs.metrics["record_length"].observe_many(lengths)
        return data, base, offsets, lengths, segment_ids, corrupt_reasons

    def _segment_ids_vectorized(self, data, offsets, lengths,
                                seg_field: Primitive,
                                field_bytes=None) -> SegmentIds:
        """Per-record segment ids (dictionary-coded): gather just the id
        field's bytes, decode each *unique* byte pattern once (the scalar
        oracle) — the columnar analogue of getSegmentId per record.
        `field_bytes`: the [n, width] id-field byte matrix when the fused
        framing scan already gathered it (zero-padded past short records,
        pack_records parity); None gathers here."""
        from .. import native

        start = self.params.start_offset
        seg_off = seg_field.binary_properties.offset
        seg_w = seg_field.binary_properties.actual_size
        extent = start + seg_off + seg_w
        if field_bytes is None:
            packed = native.pack_records(data, offsets, lengths, extent)
            field_bytes = packed[:, start + seg_off:]
        short = lengths < extent  # id field truncated -> decode actual bytes
        options = DecodeOptions.from_copybook(self.copybook)
        out = decode_segment_id_bytes(field_bytes, seg_field, options)
        for i in np.nonzero(short)[0]:
            avail = max(0, int(lengths[i]) - (start + seg_off))
            value = options.decode(seg_field.dtype,
                                   bytes(field_bytes[i, :avail]))
            out.replace_at(int(i), "" if value is None else str(value).strip())
        return out

    def _read_result_fast(self, result: "FileResult", data, base: int,
                          offsets, lengths,
                          segment_ids: Optional[List[str]],
                          file_id: int, backend: str,
                          prefix: str,
                          start_record_id: int,
                          corrupt_reasons: Optional[dict] = None) -> None:
        if corrupt_reasons:
            result.corrupt_row_reasons = dict(corrupt_reasons)
        params = self.params
        seg = params.multisegment
        n = len(offsets)
        level_count = len(seg.segment_level_ids) if seg else 0
        segment_filter = (set(seg.segment_id_filter)
                          if seg and seg.segment_id_filter else None)

        keep = np.ones(n, dtype=bool)
        level_ids_per_record: Optional[List[List[Optional[str]]]] = None
        if level_count and segment_ids is not None:
            with Stage("seg_id"):
                level_ids_per_record, no_root = _segment_level_ids_vectorized(
                    segment_ids, seg.segment_level_ids, prefix, file_id,
                    start_record_id)
            keep[no_root] = False  # before the first matched segment
        if segment_filter is not None and segment_ids is not None:
            keep &= segment_ids.mask_of(segment_filter)

        start = params.start_offset
        kept = np.nonzero(keep)[0]
        if self.pushdown is not None:
            # scanned = records the PUSHDOWN examined: level-gating and
            # the legacy segment_id_filter dropped theirs above, and
            # counting them as scanned-but-unpruned would overstate
            # selectivity in the audit/fleet rollups
            kept = self._pushdown_kept(
                self.pushdown, kept, data, offsets, lengths,
                segment_ids, start, backend, n_scanned=len(kept))
            keep = np.zeros(n, dtype=bool)
            keep[kept] = True
        result.n_rows = len(kept)

        # Decode ONCE over every kept record with the full (all-redefines)
        # plan: redefines share byte offsets, so inactive rows decode
        # garbage that a per-redefine struct-validity mask hides — and the
        # per-segment split + interleave gather disappears entirely.
        # Size-skewed profiles (e.g. exp3's 16KB 'C' vs 64B 'P' records)
        # come through here too: the segment row masks reach the decode
        # (masked groups subset-decode or defer into the fused native
        # assembly, which skips hidden rows in-kernel), so the wide
        # plan's columns never run over the narrow records' bytes.
        # (not with variable regions: a redefine's region moves bytes
        # the other redefines read, so each segment's rows go through the
        # plan of their own redefine, below)
        if segment_ids is not None and self.segment_redefine_map \
                and not self.variable_arrays:
            full = self._decoder_for_segment("", backend)
            active_of_uniq = segment_ids.map_uniq(
                self.segment_redefine_map)
            distinct = sorted(set(active_of_uniq))
            a_idx = {a: j for j, a in enumerate(distinct)}
            per_uniq = np.asarray([a_idx[a] for a in active_of_uniq],
                                  dtype=np.int32)
            row_act = per_uniq[segment_ids.codes[kept]]
            masks = {a.upper(): row_act == j
                     for a, j in a_idx.items() if a}
            decoded = full.decode_raw(
                data, offsets[kept], lengths[kept], start_offset=start,
                segment_row_masks=masks, lazy_masked=True)
            kept64 = kept.astype(np.int64)
            result.segments.append(SegmentBatch(
                decoded, None, kept64, start_record_id + kept64,
                seg_level_ids=(
                    level_ids_per_record
                    if level_ids_per_record is not None
                    and len(kept) == n
                    else level_ids_per_record.take(kept)
                    if level_ids_per_record is not None else None),
                redefine_masks=masks,
                row_actives=SegmentIds(row_act, distinct)))
            return

        # per-active-segment split: map segment ids -> active redefines per
        # UNIQUE id; same-active ids merge into one integer-code mask
        by_segment: Dict[str, np.ndarray] = {}
        if segment_ids is None:
            by_segment[""] = kept
        else:
            for active in set(segment_ids.map_uniq(
                    self.segment_redefine_map)):
                mask = segment_ids.mask_of_mapped(
                    self.segment_redefine_map, active)
                positions = np.nonzero(keep & mask)[0]
                if positions.size:
                    by_segment[active] = positions

        for active, positions in by_segment.items():
            decoder = self._decoder_for_segment(active, backend)
            decoded = decoder.decode_raw(
                data, offsets[positions], lengths[positions],
                start_offset=start)
            result.segments.append(SegmentBatch(
                decoded, active or None,
                positions.astype(np.int64),
                start_record_id + positions.astype(np.int64),
                seg_level_ids=(
                    level_ids_per_record.take(positions)
                    if level_ids_per_record is not None else None)))

    def _pushdown_kept(self, pushdown, kept: np.ndarray, data,
                       offsets: np.ndarray, lengths: np.ndarray,
                       segment_ids, start: int, backend: str,
                       n_scanned: int) -> np.ndarray:
        """Pushdown over the kept records of a framed shard: segment-id
        conjuncts drop on the raw id bytes (depth 2, no decode at
        all), then the stage-1 decode of ONLY the filter columns
        evaluates the value predicate — per active segment, so a field
        owned by one redefine evaluates null (and therefore drops) on
        other segments' records, exactly like a post-hoc filter on the
        assembled nested table."""
        pruned_segment = 0
        bytes_skipped = 0
        if pushdown.segment_values is not None and segment_ids is not None \
                and len(kept):
            mask = segment_ids.mask_of(set(pushdown.segment_values))[kept]
            pruned_segment = len(kept) - int(mask.sum())
            if pruned_segment:
                bytes_skipped += int(lengths[kept][~mask].sum())
            kept = kept[mask]
        pruned_filter = 0
        if pushdown.value_expr is not None and len(kept):
            if segment_ids is None or not self.segment_redefine_map:
                mask = pushdown.mask_raw(
                    self, "", backend, data, offsets[kept],
                    lengths[kept], start_offset=start)
            else:
                mask = np.zeros(len(kept), dtype=bool)
                for active in set(segment_ids.map_uniq(
                        self.segment_redefine_map)):
                    amask = segment_ids.mask_of_mapped(
                        self.segment_redefine_map, active)[kept]
                    idx = np.nonzero(amask)[0]
                    if not len(idx):
                        continue
                    sub = kept[idx]
                    m = pushdown.mask_raw(
                        self, active, backend, data, offsets[sub],
                        lengths[sub], start_offset=start)
                    mask[idx[m]] = True
            pruned_filter = len(kept) - int(mask.sum())
            if pruned_filter:
                bytes_skipped += int(lengths[kept][~mask].sum())
            kept = kept[mask]
        pushdown.stats.note(scanned=n_scanned,
                            pruned_segment=pruned_segment,
                            pruned_filter=pruned_filter,
                            bytes_skipped=bytes_skipped)
        return kept

    def read_rows_columnar(self, stream: SimpleStream, file_id: int = 0,
                           backend: str = "numpy",
                           segment_id_prefix: Optional[str] = None,
                           start_record_id: int = 0,
                           starting_file_offset: int = 0) -> List[List[object]]:
        return self.read_result_columnar(
            stream, file_id=file_id, backend=backend,
            segment_id_prefix=segment_id_prefix,
            start_record_id=start_record_id,
            starting_file_offset=starting_file_offset).to_rows()

    def read_result_columnar(self, stream: SimpleStream, file_id: int = 0,
                             backend: str = "numpy",
                             segment_id_prefix: Optional[str] = None,
                             start_record_id: int = 0,
                             starting_file_offset: int = 0,
                             stage_times=None,
                             framed: Optional[FramedRecords] = None
                             ) -> FileResult:
        """Frame all records, pack per-active-segment padded batches, decode
        with the batched kernels; rows/Arrow are materialized lazily from
        the FileResult. `stage_times`: optional profiling.StageTimes —
        the pipeline engine passes it to attribute read/frame/decode busy
        time. `framed`: the stream's records as the index pass framed
        them (only on the route `index.preframed_route` chose)."""
        params = self.params
        ledger = params.new_diagnostics() if params.is_permissive else None
        result = FileResult(
            n_rows=0,
            file_id=file_id,
            input_file_name=stream.input_file_name,
            policy=params.schema_policy,
            generate_record_id=params.generate_record_id,
            generate_input_file_field=bool(params.input_file_name_column),
            corrupt_record_field=params.corrupt_record_column,
            diagnostics=ledger)
        if self.copybook.is_hierarchical or self.dynamic_occurs_layout:
            # hierarchical nesting, and the variable layouts no plan lays
            # out (`row_path_reason`), have no static columnar plan
            # (reference extractHierarchicalRecord,
            # RecordExtractors.scala:211; VarOccursRecordExtractor) — but
            # hierarchical VALUES still come from batched kernels: the
            # decode-once batch feeds a span-based Arrow assembly (no
            # Python rows) and a lazy nesting walk for the row path
            ctx = None
            if self.copybook.is_hierarchical:
                ctx = self._hierarchical_columnar_setup(
                    stream, backend, ledger=ledger,
                    stage_times=stage_times)
            if ctx is not None:
                result.n_rows = ctx["n_roots"]
                result.rows_factory = (
                    lambda: self._read_rows_hierarchical_columnar(
                        ctx, file_id, start_record_id))
                result.arrow_factory = (
                    lambda output_schema: self._hierarchical_table(
                        ctx, output_schema, file_id, start_record_id))
                if self.pushdown is not None:
                    # no static columnar plan -> the whole filter runs
                    # post-decode on the assembled table (correct,
                    # unpruned; the explain report calls this depth out)
                    self.pushdown.filter_result_generic(
                        result, self._output_schema())
                return result
            rows = list(self.iter_rows(
                stream, file_id=file_id,
                start_record_id=start_record_id,
                starting_file_offset=starting_file_offset,
                segment_id_prefix=segment_id_prefix,
                ledger=ledger))
            result.rows = rows
            result.n_rows = len(rows)
            obs = obs_current()
            if obs is not None and obs.device_stats is not None:
                # the read says what it left to the walk
                if self.variable_arrays:
                    obs.device_stats.note_odo(fallback_records=len(rows))
                if self.hier_route is not None:
                    obs.device_stats.note_hier(
                        row_path_roots=len(rows),
                        reason=self.hier_route["reason"])
            if self.pushdown is not None:
                self.pushdown.filter_result_generic(
                    result, self._output_schema())
            return result
        fast = self._frame_fast(stream, ledger=ledger,
                                stage_times=stage_times, framed=framed)
        if fast is not None:
            data, base, offsets, lengths, segment_ids, reasons = fast
            result.records_framed = len(offsets)
            with timed_stage(stage_times, "decode"):
                if self.element_rows is not None:
                    self._read_result_elements(
                        result, data, offsets, lengths, file_id, backend,
                        start_record_id, reasons)
                else:
                    self._read_result_fast(
                        result, data, base, offsets, lengths, segment_ids,
                        file_id, backend,
                        segment_id_prefix or default_segment_id_prefix(),
                        start_record_id, corrupt_reasons=reasons)
            return result
        seg = params.multisegment
        prefix = segment_id_prefix or default_segment_id_prefix()
        accumulator = (SegmentIdAccumulator(seg.segment_level_ids, prefix, file_id)
                       if seg else None)
        level_count = len(seg.segment_level_ids) if seg else 0
        segment_filter = set(seg.segment_id_filter) if seg and seg.segment_id_filter else None
        pushdown = self.pushdown
        pd_segments = (set(pushdown.segment_values)
                       if pushdown is not None
                       and pushdown.segment_values is not None else None)
        pd_scanned = pd_pruned_segment = pd_pruned_filter = 0
        pd_bytes_skipped = 0

        framed = []   # (record_index, active_redefine, data, level_ids)
        record_reader = self.make_record_reader(
            stream, start_record_id, starting_file_offset, ledger)
        with timed_stage(stage_times, "frame"):
            while record_reader.has_next():
                record_index = record_reader.record_index + 1
                segment_id, data = next(record_reader)
                level_ids: List[Optional[str]] = []
                if level_count and accumulator is not None:
                    accumulator.acquired_segment_id(segment_id,
                                                    record_index)
                    level_ids = [accumulator.get_segment_level_id(i)
                                 for i in range(level_count)]
                if level_ids and level_ids[0] is None:
                    continue
                if segment_filter is not None \
                        and segment_id not in segment_filter:
                    continue
                if pushdown is not None:
                    pd_scanned += 1
                    if pd_segments is not None \
                            and segment_id not in pd_segments:
                        # depth-2 pushdown: the segment-id conjunct
                        # drops the record at framing time
                        pd_pruned_segment += 1
                        pd_bytes_skipped += len(data)
                        continue
                active = self.segment_redefine_map.get(segment_id, "")
                framed.append((record_index, active, data, level_ids))
        result.records_framed = (record_reader.record_index + 1
                                 - start_record_id)
        if record_reader.corrupt_reasons:
            # absolute record indices -> output positions of kept rows
            pos_of = {idx: pos for pos, (idx, _, _, _) in enumerate(framed)}
            result.corrupt_row_reasons = {
                pos_of[idx]: reason
                for idx, reason in record_reader.corrupt_reasons.items()
                if idx in pos_of}

        start = params.start_offset
        by_segment: Dict[str, List[int]] = {}
        for pos, (_, active, _, _) in enumerate(framed):
            by_segment.setdefault(active, []).append(pos)

        result.n_rows = len(framed)
        with timed_stage(stage_times, "decode"):
            for active, positions in by_segment.items():
                decoder = self._decoder_for_segment(active, backend)
                # pack to the plan's byte extent, not the full record
                # size — narrow segments of a wide copybook decode from
                # narrow matrices (wide enough for the stage-1 filter
                # columns too: the predicate may reach past the
                # projected plan)
                rs = decoder.plan.max_extent
                if pushdown is not None \
                        and pushdown.value_expr is not None:
                    rs = max(rs, pushdown._stage1_decoder(
                        self, active, backend).plan.max_extent)
                batch = np.zeros((len(positions), rs), dtype=np.uint8)
                lengths = np.zeros(len(positions), dtype=np.int64)
                for row_i, pos in enumerate(positions):
                    payload = framed[pos][2][start: start + rs]
                    batch[row_i, :len(payload)] = np.frombuffer(payload,
                                                                np.uint8)
                    lengths[row_i] = len(payload)
                if pushdown is not None \
                        and pushdown.value_expr is not None \
                        and len(positions):
                    keep = pushdown.mask_matrix(self, active, backend,
                                                batch, lengths)
                    if not keep.all():
                        dropped = int(len(keep) - keep.sum())
                        pd_pruned_filter += dropped
                        # FULL record bytes, not the stage-extent-
                        # clamped payload — bytes_skipped must agree
                        # with the fast path for the same file+filter
                        pd_bytes_skipped += sum(
                            len(framed[p][2])
                            for p, k in zip(positions, keep) if not k)
                        result.n_rows -= dropped
                        batch = batch[keep]
                        lengths = lengths[keep]
                        positions = [p for p, k in zip(positions, keep)
                                     if k]
                        if not positions:
                            continue
                decoded = decoder.decode(batch, lengths=lengths)
                has_levels = level_count > 0
                result.segments.append(SegmentBatch(
                    decoded, active or None,
                    np.asarray(positions, dtype=np.int64),
                    np.asarray([framed[p][0] for p in positions],
                               dtype=np.int64),
                    seg_level_ids=([framed[p][3] for p in positions]
                                   if has_levels else None)))
        if pushdown is not None:
            pushdown.stats.note(scanned=pd_scanned,
                                pruned_segment=pd_pruned_segment,
                                pruned_filter=pd_pruned_filter,
                                bytes_skipped=pd_bytes_skipped)
        return result


    def _output_schema(self):
        """The read's CobolOutputSchema, built reader-side for the
        generic (post-decode) filter paths through the SAME shared
        constructor the API layer uses, so the filtered table types
        identically (FileResult.to_arrow then serves it for the API's
        structurally-equal schema instance)."""
        from .schema import output_schema_for

        return output_schema_for(self.copybook, self.params,
                                 is_var_len=True)


def file_record_id_base(file_order: int) -> int:
    """Deterministic Record_Id base per file (reference Constants.scala:28)."""
    return file_order * DEFAULT_FILE_RECORD_ID_INCREMENT
