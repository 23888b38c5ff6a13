"""Read results: decoded kernel outputs carried to the API boundary.

The round-1 design materialized Python rows inside the readers and the API
rebuilt columns from them — destroying the kernel's numpy columns at
~tens of µs/row. Here the readers return `FileResult`s holding the
`DecodedBatch`es themselves (plus the generated-column inputs), so
`to_arrow`/`to_pandas` go straight from kernel outputs to Arrow buffers
and rows are materialized only when actually asked for.

A FileResult is either columnar (segments of DecodedBatches with record
positions) or row-backed (host oracle path, hierarchical assemblies —
shapes with no static columnar plan).
"""
from __future__ import annotations

from dataclasses import dataclass, field as dc_field
from typing import List, Optional, Sequence

import numpy as np

from ..copybook.datatypes import SchemaRetentionPolicy
from ..profiling import Stage
from .columnar import DecodedBatch


class SegLevelColumns:
    """Seg_Id0..N level columns (None = level not shown for that row).

    Two representations: materialized per-level object arrays (`levels`),
    or a coded form — per-row root record ids, child counters and
    visibility masks — that the native formatter turns straight into Arrow
    string buffers (`arrow_level`). The object arrays materialize lazily,
    so Arrow-only reads never build 600k Python strings."""

    def __init__(self, levels: Optional[List[np.ndarray]] = None,
                 coded: Optional[dict] = None):
        self._levels = levels
        self.coded = coded

    @property
    def levels(self) -> List[np.ndarray]:
        if self._levels is None:
            self._levels = self._materialize()
        return self._levels

    def _materialize(self) -> List[np.ndarray]:
        c = self.coded
        root_rid = c["root_rid"]
        prefix = c["prefix"]
        rid_str = root_rid.astype("U20")
        root_u = np.where(root_rid >= 0,
                          np.char.add(np.asarray(prefix, dtype="U"),
                                      rid_str), "")
        levels: List[np.ndarray] = []
        for k in range(c["level_count"]):
            valid = c["valids"][k]
            if k == 0:
                col = root_u.astype(object)
            else:
                cnt_str = c["counters"][k].astype("U20")
                col = np.char.add(np.char.add(root_u, f"_L{k}_"),
                                  cnt_str).astype(object)
            col[~valid] = None
            levels.append(col)
        return levels

    def arrow_level(self, k: int):
        """(int32 offsets, utf8 data, valid bool array) Arrow buffers for
        level k via the native formatter; None when unavailable."""
        from .. import native

        c = self.coded
        if c is None or k >= c["level_count"]:
            return None
        valid = c["valids"][k]
        res = native.format_seg_id_level(
            c["root_rid"], c["counters"][k], c["prefix"], k, valid)
        if res is None:
            return None
        offsets, data = res
        return offsets, data, valid

    def __len__(self) -> int:
        if self.coded is not None:
            return len(self.coded["root_rid"])
        return len(self._levels[0]) if self._levels else 0

    def __bool__(self) -> bool:
        return len(self) > 0

    def __getitem__(self, i: int) -> List[object]:
        return [lvl[i] for lvl in self.levels]

    def __eq__(self, other) -> bool:
        if isinstance(other, SegLevelColumns):
            other = [other[i] for i in range(len(other))]
        return [self[i] for i in range(len(self))] == other

    def take(self, positions: np.ndarray) -> "SegLevelColumns":
        if self.coded is not None:
            c = self.coded
            return SegLevelColumns(coded=dict(
                c,
                root_rid=c["root_rid"][positions],
                counters=[None if cnt is None else cnt[positions]
                          for cnt in c["counters"]],
                valids=[v[positions] for v in c["valids"]]))
        return SegLevelColumns([lvl[positions] for lvl in self.levels])


@dataclass
class SegmentBatch:
    """One decoded batch of a file read: either one active segment
    (`active` set), or a decode-once batch over every record with
    per-row segment routing (`redefine_masks`/`row_actives` set) — the
    shape that skips the interleave gather entirely."""

    batch: DecodedBatch
    active: Optional[str]                 # active segment redefine, or None
    positions: np.ndarray                 # output position of each row
    record_ids: Optional[np.ndarray]      # Record_Id per row (None: positions)
    # per-row Seg_Id lists, or a SegLevelColumns view
    seg_level_ids: Optional[Sequence[Sequence[object]]] = None
    # decode-once (whole-plan) batches: per-redefine boolean row masks
    # (struct validity) and the per-row active redefine names
    redefine_masks: Optional[dict] = None
    row_actives: Optional[Sequence[Optional[str]]] = None


@dataclass
class FileResult:
    """Decoded result of one input file (or one shard of it)."""

    n_rows: int
    file_id: int = 0
    input_file_name: str = ""
    policy: SchemaRetentionPolicy = SchemaRetentionPolicy.KEEP_ORIGINAL
    generate_record_id: bool = False
    generate_input_file_field: bool = False
    segments: List[SegmentBatch] = dc_field(default_factory=list)
    rows: Optional[List[List[object]]] = None   # row-backed fallback
    # fault-tolerance surface: the shard's error ledger, the name of the
    # optional per-row debug column ('' = none), and the reason per kept
    # malformed row keyed by record POSITION within this shard
    diagnostics: Optional[object] = None
    corrupt_record_field: str = ""
    corrupt_row_reasons: Optional[dict] = None
    # lazy producers (hierarchical decode-once reads): rows and Arrow are
    # materialized only when actually asked for; each factory is dropped
    # after first use so the captured decode batch can be released once
    # both products (cached below) exist. The Arrow cache remembers the
    # output_schema it was built for — a later call with a DIFFERENT
    # schema rebuilds from the row path instead of serving a stale table
    rows_factory: Optional[object] = None
    arrow_factory: Optional[object] = None
    # records the framer CONSUMED (and numbered) producing this result —
    # >= n_rows when segment filters / level gating drop rows after
    # numbering. The continuous-ingest tailer advances its record-id
    # watermark by this, so batch-wise Record_Ids stay identical to a
    # one-shot read's. None on paths that never set it
    records_framed: Optional[int] = None
    _arrow_cache: Optional[object] = dc_field(default=None, repr=False)
    _arrow_cache_schema: Optional[object] = dc_field(default=None, repr=False)
    _corrupt_col_added: bool = dc_field(default=False, repr=False)

    @property
    def is_columnar(self) -> bool:
        """Kernel outputs available (independent of row caching)."""
        return bool(self.segments) or self.arrow_factory is not None \
            or self._arrow_cache is not None

    def _append_corrupt_column(self, rows: List[List[object]],
                               positions) -> None:
        """Trailing debug-column values (reason for malformed rows, None
        otherwise), appended once per materialization."""
        if not self.corrupt_record_field or self._corrupt_col_added:
            return
        reasons = self.corrupt_row_reasons or {}
        for p, row in zip(positions, rows):
            row.append(reasons.get(p))
        self._corrupt_col_added = True

    def to_rows(self) -> List[List[object]]:
        if self.rows is None and self.rows_factory is not None:
            self.rows = self.rows_factory()
            self.rows_factory = None
        if self.rows is not None:
            self._append_corrupt_column(self.rows, range(len(self.rows)))
            return self.rows
        keyed: List[tuple] = []
        for seg in self.segments:
            n = len(seg.positions)
            record_ids = (seg.record_ids if seg.record_ids is not None
                          else seg.positions)
            seg_rows = seg.batch.to_rows(
                policy=self.policy,
                generate_record_id=self.generate_record_id,
                file_id=self.file_id,
                record_ids=[int(r) for r in record_ids],
                generate_input_file_field=self.generate_input_file_field,
                input_file_name=self.input_file_name,
                segment_level_ids=seg.seg_level_ids,
                active_segments=(seg.row_actives
                                 if seg.row_actives is not None
                                 else [seg.active] * n))
            keyed.extend(zip((int(p) for p in seg.positions), seg_rows))
        keyed.sort(key=lambda t: t[0])  # positions are sparse order keys
        self.rows = [r for _, r in keyed]
        self._append_corrupt_column(self.rows, (p for p, _ in keyed))
        return self.rows

    def to_arrow(self, output_schema):
        """pyarrow Table in record order (vectorized; no Python rows)."""
        import pyarrow as pa

        from .arrow_out import arrow_schema, rows_to_table, segment_table

        # a table assembled eagerly (pipeline engine's per-chunk assemble
        # stage, or the generic filter path) serves any later call for
        # the same schema directly — by identity first, then by Arrow
        # structural equality: the API layer builds its OWN
        # CobolOutputSchema instance from the same inputs, and a
        # reader-side filtered table must not be thrown away and
        # rebuilt from Python rows just because the instances differ
        if self._arrow_cache is not None:
            if self._arrow_cache_schema is output_schema:
                return self._arrow_cache
            if self._arrow_cache.schema.equals(
                    arrow_schema(output_schema.schema)):
                return self._arrow_cache
        # prefer the kernel outputs even when rows were also materialized
        # (to_rows caching must not reroute to_arrow onto the row fallback)
        if not self.segments:
            if self.arrow_factory is not None:
                table = self.arrow_factory(output_schema)
                if table is not None:
                    self._arrow_cache = table
                    self._arrow_cache_schema = output_schema
                    self.arrow_factory = None
                    return table
            if self.rows is None and self.rows_factory is not None:
                self.rows = self.rows_factory()
                self.rows_factory = None
            if self.rows is not None:
                # not cached: _arrow_cache feeds is_columnar, which must
                # keep reporting "kernel outputs available" truthfully
                return rows_to_table(self.to_rows(), output_schema.schema)
            return arrow_schema(output_schema.schema).empty_table()
        reasons = (self.corrupt_row_reasons or {}) \
            if self.corrupt_record_field else None
        tables = []
        order = []
        for seg in self.segments:
            record_ids = (seg.record_ids if seg.record_ids is not None
                          else seg.positions)
            seg_reasons = None
            if reasons:
                seg_reasons = [reasons.get(int(p)) for p in seg.positions]
            # counted on the read's DeviceStats through the reference the
            # batch captured: the read's obs context is gone by now
            with Stage("assemble.table", seg.batch.stage_stats):
                tables.append(segment_table(
                    seg.batch, seg.active, output_schema,
                    file_id=self.file_id,
                    record_ids=np.asarray(record_ids, dtype=np.int64),
                    seg_level_ids=seg.seg_level_ids,
                    input_file_name=self.input_file_name,
                    redefine_masks=seg.redefine_masks,
                    corrupt_reasons=seg_reasons))
            order.append(np.asarray(seg.positions, dtype=np.int64))
        if len(tables) == 1:
            table = tables[0]
            pos = order[0]
            # ascending positions (all-records decode-once batches, or a
            # filtered subset) are already in record order — no gather
            if len(pos) == 0 or bool(np.all(np.diff(pos) > 0)):
                self._count_pass("take_elided")
                return table
            return table.take(_record_order_indices(pos))
        table = pa.concat_tables(tables)
        # rows currently ordered [seg0 rows..., seg1 rows...]; invert to
        # record order — unless the batches happen to tile the position
        # space in globally ascending order (contiguous shard splits),
        # where the concatenation IS record order and the full-table
        # gather copy disappears
        pos = np.concatenate(order)
        if len(pos) == 0 or bool(np.all(np.diff(pos) > 0)):
            self._count_pass("take_elided")
            return table
        return table.take(_record_order_indices(pos))

    def _count_pass(self, name: str) -> None:
        """Fold one fused-pass engagement into the owning read's
        counters, through any batch's captured reference (to_arrow runs
        after the read's obs context died)."""
        for seg in self.segments:
            pc = seg.batch.pass_counts
            if pc is not None:
                pc.incr(name)
                return


def _record_order_indices(pos: np.ndarray) -> np.ndarray:
    """Take-indices that order rows by their (unique) record positions:
    an O(n) scatter instead of an argsort."""
    if not len(pos):
        return pos
    slots = np.full(int(pos.max()) + 1, -1, dtype=np.int64)
    slots[pos] = np.arange(len(pos), dtype=np.int64)
    return slots[slots >= 0]


def rows_file_result(rows: List[List[object]]) -> FileResult:
    return FileResult(n_rows=len(rows), rows=rows)
