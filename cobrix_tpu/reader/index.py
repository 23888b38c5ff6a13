"""Sparse index generation — the sequential-to-parallel bridge.

One sequential pass over a variable-length stream produces split points every
N records / M MB so shards can be decoded in parallel; for hierarchical data
splits land only at root-segment boundaries, and size-based splitting carries
the drift so shard boundaries stay aligned with storage blocks. Mirrors the
reference IndexGenerator.sparseIndexGenerator (reader/index/IndexGenerator.scala:33-127)
and SparseIndexEntry (reader/index/entry/SparseIndexEntry.scala:19).

In the TPU design the index entries become the unit of host-side data
parallelism: each entry maps to one byte-range shard a host worker frames
and ships to the device as a `[batch, max_len]` block (SURVEY.md §2.5).
"""
from __future__ import annotations

import logging
import os
from dataclasses import dataclass, replace
from typing import List, NamedTuple, Optional

from ..copybook.ast import Primitive
from ..copybook.copybook import Copybook
from .diagnostics import (
    DEFAULT_RESYNC_WINDOW,
    ReadDiagnostics,
    RecordErrorPolicy,
)
from .header_parsers import RdwHeaderParser, RecordHeaderParser
from .parameters import DEFAULT_INDEX_ENTRY_SIZE_MB, MEGABYTE
from .raw_extractors import RawRecordExtractor
from .recovery import (
    PendingReader,
    generic_blob_validator,
    rdw_blob_validator,
    resync_stream,
)
from .stream import SimpleStream


@dataclass(frozen=True)
class SparseIndexEntry:
    offset_from: int
    offset_to: int      # -1 = to end of file
    file_id: int
    record_index: int


@dataclass(frozen=True)
class FramedRecords:
    """An index entry's records as the index pass walked them: what the
    RDW scan of `VarLenReader._frame_fast` finds in the entry's byte
    range. `offsets` are payload offsets from the entry's first byte;
    `seg_bytes` is the [n, width] matrix of the records' segment id
    bytes, None where no field is resolved or the fused native walk is
    not there (the shard gathers them then)."""
    offsets: object         # numpy int64, one a record
    lengths: object
    seg_bytes: Optional[object]


# A file whose records average fewer payload bytes than this over its first
# DENSE_PROBE_BYTES is dense, and two mechanisms of the threaded indexed
# scan read that: it is framed once, by its index pass (`preframed_route`),
# and a file smaller than the pool's work is cut into SHARDS_PER_THREAD
# shards a pool thread (`index_split`). The constant rests on two points
# of the benchmark's ledger and nothing finer: at 65-70 B a record
# (exp2_read: 8.2 million records a 512 MiB file) the one framing gave
# +46.6 %, at 722 B (tpch_orders_odo_read: 743 k) and at 5.4 KB
# (exp3_read: 99 k) it gave nothing the runs resolve, or lost
# (PERF_LEDGER.jsonl, PR 35); and shards of a wide-record file cut to the
# pool would fill their launches worse (exp3's 'C' rows: about 1,300 a
# 20 MiB shard in a bucket of 2,048, where 6,600 fill 8,192 at 100 MiB).
# Speed only: every route and split gives the same tables.
DENSE_MAX_MEAN_RECORD = 256
DENSE_PROBE_BYTES = MEGABYTE
# The least `index_split` cuts a dense file into: 16 MiB is about 250 k
# records of 64-67 B (exp2_read, hier_companies_read), one launch of
# 262,144 rows or more, so that what a shard costs whatever its size (its
# stream, its launches' round trips, its Arrow table) stays small beside
# its records. A file under two of it keeps the read's split.
SPLIT_FLOOR = 16 * MEGABYTE
# Shards `index_split` gives each thread of the pool: with one a thread
# the threads pack, wait on the link and assemble all at once and the read
# waits for its slowest shard; two shards of 20 MiB a thread read 5 %
# over one of 40 MiB, at the same launches' fill, in hier_companies_read
# on the chip's host of 13 cores (PERF.md section 6, PR 39, call 2).
SHARDS_PER_THREAD = 2


def _one_shard_covers(size: int, params, split_mb=None) -> bool:
    """A file too small to index: empty (nothing to index, and mmap
    rejects empty files), or within one split with no explicit split
    option nor `split_mb` from `index_split` — the whole file is one
    shard anyway."""
    if size == 0:
        return True
    explicit = (params.input_split_records is not None
                or params.input_split_size_mb is not None
                or split_mb is not None)
    return not explicit and size <= DEFAULT_INDEX_ENTRY_SIZE_MB * MEGABYTE


def _plain_local_file(file_path: str, io) -> bool:
    """Storage whose bytes the density probe and the index pass read as
    they lie: a local file, neither compressed nor behind a codec."""
    from ..io.compress import active_codec, compressed_chunkable
    from .stream import path_scheme

    return (path_scheme(file_path) in (None, "file")
            and active_codec(file_path, io) is None
            and compressed_chunkable(file_path, io))


def _mean_record(reader, file_path: str, size: int) -> Optional[float]:
    """The density probe: the mean payload of the records whose RDW
    headers lie in the file's first DENSE_PROBE_BYTES."""
    with open(file_path, "rb") as f:
        head = f.read(DENSE_PROBE_BYTES)
    return reader.mean_record_length(head, whole=len(head) >= size)


def preframed_route(reader, file_path: str, params, io=None,
                    split_mb=None) -> bool:
    """THE rule of the indexed scan's second route: True where the index
    pass of `file_path` should be the file's one framing
    (`preframed_entries`), each shard receiving its slice of the pass's
    tables instead of scanning its byte range again. Read off the file:
    the mean record length over the RDW headers of its first
    DENSE_PROBE_BYTES, under DENSE_MAX_MEAN_RECORD. False, and
    `file_index_entries` as ever, for everything the pass does not
    serve to the letter: a permissive policy (the pass's ledger is a
    throwaway, the shards' is the read's), the index store (entries are
    loaded, or saved: no pass may have run), zone-map skipping, framing
    the native scan does not do, layouts that do not reach
    `_frame_fast`'s tables, storage that is not a plain local file, and
    a file one shard covers (at `split_mb`, where `index_split` set
    one)."""
    if (params.is_permissive
            or (io is not None and io.cache_enabled)
            or getattr(reader, "chunk_skipper", None) is not None
            or not reader.supports_fast_framing
            or reader.copybook.is_hierarchical
            or reader.dynamic_occurs_layout
            or not _plain_local_file(file_path, io)):
        return False
    size = os.path.getsize(file_path)
    if _one_shard_covers(size, params, split_mb):
        return False
    mean = _mean_record(reader, file_path, size)
    return mean is not None and mean < DENSE_MAX_MEAN_RECORD


class IndexSplit(NamedTuple):
    """How one file's sparse index is cut (`index_split`): `why` is
    "option" (the read names `input_split_size_mb` or
    `input_split_records`), "pool" (the rule set `mb`), "wide_records"
    (the pool would have, but the records are not dense) or "default";
    `mb` the MiB a shard is cut at, None where records count."""
    why: str
    mb: Optional[int]


def index_split(reader, file_path: str, params, parallelism: int,
                io=None) -> IndexSplit:
    """THE split of the threaded indexed scan (`api._scan_var_len`), one
    file at a time: a dense file smaller than `parallelism` default
    splits is cut into SHARDS_PER_THREAD shards a pool thread,
    `max(SPLIT_FLOOR, size / (SHARDS_PER_THREAD * parallelism))` rounded
    up to a whole MiB, so that every thread of the pool works and none
    waits on a shard of 100 MiB. Every other file keeps the
    read's split: explicit split options always win; a pool of one, a
    permissive policy (the probe's walk is strict), the index store
    (whose entries are keyed by the options), framing the native scan
    does not do, a file header or footer (a counted header shifts an
    indexed read's Record_Ids from those of a file one shard covers:
    IndexGenerator.scala:117-120) and storage that is not a plain local
    file keep the default, as does a file under two floors or of
    `parallelism` default splits or more; a file whose records average
    DENSE_MAX_MEAN_RECORD or more keeps it as "wide_records". Cuts stay
    where the index pass makes them (at roots where shards are cut at
    roots), so every split gives the same tables."""
    if (params.input_split_records is not None
            or params.input_split_size_mb is not None):
        return IndexSplit("option", params.input_split_size_mb)
    default = IndexSplit("default", DEFAULT_INDEX_ENTRY_SIZE_MB)
    if (parallelism <= 1 or params.is_permissive
            or (io is not None and io.cache_enabled)
            or not reader.supports_fast_framing
            or params.file_start_offset or params.file_end_offset
            or not _plain_local_file(file_path, io)):
        return default
    size = os.path.getsize(file_path)
    if not (2 * SPLIT_FLOOR <= size
            < parallelism * DEFAULT_INDEX_ENTRY_SIZE_MB * MEGABYTE):
        return default
    mean = _mean_record(reader, file_path, size)
    if mean is None:
        return default
    if mean >= DENSE_MAX_MEAN_RECORD:
        return IndexSplit("wide_records", DEFAULT_INDEX_ENTRY_SIZE_MB)
    split = max(SPLIT_FLOOR, -(-size // (SHARDS_PER_THREAD * parallelism)))
    return IndexSplit("pool", -(-split // MEGABYTE))


def preframed_entries(reader, file_path: str, file_order: int,
                      split_mb=None):
    """The sparse index of one file on the route `preframed_route`
    chose: `file_index_entries`' entries to the byte, each with its
    records' tables (`FramedRecords`), yielded as its cut is found so
    that its shard can start while the pass walks on."""
    import mmap

    with open(file_path, "rb") as f:
        mm = mmap.mmap(f.fileno(), 0, access=mmap.ACCESS_READ)
        try:
            yield from reader.frame_index_fast(mm, file_order, split_mb)
        finally:
            try:
                mm.close()
            except BufferError:
                # as in file_index_entries: an error in flight still
                # references the map through its traceback
                pass


def file_index_entries(reader, file_path: str, file_order: int, params,
                       retry=None, on_retry=None, io=None, split_mb=None
                       ) -> Optional[List[SparseIndexEntry]]:
    """Sparse index for one file, or None when a single shard suffices —
    the chunk-planning primitive shared by the threaded indexed scan, the
    multi-host executor, and the chunked pipeline engine
    (cobrix_tpu.engine.chunks). The vectorized RDW index is used when the
    configuration allows it; otherwise the generic per-record generator
    (the reference's only mode, IndexGenerator.scala:33) runs.

    With `io.cache_dir` set, computed entries persist in the sparse-index
    store (cobrix_tpu.io.index_store) keyed by file fingerprint +
    framing-config fingerprint: the sequential indexing pass runs once
    per file version, and warm re-scans load the shard plan directly.
    `split_mb`: the MiB a shard that `index_split` set for the file
    (never with the store), None for the read's own split."""
    from .stream import open_stream, path_scheme

    store = config_fp = io_stats = None
    if io is not None and io.cache_enabled:
        from ..io.index_store import (SparseIndexStore,
                                      index_config_fingerprint)
        from ..io.stats import current_io_stats

        try:
            store = SparseIndexStore(io.cache_dir)
            config_fp = index_config_fingerprint(reader, params)
            io_stats = current_io_stats()
        except OSError:
            # unusable cache volume (read-only / full): index without
            # persistence — the cache must never fail the scan
            store = None

    def from_store(fingerprint: str):
        cached = store.load(file_path, fingerprint, config_fp, file_order)
        if io_stats is not None:
            io_stats.bump("index_hits" if cached is not None
                          else "index_misses")
        return cached

    def to_store(fingerprint: str, entries) -> None:
        if store is not None and entries is not None:
            store.save(file_path, fingerprint, config_fp, entries)
            if io_stats is not None:
                io_stats.bump("index_saves")

    from ..io.compress import active_codec, compressed_chunkable

    if not compressed_chunkable(file_path, io):
        # compressed input without a decompressed cache plane: byte-range
        # shards would each re-inflate the prefix, so one whole-file
        # shard (the streaming-discovery fallback) is strictly cheaper
        return None
    if path_scheme(file_path) in (None, "file") \
            and active_codec(file_path, io) is None:
        if _one_shard_covers(os.path.getsize(file_path), params,
                             split_mb):
            return None
        fingerprint = None
        if store is not None:
            st = os.stat(file_path)
            fingerprint = f"local:{st.st_size}:{st.st_mtime_ns}"
            cached = from_store(fingerprint)
            if cached is not None:
                return cached
        entries = None
        if reader.supports_fast_framing:
            # mmap, not read(): the scan touches the whole file once to
            # find split offsets; materializing it would spike RSS by the
            # file size on exactly the large files indexing targets
            import mmap

            with open(file_path, "rb") as f:
                mm = mmap.mmap(f.fileno(), 0, access=mmap.ACCESS_READ)
                try:
                    entries = reader.generate_index_fast(mm, file_order,
                                                         split_mb)
                finally:
                    try:
                        mm.close()
                    except BufferError:
                        # a FramingError in flight still references the
                        # map through its traceback; closing here would
                        # MASK that actionable error with a BufferError —
                        # the map is released when the exception is
                        pass
        if entries is None:
            with open_stream(file_path) as stream:
                entries = reader.generate_index(stream, file_order)
        to_store(fingerprint, entries)
        return entries
    # registry-backed storage (and compressed local files, whose raw
    # bytes cannot be mmap-framed): one stream serves the size probe,
    # the fingerprint probe, and the index scan (a backend open is
    # typically a network round trip; a cold compressed open is the
    # discovery inflate)
    with open_stream(file_path, retry=retry, on_retry=on_retry,
                     io=io) as stream:
        if _one_shard_covers(stream.size(), params):
            return None
        fingerprint = None
        if store is not None:
            source = getattr(stream, "source", None)
            if source is not None:
                fingerprint = source.fingerprint()
                cached = from_store(fingerprint)
                if cached is not None:
                    return cached
        entries = reader.generate_index(stream, file_order)
    if fingerprint is not None:
        to_store(fingerprint, entries)
    return entries


class IncrementalIndexer:
    """Sparse-index construction one record at a time — the streaming
    twin of `sparse_index_generator` for the continuous-ingest tailer.

    A growing file cannot be indexed by a one-shot sequential pass (the
    pass would never end), but the tailer already frames every record as
    it stabilizes; feeding those framings here keeps the sparse index
    CURRENT at every watermark, so when the generation finalizes
    (rotation, stream shutdown) the complete entries persist to the
    index store and the very first batch `read_cobol` of the rotated
    file goes straight to shard planning — no re-index pass.

    Split arithmetic mirrors `sparse_index_generator` exactly for the
    non-hierarchical case (records-per-entry, or size-per-entry with
    drift carry); `state_dict()`/`from_state` round-trip through the
    ingest checkpoint so a crashed tailer resumes indexing from its
    watermark instead of record zero. Hierarchical root-boundary
    alignment needs segment inspection the live tailer refuses anyway
    (see streaming.ingest), so it is unsupported here."""

    def __init__(self, records_per_entry: Optional[int] = None,
                 size_per_entry_mb: Optional[int] = None):
        self.records_per_entry = records_per_entry
        self.size_per_entry_mb = size_per_entry_mb
        self._bytes_per_entry = (size_per_entry_mb
                                 or DEFAULT_INDEX_ENTRY_SIZE_MB) * MEGABYTE
        self.byte_index = 0
        self.record_index = 0
        self.records_in_chunk = 0
        self.bytes_in_chunk = 0
        # (offset_from, record_index) split points; entry 0 is implicit
        self._splits: List[List[int]] = [[0, 0]]
        # one-record lookahead: the one-shot generator detects EOF
        # BEFORE its split branch, so the stream's LAST record can
        # never open a new entry — mirrored here by applying each
        # record only once a successor proves it was not last
        self._held: Optional[List] = None

    def _need_split(self) -> bool:
        if self.records_per_entry is not None:
            return self.records_in_chunk >= self.records_per_entry
        return self.bytes_in_chunk >= self._bytes_per_entry

    def add_record(self, record_size: int, is_valid: bool = True) -> None:
        """One framed record, in stream order (`record_size` includes
        its header bytes — the full stream distance it consumed)."""
        if self._held is not None:
            self._apply(*self._held)
        self._held = [int(record_size), bool(is_valid)]

    def _apply(self, record_size: int, is_valid: bool) -> None:
        if is_valid and self._need_split():
            self._splits.append([self.byte_index, self.record_index])
            self.records_in_chunk = 0
            if self.records_per_entry is None:
                # carry the size-split drift (sparse_index_generator's
                # block-alignment rule)
                self.bytes_in_chunk -= self._bytes_per_entry
            else:
                self.bytes_in_chunk = 0
        self.record_index += 1
        self.records_in_chunk += 1
        self.byte_index += record_size
        self.bytes_in_chunk += record_size

    def entries(self, file_id: int) -> List[SparseIndexEntry]:
        """The sparse index as of the records fed so far (the last entry
        is open-ended, matching the one-shot generator's output; the
        held lookahead record never contributes a split, exactly like
        the generator's last record)."""
        out: List[SparseIndexEntry] = []
        for i, (offset_from, record_index) in enumerate(self._splits):
            offset_to = (self._splits[i + 1][0]
                         if i + 1 < len(self._splits) else -1)
            out.append(SparseIndexEntry(offset_from, offset_to, file_id,
                                        record_index))
        return out

    def state_dict(self) -> dict:
        return {
            "records_per_entry": self.records_per_entry,
            "size_per_entry_mb": self.size_per_entry_mb,
            "byte_index": self.byte_index,
            "record_index": self.record_index,
            "records_in_chunk": self.records_in_chunk,
            "bytes_in_chunk": self.bytes_in_chunk,
            "splits": [list(s) for s in self._splits],
            "held": list(self._held) if self._held else None,
        }

    @classmethod
    def from_state(cls, state: dict) -> "IncrementalIndexer":
        indexer = cls(records_per_entry=state.get("records_per_entry"),
                      size_per_entry_mb=state.get("size_per_entry_mb"))
        indexer.byte_index = int(state.get("byte_index", 0))
        indexer.record_index = int(state.get("record_index", 0))
        indexer.records_in_chunk = int(state.get("records_in_chunk", 0))
        indexer.bytes_in_chunk = int(state.get("bytes_in_chunk", 0))
        splits = state.get("splits") or [[0, 0]]
        indexer._splits = [[int(a), int(b)] for a, b in splits]
        held = state.get("held")
        indexer._held = ([int(held[0]), bool(held[1])] if held
                         else None)
        return indexer


def sparse_index_generator(file_id: int,
                           data_stream: SimpleStream,
                           record_header_parser: Optional[RecordHeaderParser] = None,
                           record_extractor: Optional[RawRecordExtractor] = None,
                           records_per_index_entry: Optional[int] = None,
                           size_per_index_entry_mb: Optional[int] = None,
                           copybook: Optional[Copybook] = None,
                           segment_field: Optional[Primitive] = None,
                           is_hierarchical: bool = False,
                           root_segment_id: str = "",
                           record_error_policy: RecordErrorPolicy =
                           RecordErrorPolicy.FAIL_FAST,
                           resync_window_bytes: int = DEFAULT_RESYNC_WINDOW,
                           ledger: Optional[ReadDiagnostics] = None
                           ) -> List[SparseIndexEntry]:
    root_segment_ids = root_segment_id.split(",")
    byte_index = 0
    index: List[SparseIndexEntry] = [SparseIndexEntry(0, -1, file_id, 0)]
    root_record_id = ""
    records_in_chunk = 0
    bytes_in_chunk = 0
    record_index = 0
    is_really_hierarchical = (copybook is not None and segment_field is not None
                              and is_hierarchical)
    is_split_by_size = records_per_index_entry is None
    if records_per_index_entry is not None:
        def need_split(records: int, size: int) -> bool:
            return records >= records_per_index_entry
    else:
        bytes_per_entry = (size_per_index_entry_mb
                           or DEFAULT_INDEX_ENTRY_SIZE_MB) * MEGABYTE

        def need_split(records: int, size: int) -> bool:
            return size >= bytes_per_entry

    def get_segment_id(record: bytes) -> str:
        value = copybook.extract_primitive_field(segment_field, record)
        return "" if value is None else str(value).strip()

    permissive = record_error_policy is not RecordErrorPolicy.FAIL_FAST
    if permissive and ledger is None:
        ledger = ReadDiagnostics()
    reader = PendingReader(data_stream)

    def header_validator():
        if type(record_header_parser) is RdwHeaderParser:
            return rdw_blob_validator(record_header_parser)
        return generic_blob_validator(record_header_parser,
                                      data_stream.size(), reader.offset)

    end_of_file = False
    while not end_of_file:
        record = None
        if record_extractor is not None:
            offset0 = record_extractor.offset
            if record_extractor.has_next():
                record = next(record_extractor)
                is_valid = True
            else:
                is_valid = False
            record_size = record_extractor.offset - offset0
            has_more = record_extractor.has_next()
        else:
            header = reader.read(record_header_parser.header_length)
            while True:
                try:
                    meta = record_header_parser.get_record_metadata(
                        header, reader.offset, data_stream.size(),
                        record_index)
                    break
                except ValueError as exc:
                    # corruption tolerance mirrors VRLRecordReader so the
                    # index pass and the shard framers skip identically
                    if not permissive:
                        raise
                    header = resync_stream(
                        reader, header, header_validator(),
                        record_header_parser.header_length,
                        resync_window_bytes, ledger,
                        data_stream.input_file_name,
                        getattr(exc, "reason", str(exc)))
                    if header is None:
                        meta = None
                        break
            if meta is None:
                record_size = reader.offset - byte_index
                has_more = False
                is_valid = False
            else:
                if meta.record_length > 0:
                    record = reader.read(meta.record_length)
                record_size = reader.offset - byte_index
                has_more = record_size > 0
                is_valid = meta.is_valid

        if (record_extractor is None and reader.at_end) \
                or (record_extractor is not None
                    and data_stream.is_end_of_stream) or not has_more:
            end_of_file = True
        elif is_valid:
            if is_really_hierarchical and not root_record_id:
                cur = get_segment_id(record)
                if (cur and not root_segment_ids) or cur in root_segment_ids:
                    root_record_id = cur
            if need_split(records_in_chunk, bytes_in_chunk):
                if (not is_really_hierarchical
                        or get_segment_id(record) in root_segment_ids):
                    entry = SparseIndexEntry(byte_index, -1, file_id, record_index)
                    index[-1] = replace(index[-1], offset_to=entry.offset_from)
                    index.append(entry)
                    records_in_chunk = 0
                    if is_split_by_size:
                        # carry the size-split drift so shard boundaries stay
                        # aligned with storage blocks
                        bytes_in_chunk -= (size_per_index_entry_mb
                                           or DEFAULT_INDEX_ENTRY_SIZE_MB) * MEGABYTE
                    else:
                        bytes_in_chunk = 0
        # NOTE: invalid records (file headers/footers) ARE counted, mirroring
        # the reference exactly (IndexGenerator.scala:117-120 increments
        # unconditionally) — even though VRLRecordReader skips invalid
        # records without numbering them. The resulting Record_Id shift
        # after a file header on indexed reads is reference behavior.
        record_index += 1
        records_in_chunk += 1
        byte_index += record_size
        bytes_in_chunk += record_size
    if is_really_hierarchical and root_segment_id and not root_record_id:
        logging.getLogger(__name__).error(
            "Root segment %s=='%s' not found in the data file.",
            segment_field.name, root_segment_id)
    elif is_really_hierarchical and not root_record_id:
        logging.getLogger(__name__).error(
            "Root segment %s is empty for every record in the data file.",
            segment_field.name)
    return index
