"""Typed reader configuration.

Mirrors the reference parameter objects (reader/parameters/
ReaderParameters.scala:50-95, CobolParameters.scala:60-88,
VariableLengthParameters.scala:45-66, MultisegmentParameters.scala:22-29).
The string-keyed `.option()` surface lives in cobrix_tpu.api.options.
"""
from __future__ import annotations

from dataclasses import dataclass, field as dc_field
from typing import Dict, List, Optional, Sequence

from ..copybook.datatypes import (
    CommentPolicy,
    DebugFieldsPolicy,
    Encoding,
    FloatingPointFormat,
    SchemaRetentionPolicy,
    TrimPolicy,
)
from .diagnostics import (
    DEFAULT_LEDGER_CAP,
    DEFAULT_RESYNC_WINDOW,
    RecordErrorPolicy,
    ShardErrorPolicy,
)

DEFAULT_FILE_RECORD_ID_INCREMENT = 2 ** 32      # reference reader Constants.scala:28
DEFAULT_INDEX_ENTRY_SIZE_MB = 100
MAX_NUM_PARTITIONS = 2048
MEGABYTE = 1024 * 1024


@dataclass
class MultisegmentParameters:
    segment_id_field: str = ""
    segment_id_filter: Optional[List[str]] = None
    segment_level_ids: List[str] = dc_field(default_factory=list)
    segment_id_prefix: str = ""
    segment_id_redefine_map: Dict[str, str] = dc_field(default_factory=dict)
    field_parent_map: Dict[str, str] = dc_field(default_factory=dict)


@dataclass
class ReaderParameters:
    """Flattened reader configuration (the ~45 option surface)."""

    is_ebcdic: bool = True
    is_text: bool = False
    ebcdic_code_page: str = "common"
    # explicit custom code-page class path; only this field routes through
    # class loading (reference: getCodePageByClass is used only when the
    # codePageClass option is set), so a typo'd plain code-page name with a
    # dot still gets the 'unknown code page' message
    ebcdic_code_page_class: Optional[str] = None
    ascii_charset: str = "us-ascii"
    is_utf16_big_endian: bool = True
    floating_point_format: FloatingPointFormat = FloatingPointFormat.IBM
    variable_size_occurs: bool = False
    record_length_override: Optional[int] = None
    length_field_name: Optional[str] = None
    is_record_sequence: bool = False
    is_rdw_big_endian: bool = False
    is_rdw_part_of_record_length: bool = False
    rdw_adjustment: int = 0
    is_index_generation_needed: bool = False
    input_split_records: Optional[int] = None
    input_split_size_mb: Optional[int] = None
    hdfs_default_block_size: Optional[int] = None
    start_offset: int = 0
    end_offset: int = 0
    file_start_offset: int = 0
    file_end_offset: int = 0
    generate_record_id: bool = False
    schema_policy: SchemaRetentionPolicy = SchemaRetentionPolicy.KEEP_ORIGINAL
    string_trimming_policy: TrimPolicy = TrimPolicy.BOTH
    multisegment: Optional[MultisegmentParameters] = None
    comment_policy: CommentPolicy = dc_field(default_factory=CommentPolicy)
    drop_group_fillers: bool = False
    drop_value_fillers: bool = True
    non_terminals: Sequence[str] = ()
    occurs_mappings: Dict[str, Dict[str, int]] = dc_field(default_factory=dict)
    debug_fields_policy: DebugFieldsPolicy = DebugFieldsPolicy.NONE
    record_header_parser: Optional[str] = None
    record_extractor: Optional[str] = None
    rhp_additional_info: Optional[str] = None
    re_additional_info: str = ""
    input_file_name_column: str = ""
    # column projection: decode only these fields (others emit null).
    # A TPU-native extension — the reference decodes every field per record
    select: Optional[Sequence[str]] = None
    # predicate pushdown: the canonical wire-JSON form of a
    # cobrix_tpu.query filter expression (query/expr.py), normalized by
    # the option parser so every surface (serve 'R' frames, Flight
    # tickets, resume/plan fingerprints) sees ONE deterministic
    # spelling. None = no filter. Bound to the copybook per reader
    # (query/pushdown.BoundFilter); rows failing it are dropped before
    # the full decode wherever a static columnar plan exists
    filter: Optional[str] = None
    # -- fault tolerance (Spark parse-mode analogue; not a reference
    # option — the reference is fail-fast only) --------------------------
    record_error_policy: RecordErrorPolicy = RecordErrorPolicy.FAIL_FAST
    # bounded forward search for the next plausible header after a corrupt
    # run (permissive policies only)
    resync_window_bytes: int = DEFAULT_RESYNC_WINDOW
    # cap on detailed ledger entries (counts are always exact)
    max_corrupt_ledger_entries: int = DEFAULT_LEDGER_CAP
    # name of the optional per-row debug column holding the corruption
    # reason for malformed-but-kept rows ('' = no column)
    corrupt_record_column: str = ""
    # -- IO retry (stream.RetryPolicy inputs) ----------------------------
    io_retry_attempts: int = 3          # total attempts per storage read
    io_retry_base_delay: float = 0.05   # seconds; doubles per attempt
    io_retry_max_delay: float = 2.0     # per-sleep cap, seconds
    io_retry_deadline: float = 30.0     # overall budget per read, seconds
    # -- remote storage io (cobrix_tpu.io; registry-backed schemes only,
    # local files read through the OS page cache) ------------------------
    # on-disk cache root for the persistent block cache AND the
    # sparse-index store ('' = both planes off). Safe to share across
    # processes; entries are keyed by file fingerprint (etag/size/mtime)
    # and invalidate structurally when the remote file changes
    cache_dir: str = ""
    # LRU budget for the block cache, in MB (0 = unbounded)
    cache_max_mb: float = 1024.0
    # read-ahead depth: how many blocks a bounded pool fetches ahead of
    # the consumer (0 = no prefetch). Each stream owns its pool; workers
    # build theirs after fork, so no threads/fds cross processes
    prefetch_blocks: int = 2
    # block granularity (MB) shared by the cache and the prefetcher
    io_block_mb: float = 8.0
    # -- compressed feeds (cobrix_tpu.io.compress) -----------------------
    # codec for compressed inputs: 'auto' (strict magic-byte detection
    # with extension fallback), 'none' (disable detection — read the
    # raw bytes), or a codec name ('gzip'/'zlib'/'bz2'/'xz'/'zstd') to
    # pin a misnamed or extensionless feed
    compression: str = "auto"
    # decompressed-plane granularity (MB): the inflate-index checkpoint
    # stride and the post-decompression block-cache entry size
    compress_block_mb: float = 4.0
    # -- chunked pipeline executor (cobrix_tpu.engine) -------------------
    # worker threads overlapping read -> frame -> decode -> Arrow assembly
    # across chunks. 0 = today's sequential path (the safe fallback);
    # < 0 = auto-size to the machine (min(8, cpu_count))
    pipeline_workers: int = 0
    # target chunk size for fixed-length byte strides AND the default
    # sparse-index split size for variable-length reads when pipelining
    # is on and no explicit input_split option is set (fractional MB
    # accepted — tests force multi-chunk plans on tiny files)
    pipeline_chunk_mb: float = 16.0
    # backpressure bound: chunks concurrently held in flight (raw bytes +
    # decoded columns). 0 = workers + 2
    pipeline_max_inflight: int = 0
    # -- distributed supervision (parallel/supervisor.py + engine
    # watchdog; the Spark task-retry/speculation analogue) ---------------
    # what a shard-level failure (worker crash, deadline, exhausted
    # re-dispatch) does to the scan: fail_fast raises, partial returns the
    # completed shards plus a ShardFailureInfo ledger on ReadDiagnostics
    shard_error_policy: ShardErrorPolicy = ShardErrorPolicy.FAIL_FAST
    # per-shard (multihost) / per-chunk (pipeline) wall deadline; a shard
    # past it is treated as wedged — worker killed + shard re-dispatched.
    # 0 = no deadline (crash detection stays on)
    shard_timeout_s: float = 0.0
    # re-dispatches allowed per shard after crash/timeout/error before the
    # shard counts as failed (total attempts = 1 + shard_max_retries)
    shard_max_retries: int = 2
    # straggler speculation: once enough shard latencies are observed, a
    # shard still running past this quantile of completed latencies gets a
    # duplicate dispatched on an idle worker; first completion wins,
    # duplicates dedupe by shard key. 0 = off (Spark's default too)
    speculative_quantile: float = 0.0
    # whole-scan wall deadline across plan+dispatch+reassembly. 0 = none
    scan_deadline_s: float = 0.0
    # worker heartbeat period (multihost supervision; liveness telemetry)
    heartbeat_interval_s: float = 0.5
    # -- observability (cobrix_tpu.obs) ----------------------------------
    # Chrome-trace/Perfetto JSON output path: when set, the read records
    # trace spans on every execution path (scan -> shard -> chunk ->
    # stage, supervisor events) — including forked multihost workers,
    # merged onto one timeline — and writes the file at read end. '' =
    # tracing off (the ~zero-overhead default)
    trace_file: str = ""
    # inbound trace context: a request-scoped trace id propagated from
    # an upstream caller (the serving tier's 'R' frame, or any in-process
    # orchestrator). '' = mint a fresh id when tracing is on. The id
    # lands in every trace export and the scan audit record, so one
    # request stitches across processes
    trace_id: str = ""
    # caller-assigned request id carried on the trace root span and the
    # audit record ('' = none); purely identifying, never behavioral
    request_id: str = ""
    # minimum seconds between progress_callback invocations (the final
    # done=True snapshot always fires)
    progress_interval_s: float = 0.5
    # per-field/kernel-group cost attribution (obs.fieldcost): timers
    # around each kernel-group launch and Arrow-assembly column step,
    # surfaced as ReadMetrics.field_costs and the explain cost table.
    # Off by default — the disabled path takes zero timestamps;
    # `read_cobol(..., explain=True)` forces it on for that read
    field_costs: bool = False
    # -- streaming delivery (batch_callback / cobrix_tpu.serve) ----------
    # cap on rows per emitted Arrow record batch when results stream out
    # incrementally (a serving client shouldn't receive one giant batch
    # per 16 MB chunk if it renders incrementally). 0 = one batch per
    # assembled chunk/file table
    stream_batch_rows: int = 0
    # -- scan-time data profiler (cobrix_tpu.stats) ----------------------
    # collect per-chunk per-field statistics (zone maps, null counts,
    # segment histograms) on a canonical grid after the read and persist
    # them under <cache_dir>/stats/. Requires cache_dir. Off = the stats
    # package is never even imported
    collect_stats: bool = False
    # consume persisted profiles: chunk skipping before framing (with a
    # filter) and stats-answered dataset aggregates. Requires cache_dir
    use_stats: bool = False
    # the profiler's canonical chunk grid stride, in MB (fractional
    # accepted — tests force multi-chunk profiles on tiny files).
    # Deliberately NOT part of the profile's config fingerprint: skip
    # decisions are grid-independent (stats/skip.py union coverage)
    stats_chunk_mb: float = 4.0

    def resolved_pipeline_workers(self) -> int:
        """Effective worker count: 0 = sequential, negative = auto."""
        if self.pipeline_workers >= 0:
            return self.pipeline_workers
        import os

        return min(8, os.cpu_count() or 1)

    @property
    def is_permissive(self) -> bool:
        """True when malformed records are tolerated (resync + ledger
        instead of a raised error)."""
        return self.record_error_policy is not RecordErrorPolicy.FAIL_FAST

    def new_diagnostics(self):
        """A fresh per-read/shard error ledger sized by this config."""
        from .diagnostics import ReadDiagnostics

        return ReadDiagnostics(max_entries=self.max_corrupt_ledger_entries)

    @property
    def data_encoding(self) -> Encoding:
        return Encoding.EBCDIC if self.is_ebcdic else Encoding.ASCII

    @property
    def is_variable_length(self) -> bool:
        """The option-validation predicate for per-record input-file
        tracking (reference CobolParametersParser.scala:576-581 —
        generate_record_id alone does NOT enable it)."""
        return bool(self.is_record_sequence or self.is_text
                    or self.variable_size_occurs or self.length_field_name
                    or self.record_extractor or self.file_start_offset > 0
                    or self.file_end_offset > 0)

    @property
    def supports_fast_framing(self) -> bool:
        """True when whole-shard vectorized RDW framing applies (no custom
        extractors/parsers, no text mode, no length fields) — also the
        gate for pipeline auto-splitting, where split granularity is
        pinned row-identical by the indexed-scan tests. The RDW gives
        every record's length, so `variable_size_occurs` does not stand
        in its way: a file of variable-size OCCURS records is framed by
        the native scanner and cut into index shards like any other RDW
        file (without RDW its length is the walk: VarOccursRecordExtractor,
        record by record)."""
        return bool(self.is_record_sequence
                    and not (self.record_extractor
                             or self.record_header_parser
                             or self.is_text or self.length_field_name))

    @property
    def needs_var_len_reader(self) -> bool:
        """True when the configuration routes through the variable-length
        reader. Wider than `is_variable_length`: generate_record_id alone
        makes the reference's variableLengthParams Some(...), so the varlen
        reader (with a fixed-length header parser) handles the read — which
        is why Seg_Id generation and segment filtering work on fixed-size
        records only when record ids are generated
        (CobolParametersParser.parseVariableLengthParameters:~253-262,
        DefaultSource.buildEitherReader:72-81)."""
        return self.is_variable_length or self.generate_record_id
