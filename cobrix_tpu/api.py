"""User-facing API: `read_cobol(path, copybook=..., **options)`.

The equivalent of the reference's Spark DataSource surface
(`spark.read.format("cobol").option(...).load(path)` — DefaultSource.scala:50,
CobolRelation.scala:85, CobolParametersParser.scala:191): the same ~45
string-keyed options, the same pedantic/unused-key auditing and option
incompatibility matrices, a deterministic multi-file ordering with per-file
Record_Id bases, and output as columns/rows/pandas/Arrow instead of an RDD.
"""
from __future__ import annotations

import glob as _glob
import json
import os
from typing import (TYPE_CHECKING, Dict, Iterable, List, Optional,
                    Sequence, Tuple, Union)

if TYPE_CHECKING:  # explain.py imports api; annotation only
    from .explain import ScanReport

import numpy as np

from .copybook.copybook import Copybook
from .copybook.datatypes import (
    CommentPolicy,
    DebugFieldsPolicy,
    FloatingPointFormat,
    SchemaRetentionPolicy,
    TrimPolicy,
)
from .reader.diagnostics import (
    DEFAULT_LEDGER_CAP,
    DEFAULT_RESYNC_WINDOW,
    ReadDiagnostics,
    RecordErrorPolicy,
    ShardErrorPolicy,
    ShardFailureInfo,
)
from .reader.columnar import validate_backend
from .reader.fixed_len_reader import FixedLenReader
from .reader.json_out import rows_to_json
from .reader.parameters import (
    DEFAULT_FILE_RECORD_ID_INCREMENT,
    MultisegmentParameters,
    ReaderParameters,
)
from .profiling import PoolWait, ReadMetrics, Stage, stage, timed_stage
from .reader.result import FileResult, rows_file_result
from .reader.schema import CobolOutputSchema, StructType
from .reader.stream import RetryPolicy, open_stream, path_scheme
from .reader.var_len_reader import VarLenReader, default_segment_id_prefix


class Options:
    """Option map wrapper tracking key usage for pedantic-mode auditing
    (reference Parameters.scala:27-98)."""

    def __init__(self, options: Dict[str, object]):
        # Python-native callers pass mappings/lists directly (e.g.
        # occurs_mapping as a dict); the option layer is string-keyed like
        # the reference's .option() map, so structured values carry as
        # JSON. query.Expr filters serialize via their canonical wire
        # form, NOT str() — the grammar spelling cannot express fields
        # named like its own keywords (SEGMENT, IN, NOT, ...)
        self._map = {str(k): (json.dumps(v) if isinstance(v, (dict, list))
                              else v.canonical() if hasattr(v, "canonical")
                              else str(v))
                     for k, v in options.items()}
        self._used = set()

    def get(self, key: str, default: Optional[str] = None) -> Optional[str]:
        if key in self._map:
            self._used.add(key)
            return self._map[key]
        return default

    def __contains__(self, key: str) -> bool:
        return key in self._map

    def mark_used(self, key: str) -> None:
        self._used.add(key)

    def get_bool(self, key: str, default: bool = False) -> bool:
        v = self.get(key)
        if v is None:
            return default
        return v.strip().lower() in ("true", "1", "yes")

    def get_int(self, key: str, default: Optional[int] = None) -> Optional[int]:
        v = self.get(key)
        return default if v is None else int(v)

    def keys(self):
        return self._map.keys()

    def unused_keys(self) -> List[str]:
        return [k for k in self._map if k not in self._used]


_ENUM_PARSERS = {
    "schema_retention_policy": {
        "keep_original": SchemaRetentionPolicy.KEEP_ORIGINAL,
        "collapse_root": SchemaRetentionPolicy.COLLAPSE_ROOT,
    },
    "string_trimming_policy": {
        "none": TrimPolicy.NONE, "left": TrimPolicy.LEFT,
        "right": TrimPolicy.RIGHT, "both": TrimPolicy.BOTH,
    },
    "floating_point_format": {
        "ibm": FloatingPointFormat.IBM,
        "ibm_little_endian": FloatingPointFormat.IBM_LE,
        "ieee754": FloatingPointFormat.IEEE754,
        "ieee754_little_endian": FloatingPointFormat.IEEE754_LE,
    },
    "debug": {
        "false": DebugFieldsPolicy.NONE, "none": DebugFieldsPolicy.NONE,
        "true": DebugFieldsPolicy.HEX, "hex": DebugFieldsPolicy.HEX,
        "raw": DebugFieldsPolicy.RAW,
    },
}


def _normalize_filter_option(value: Optional[str]) -> Optional[str]:
    """The `filter` option (grammar text, wire JSON, or the str() of a
    query.Expr — all strings by the time the option layer sees them)
    -> canonical wire JSON. Raises ValueError with the parse position
    on malformed input, BEFORE any data is read."""
    if not value:
        return None
    from .query.expr import normalize_filter

    try:
        return normalize_filter(value)
    except (ValueError, TypeError) as exc:
        raise ValueError(f"Invalid 'filter' option: {exc}") from exc


def _parse_enum(opts: Options, key: str, default: str):
    value = opts.get(key, default)
    table = _ENUM_PARSERS[key]
    parsed = table.get(value.strip().lower())
    if parsed is None:
        raise ValueError(f"Invalid value '{value}' for '{key}' option.")
    return parsed


def _parse_segment_levels(opts: Options) -> List[str]:
    levels = []
    i = 0
    while True:
        name = f"segment_id_level{i}"
        if name in opts:
            levels.append(opts.get(name))
        elif i == 0 and "segment_id_root" in opts:
            levels.append(opts.get("segment_id_root"))
        else:
            return levels
        i += 1


def _parse_prefixed_map(opts: Options,
                        prefixes: Tuple[str, ...]) -> Dict[str, str]:
    """Parse 'redefine-segment-id-map:N' / 'segment-children:N' options
    ('FIELD => A,B') into {item: field} (segment-id -> redefine name, or
    child -> parent respectively)."""
    from .copybook.ast import transform_identifier
    out: Dict[str, str] = {}
    for key in list(opts.keys()):
        k = key.lower()
        if any(k.startswith(p) for p in prefixes):
            opts.mark_used(key)
            value = opts.get(key)
            parts = value.split("=>")
            if len(parts) != 2:
                raise ValueError(
                    f"Illegal argument for the '{prefixes[0]}' option: '{value}'.")
            field = transform_identifier(parts[0].strip())
            for item in (transform_identifier(s.strip())
                         for s in parts[1].split(",")):
                out[item] = field
    return out


def parse_options(options: Dict[str, object],
                  streaming: bool = False) -> Tuple[ReaderParameters, Options]:
    """String options -> typed ReaderParameters
    (reference CobolParametersParser.parse, :191). `streaming`: relax the
    per-record input-file-column gate — the micro-batch streamer tracks
    file names per batch even for fixed-length records."""
    opts = Options(options)

    encoding = (opts.get("encoding", "") or "").strip().lower()
    if encoding not in ("", "ebcdic", "ascii"):
        raise ValueError(f"Invalid value '{encoding}' for 'encoding' option. "
                         "Should be either 'EBCDIC' or 'ASCII'.")
    is_ebcdic = encoding in ("", "ebcdic")

    comment_policy = CommentPolicy(
        truncate_comments=opts.get_bool("truncate_comments", True),
        comments_up_to_char=opts.get_int("comments_lbound", 6),
        comments_after_char=opts.get_int("comments_ubound", 72))
    if not comment_policy.truncate_comments and (
            "comments_lbound" in options or "comments_ubound" in options):
        raise ValueError(
            "When 'truncate_comments=false' the following parameters cannot be "
            "used: 'comments_lbound', 'comments_ubound'.")

    is_record_sequence = (opts.get_bool("is_xcom") or
                          opts.get_bool("is_record_sequence"))
    if "record_length_field" in opts and (
            "is_record_sequence" in opts or "is_xcom" in opts):
        raise ValueError("Option 'record_length_field' cannot be used together "
                         "with 'is_record_sequence' or 'is_xcom'.")

    multisegment = None
    if "segment_field" in opts:
        filter_str = opts.get("segment_filter")
        multisegment = MultisegmentParameters(
            segment_id_field=opts.get("segment_field"),
            segment_id_filter=filter_str.split(",") if filter_str else None,
            segment_level_ids=_parse_segment_levels(opts),
            segment_id_prefix=opts.get("segment_id_prefix", ""),
            segment_id_redefine_map=_parse_prefixed_map(
                opts, ("redefine-segment-id-map", "redefine_segment_id_map")),
            field_parent_map=_parse_prefixed_map(
                opts, ("segment-children", "segment_children")))

    occurs_mappings = {}
    # the reference README documents the singular key (`occurs_mapping`,
    # README.md:1101); both spellings are accepted, but not together
    occurs_keys = [k for k in ("occurs_mappings", "occurs_mapping")
                   if k in opts]
    if len(occurs_keys) > 1:
        raise ValueError(
            "Options 'occurs_mappings' and 'occurs_mapping' cannot be "
            "specified at the same time")
    if occurs_keys:
        occurs_mappings = {
            k: {sk: int(sv) for sk, sv in v.items()}
            for k, v in json.loads(opts.get(occurs_keys[0])).items()}

    non_terminals = tuple(
        s for s in (opts.get("non_terminals", "") or "").split(",") if s)

    params = ReaderParameters(
        is_ebcdic=is_ebcdic,
        is_text=opts.get_bool("is_text"),
        ebcdic_code_page=opts.get("ebcdic_code_page", "common"),
        ebcdic_code_page_class=opts.get("ebcdic_code_page_class"),
        ascii_charset=opts.get("ascii_charset", "") or "us-ascii",
        is_utf16_big_endian=opts.get_bool("is_utf16_big_endian", True),
        floating_point_format=_parse_enum(opts, "floating_point_format", "ibm"),
        variable_size_occurs=opts.get_bool("variable_size_occurs"),
        record_length_override=opts.get_int("record_length"),
        length_field_name=opts.get("record_length_field"),
        is_record_sequence=is_record_sequence,
        is_rdw_big_endian=opts.get_bool("is_rdw_big_endian"),
        is_rdw_part_of_record_length=opts.get_bool("is_rdw_part_of_record_length"),
        rdw_adjustment=opts.get_int("rdw_adjustment", 0),
        is_index_generation_needed=opts.get_bool("enable_indexes", True),
        input_split_records=opts.get_int("input_split_records"),
        input_split_size_mb=opts.get_int("input_split_size_mb"),
        start_offset=opts.get_int("record_start_offset", 0),
        end_offset=opts.get_int("record_end_offset", 0),
        file_start_offset=opts.get_int("file_start_offset", 0),
        file_end_offset=opts.get_int("file_end_offset", 0),
        generate_record_id=opts.get_bool("generate_record_id"),
        schema_policy=_parse_enum(opts, "schema_retention_policy", "keep_original"),
        string_trimming_policy=_parse_enum(opts, "string_trimming_policy", "both"),
        multisegment=multisegment,
        comment_policy=comment_policy,
        drop_group_fillers=opts.get_bool("drop_group_fillers"),
        drop_value_fillers=opts.get_bool("drop_value_fillers", True),
        non_terminals=non_terminals,
        occurs_mappings=occurs_mappings,
        debug_fields_policy=_parse_enum(opts, "debug", "false"),
        record_header_parser=opts.get("record_header_parser"),
        record_extractor=opts.get("record_extractor"),
        rhp_additional_info=opts.get("rhp_additional_info"),
        re_additional_info=opts.get("re_additional_info", ""),
        input_file_name_column=opts.get("with_input_file_name_col", ""),
        select=tuple(s.strip() for s in opts.get("select", "").split(",")
                     if s.strip()) or None,
        filter=_normalize_filter_option(opts.get("filter")),
        record_error_policy=RecordErrorPolicy.parse(
            opts.get("record_error_policy", "fail_fast")),
        resync_window_bytes=opts.get_int("resync_window",
                                         DEFAULT_RESYNC_WINDOW),
        max_corrupt_ledger_entries=opts.get_int(
            "max_corrupt_ledger_entries", DEFAULT_LEDGER_CAP),
        corrupt_record_column=opts.get("corrupt_record_column", ""),
        io_retry_attempts=opts.get_int("io_retry_attempts", 3),
        io_retry_base_delay=float(
            opts.get_int("io_retry_base_delay_ms", 50)) / 1000.0,
        io_retry_max_delay=float(
            opts.get_int("io_retry_max_delay_ms", 2000)) / 1000.0,
        io_retry_deadline=float(
            opts.get_int("io_retry_deadline_ms", 30000)) / 1000.0,
        cache_dir=opts.get("cache_dir", "") or "",
        cache_max_mb=float(opts.get("cache_max_mb", "") or 1024.0),
        prefetch_blocks=opts.get_int("prefetch_blocks", 2),
        io_block_mb=float(opts.get("io_block_mb", "") or 8.0),
        compression=(opts.get("compression", "auto") or "auto").lower(),
        compress_block_mb=float(
            opts.get("compress_block_mb", "") or 4.0),
        pipeline_workers=opts.get_int("pipeline_workers", 0),
        pipeline_chunk_mb=float(opts.get("chunk_size_mb", "") or 16.0),
        pipeline_max_inflight=opts.get_int("max_inflight_chunks", 0),
        shard_error_policy=ShardErrorPolicy.parse(
            opts.get("shard_error_policy", "fail_fast")),
        shard_timeout_s=float(opts.get("shard_timeout_s", "") or 0.0),
        shard_max_retries=opts.get_int("shard_max_retries", 2),
        speculative_quantile=float(
            opts.get("speculative_quantile", "") or 0.0),
        scan_deadline_s=float(opts.get("scan_deadline_s", "") or 0.0),
        heartbeat_interval_s=float(
            opts.get("heartbeat_interval_s", "") or 0.5),
        trace_file=opts.get("trace_file", "") or "",
        trace_id=opts.get("trace_id", "") or "",
        request_id=opts.get("request_id", "") or "",
        progress_interval_s=float(
            opts.get("progress_interval_s", "") or 0.5),
        stream_batch_rows=opts.get_int("stream_batch_rows", 0),
        field_costs=opts.get_bool("field_costs"),
        collect_stats=opts.get_bool("collect_stats"),
        use_stats=opts.get_bool("use_stats"),
        stats_chunk_mb=float(opts.get("stats_chunk_mb", "") or 4.0),
    )
    # recognized keys consumed later by read_cobol — mark used before the
    # pedantic unused-key audit runs
    opts.get_bool("debug_ignore_file_size")
    opts.get_int("parallelism", 0)
    opts.get_int("hosts", 0)
    # HDFS-locality knobs (LocalityParameters.scala:21-30): accepted for
    # workload compatibility; shard placement here has no HDFS block
    # topology to optimize (SURVEY.md §2.5 — locality consciously
    # dropped). `optimize_allocation` maps to the idle re-allocation
    # pass of the static planner (parallel.planner.balance,
    # LocationBalancer.scala:42-66 analogue) for callers that use it;
    # the supervised multihost scheduler load-balances dynamically and
    # needs no static pass
    opts.get_bool("improve_locality", True)
    opts.get_bool("optimize_allocation")
    _validate_options(opts, params, streaming)
    return params, opts


def _validate_options(opts: Options, params: ReaderParameters,
                      streaming: bool = False) -> None:
    """Option incompatibility matrices + pedantic unused-key audit
    (reference validateSparkCobolOptions, :473-610)."""
    rdw_ish = ["is_text", "record_length", "is_record_sequence", "is_xcom",
               "is_rdw_big_endian", "is_rdw_part_of_record_length",
               "rdw_adjustment", "record_length_field",
               "record_header_parser", "rhp_additional_info"]
    if "record_extractor" in opts:
        bad = [k for k in rdw_ish if k in opts]
        if bad:
            raise ValueError(
                f"Option 'record_extractor' and {', '.join(bad)} cannot be "
                "used together.")
    if "record_length" in opts:
        bad = [k for k in rdw_ish[2:] if k in opts] \
            + (["is_text"] if "is_text" in opts else [])
        if bad:
            raise ValueError(
                f"Option 'record_length' and {', '.join(bad)} cannot be "
                "used together.")
    if params.input_file_name_column and not streaming:
        if not params.is_variable_length:
            raise ValueError(
                "Option 'with_input_file_name_col' is supported only when "
                "one of this holds: 'is_record_sequence' = true or "
                "'variable_size_occurs' = true or one of these options is "
                "set: 'record_length_field', 'file_start_offset', "
                "'file_end_offset' or a custom record extractor is specified")
    if params.corrupt_record_column and not params.is_permissive:
        raise ValueError(
            "Option 'corrupt_record_column' requires "
            "record_error_policy='permissive' or 'drop_malformed' "
            "(under 'fail_fast' the first malformed record raises instead "
            "of being recorded).")
    if params.resync_window_bytes <= 0:
        raise ValueError(
            f"Invalid 'resync_window' of {params.resync_window_bytes} "
            "bytes; it must be a positive byte count.")
    if params.io_retry_attempts < 1:
        raise ValueError(
            f"Invalid 'io_retry_attempts' of {params.io_retry_attempts}; "
            "at least one attempt is required.")
    if params.cache_max_mb < 0:
        raise ValueError(
            f"Invalid 'cache_max_mb' of {params.cache_max_mb}; it must "
            "be >= 0 (0 = unbounded).")
    if params.prefetch_blocks < 0:
        raise ValueError(
            f"Invalid 'prefetch_blocks' of {params.prefetch_blocks}; it "
            "must be >= 0 (0 disables read-ahead).")
    if params.io_block_mb <= 0:
        raise ValueError(
            f"Invalid 'io_block_mb' of {params.io_block_mb}; it must be "
            "a positive block size in megabytes.")
    if params.compression not in ("auto", "none", "off", "raw"):
        from .io.compress import codec_by_name

        try:
            codec_by_name(params.compression)
        except ValueError as exc:
            raise ValueError(f"Invalid 'compression' option: {exc}")
    if params.compress_block_mb <= 0:
        raise ValueError(
            f"Invalid 'compress_block_mb' of {params.compress_block_mb}; "
            "it must be a positive block size in megabytes.")
    if params.cache_dir:
        cache_parent = os.path.dirname(
            os.path.abspath(params.cache_dir)) or "."
        if not os.path.isdir(cache_parent):
            raise ValueError(
                f"Invalid 'cache_dir' '{params.cache_dir}': parent "
                f"directory '{cache_parent}' does not exist.")
    if params.pipeline_chunk_mb <= 0:
        raise ValueError(
            f"Invalid 'chunk_size_mb' of {params.pipeline_chunk_mb}; "
            "it must be a positive size in megabytes.")
    if params.pipeline_max_inflight < 0:
        raise ValueError(
            f"Invalid 'max_inflight_chunks' of "
            f"{params.pipeline_max_inflight}; it must be >= 0 "
            "(0 sizes it from the worker count).")
    if params.shard_timeout_s < 0:
        raise ValueError(
            f"Invalid 'shard_timeout_s' of {params.shard_timeout_s}; "
            "it must be >= 0 (0 disables the per-shard deadline).")
    if params.scan_deadline_s < 0:
        raise ValueError(
            f"Invalid 'scan_deadline_s' of {params.scan_deadline_s}; "
            "it must be >= 0 (0 disables the whole-scan deadline).")
    if params.shard_max_retries < 0:
        raise ValueError(
            f"Invalid 'shard_max_retries' of {params.shard_max_retries}; "
            "it must be >= 0 (0 means a failed shard is never "
            "re-dispatched).")
    if not 0.0 <= params.speculative_quantile < 1.0:
        raise ValueError(
            f"Invalid 'speculative_quantile' of "
            f"{params.speculative_quantile}; it must be in [0, 1) "
            "(0 disables straggler speculation).")
    if params.heartbeat_interval_s <= 0:
        raise ValueError(
            f"Invalid 'heartbeat_interval_s' of "
            f"{params.heartbeat_interval_s}; it must be positive.")
    if params.progress_interval_s < 0:
        raise ValueError(
            f"Invalid 'progress_interval_s' of "
            f"{params.progress_interval_s}; it must be >= 0 "
            "(0 invokes the callback on every completed chunk).")
    if params.stream_batch_rows < 0:
        raise ValueError(
            f"Invalid 'stream_batch_rows' of {params.stream_batch_rows}; "
            "it must be >= 0 (0 streams one batch per assembled chunk).")
    if (params.collect_stats or params.use_stats) \
            and not params.cache_dir:
        raise ValueError(
            "Options 'collect_stats'/'use_stats' require 'cache_dir': "
            "profiles persist in (and load from) the cache directory's "
            "stats plane.")
    if params.stats_chunk_mb <= 0:
        raise ValueError(
            f"Invalid 'stats_chunk_mb' of {params.stats_chunk_mb}; it "
            "must be a positive size in megabytes.")
    if params.trace_file:
        # fail BEFORE the scan, not after minutes of decode: the trace is
        # written at read end, so an unwritable destination would
        # otherwise discard a fully successful read
        trace_dir = os.path.dirname(params.trace_file) or "."
        if not os.path.isdir(trace_dir):
            raise ValueError(
                f"Invalid 'trace_file' '{params.trace_file}': directory "
                f"'{trace_dir}' does not exist.")
        if not os.access(trace_dir, os.W_OK):
            raise ValueError(
                f"Invalid 'trace_file' '{params.trace_file}': directory "
                f"'{trace_dir}' is not writable.")
    seg = params.multisegment
    if seg and seg.field_parent_map and seg.segment_level_ids:
        raise ValueError(
            "Options 'segment_id_level*'/'segment_id_root' and "
            "'segment-children:*' cannot be used together.")
    if seg and seg.field_parent_map and not seg.segment_id_redefine_map:
        raise ValueError(
            "Option 'segment-children:*' requires 'redefine-segment-id-map:*' "
            "to be set as well.")
    pedantic = opts.get_bool("pedantic")  # marks the key used
    unused = opts.unused_keys()
    if unused and pedantic:
        raise ValueError("Redundant or unrecognized option(s) to 'spark-cobol': "
                         + ", ".join(sorted(unused)) + ".")


def load_copybook_contents(copybook, copybook_contents):
    """Resolve the copybook SOURCE the way `read_cobol` does: exactly
    one of `copybook` (path or list of paths) / `copybook_contents`
    (text), with the reference's error messages. Shared with the
    continuous-ingest surface (streaming.ingest) so the loading rules
    can never drift between entry points."""
    if copybook is not None and copybook_contents is not None:
        raise ValueError("Both 'copybook' and 'copybook_contents' options "
                         "cannot be specified at the same time")
    if copybook_contents is not None:
        return copybook_contents
    if copybook is None:
        raise ValueError(
            "COPYBOOK is not provided. Please, provide either 'copybook' "
            "path or 'copybook_contents'.")
    books = [copybook] if isinstance(copybook, str) else list(copybook)
    contents = []
    for b in books:
        if os.path.exists(b) and not os.path.isfile(b):
            raise ValueError(f"The copybook path '{b}' is not a file.")
        with open(b, encoding="utf-8") as f:
            contents.append(f.read())
    return contents if len(contents) > 1 else contents[0]


def list_input_files(path) -> List[str]:
    """Recursive globbed listing skipping hidden files, stable order
    (reference FileUtils.scala:54-228, getListFilesWithOrder)."""
    from .reader.stream import normalize_local, path_scheme, stream_lister

    paths = [path] if isinstance(path, str) else list(path)
    out: List[str] = []
    for p in paths:
        scheme = path_scheme(p)
        if scheme not in (None, "file"):
            # registry-backed storage: backends with a listing capability
            # (the fsspec adapter and anything registered with `lister=`)
            # expand directories/globs remotely; others pass through
            # verbatim as one input
            lister = stream_lister(scheme)
            if lister is not None:
                out.extend(lister(p))
            else:
                out.append(p)
            continue
        # file:// never propagates past listing: downstream os.path
        # consumers see plain local paths
        p = normalize_local(p)
        if os.path.isdir(p):
            for root, dirs, files in os.walk(p):
                dirs[:] = sorted(d for d in dirs if not d.startswith((".", "_")))
                for f in sorted(files):
                    if not f.startswith((".", "_")):
                        out.append(os.path.join(root, f))
        elif os.path.isfile(p):
            out.append(p)
        else:
            matched = sorted(_glob.glob(p))
            if not matched:
                raise FileNotFoundError(f"Input path does not exist: {p}")
            for m in matched:
                out.extend(list_input_files(m))
    return out


class CobolData:
    """Decoded result: per-file columnar results + schema, materializable
    as rows, JSON lines, pandas, or Arrow. Arrow tables are built straight
    from the kernel output arrays (reader/arrow_out.py); Python rows are
    materialized only when asked for."""

    def __init__(self, rows, schema: CobolOutputSchema,
                 results: Optional[List["FileResult"]] = None,
                 parallelism: int = 1):
        self._rows = rows
        self._results = results
        self._arrow_tables = None
        self.output_schema = schema
        self.parallelism = parallelism
        # structured per-read metrics (profiling.ReadMetrics); populated by
        # read_cobol
        self.metrics: Optional[ReadMetrics] = None
        # the read's error ledger (permissive policies; None under
        # fail_fast) — aggregated over every file/shard by read_cobol
        self.diagnostics: Optional[ReadDiagnostics] = None
        # copybook plan fingerprint (plan.cache.parse_fingerprint),
        # stamped by read_cobol — the sink's schema-drift sentinel
        self.plan_fingerprint: str = ""

    @classmethod
    def from_results(cls, results: List["FileResult"],
                     schema: CobolOutputSchema,
                     parallelism: int = 1) -> "CobolData":
        return cls(None, schema, results, parallelism=parallelism)

    @classmethod
    def from_arrow_tables(cls, tables, schema: CobolOutputSchema
                          ) -> "CobolData":
        """Multi-host results: the columnar product arrived as Arrow
        tables (one per shard, already in record order)."""
        data = cls(None, schema, None)
        data._arrow_tables = tables
        return data

    @property
    def schema(self) -> StructType:
        return self.output_schema.schema

    def __len__(self) -> int:
        if self._arrow_tables is not None:
            return sum(t.num_rows for t in self._arrow_tables)
        if self._rows is not None:
            return len(self._rows)
        return sum(r.n_rows for r in self._results)

    def to_rows(self) -> List[List[object]]:
        if self._arrow_tables is not None:
            raise NotImplementedError(
                "multi-host (hosts=N) results are Arrow-backed; use "
                "to_arrow()/to_pandas(), or read without `hosts` for "
                "Python row materialization")
        if self._rows is None:
            rows: List[List[object]] = []
            for r in self._results:
                rows.extend(r.to_rows())
            self._rows = rows
        return self._rows

    def to_dicts(self) -> List[dict]:
        names = self.schema.field_names()
        return [dict(zip(names, row)) for row in self.to_rows()]

    def to_json_lines(self) -> List[str]:
        return rows_to_json(self.to_rows(), self.schema)

    def to_pandas(self):
        return self.to_arrow().to_pandas()

    def to_dataset(self, dataset_dir: str, file_format: str = "parquet",
                   partition_by=(), target_file_mb: float = 64.0,
                   retry=None):
        """One-shot atomic export into a transactional sink dataset
        (`cobrix_tpu.sink`): every data file is staged and finalized,
        then ONE manifest record commits them all — a crash at any
        instant leaves the dataset exactly as it was. Re-exporting into
        the same dataset appends a new commit; a dataset written under
        a different copybook/schema fingerprint is refused
        (`SinkSchemaError`). Returns the `DatasetSink` (its
        ``recovery`` report and ``to_table()`` read-back included)."""
        from .reader.arrow_out import arrow_schema as _arrow_schema
        from .sink import DatasetSink, schema_fingerprint

        schema = _arrow_schema(self.schema)
        sink = DatasetSink(
            dataset_dir, arrow_schema=schema,
            schema_fp=schema_fingerprint(schema, self.plan_fingerprint),
            file_format=file_format, partition_by=partition_by,
            target_file_mb=target_file_mb, retry=retry)
        sink.commit_table(self.to_arrow(), source="read_cobol")
        return sink

    def to_ebcdic(self, path: Optional[str] = None, *,
                  framing: str = "fixed",
                  rdw_big_endian: bool = False,
                  rdw_adjustment: int = 0,
                  rdw_part_of_record_length: bool = False,
                  variable_size_occurs: bool = False,
                  truncate: bool = True,
                  fill_byte: Optional[int] = None):
        """Encode the decoded records back to mainframe binary (the write
        half of the bridge: the sink emits Parquet, this emits
        fixed-length or RDW-framed EBCDIC/ASCII consumable by the same
        copybook). Generated columns (File_Id/Record_Id/Seg_Id*/input
        file name/corrupt-record) are stripped; the data columns are
        re-encoded through `cobrix_tpu.encode` against this read's
        copybook. Returns the bytes, or writes to `path` and returns
        None."""
        from .encode.encoder import RecordEncoder

        schema = self.output_schema
        enc = RecordEncoder(schema.copybook, policy=schema.policy,
                            variable_size_occurs=variable_size_occurs,
                            fill_byte=fill_byte)
        nseg = schema.generate_seg_id_field_count
        lead = ((3 + nseg) if (schema.generate_record_id
                               and schema.input_file_name_field)
                else (2 + nseg) if schema.generate_record_id
                else (nseg + 1) if schema.input_file_name_field
                else nseg)
        tail = -1 if schema.corrupt_record_field else None

        def bodies():
            for row in self.to_rows():
                yield row[lead:tail]

        import io as _io
        sink = _io.BytesIO() if path is None else open(path, "wb")
        try:
            if framing == "fixed":
                enc.encode_fixed(bodies(), sink)
            elif framing == "rdw":
                enc.encode_rdw(
                    bodies(), sink, big_endian=rdw_big_endian,
                    adjustment=rdw_adjustment,
                    part_of_record_length=rdw_part_of_record_length,
                    truncate=truncate)
            else:
                raise ValueError(f"Unknown framing '{framing}' (fixed|rdw)")
        finally:
            if path is not None:
                sink.close()
        return sink.getvalue() if path is None else None

    @property
    def _stage_stats(self):
        """The read's DeviceStats: where the stages of work done after
        the read (its obs context is gone) are counted."""
        return self.metrics.device_stats if self.metrics is not None \
            else None

    def to_arrow(self):
        """pyarrow Table with schema-declared types, built from the kernel
        outputs without row materialization (the reference must feed Spark
        rows, SparkCobolRowType.scala:24; a columnar framework emits
        columns). The seconds of every call accumulate in
        `metrics.timings_s["to_arrow"]`; what `_to_arrow_impl` does beyond
        the per-batch builds (concatenation, the record-order take) is
        the caller's thread's `assemble.table` stage."""
        with stage(self.metrics, "to_arrow"), Stage("assemble.table",
                                                    self._stage_stats):
            table = self._to_arrow_impl()
        if (self.metrics is not None
                and self.metrics.field_costs_acc is not None):
            # sequential assembly ran after the trace was written; fold
            # its accrued per-field costs back into the artifact
            self.metrics.refresh_trace_field_costs()
        return table

    def _to_arrow_impl(self):
        import pyarrow as pa

        from .reader.arrow_out import arrow_schema, rows_to_table

        if self._arrow_tables is not None:
            if not self._arrow_tables:
                return self._stamp(arrow_schema(self.schema).empty_table())
            return self._stamp(
                self._arrow_tables[0] if len(self._arrow_tables) == 1
                else pa.concat_tables(self._arrow_tables))
        if self._results is None:
            return self._stamp(rows_to_table(self._rows, self.schema))
        if self.parallelism > 1 and len(self._results) > 1:
            # per-shard table builds release the GIL inside Arrow; shard
            # order preserves record order, so concat needs no reordering
            from concurrent.futures import ThreadPoolExecutor

            with ThreadPoolExecutor(
                    max_workers=min(self.parallelism,
                                    len(self._results))) as ex:
                with PoolWait(self._stage_stats):
                    tables = list(ex.map(
                        lambda r: r.to_arrow(self.output_schema),
                        self._results))
        else:
            tables = [r.to_arrow(self.output_schema) for r in self._results]
        if not tables:
            return self._stamp(arrow_schema(self.schema).empty_table())
        return self._stamp(tables[0] if len(tables) == 1
                           else pa.concat_tables(tables))

    def _stamp(self, table):
        """Attach the read's error ledger to the Arrow schema metadata
        (key 'cobrix_tpu.read_diagnostics', JSON) so the fault record
        travels with the data through downstream Arrow/Parquet sinks."""
        if self.diagnostics is None:
            return table
        metadata = dict(table.schema.metadata or {})
        metadata[b"cobrix_tpu.read_diagnostics"] = \
            self.diagnostics.to_json().encode()
        return table.replace_schema_metadata(metadata)


def _retry_policy(params: ReaderParameters) -> RetryPolicy:
    """The read's IO retry policy for registry-backed storage."""
    return RetryPolicy(max_attempts=params.io_retry_attempts,
                       base_delay=params.io_retry_base_delay,
                       max_delay=params.io_retry_max_delay,
                       deadline=params.io_retry_deadline)


def _io_config(params: ReaderParameters):
    """The read's remote-IO configuration (None = all features off)."""
    from .io.config import IoConfig

    return IoConfig.from_params(params)


def _total_input_bytes(files: Sequence[str], io_stats=None,
                       io=None, retry: Optional[RetryPolicy] = None,
                       on_retry=None) -> int:
    """LOGICAL input bytes across local AND backend-resolved files
    (progress totals + throughput metrics — decompressed bytes for
    compressed feeds, since every downstream offset lives in that
    space); sizing failures never fail the read — an unknown size just
    reports as 0. This runs before the read's obs context activates, so
    it activates a sizing-scoped context around the probes: remote
    sizes, codec sniffs, and inflate-discovery results all seed the
    read's metadata memo for the planners and validators downstream.
    Probes run under the read's retry policy so transient backend
    failures here are retried AND ledgered like any other IO."""
    from .io.compress import active_codec
    from .obs.context import ObsContext
    from .obs.context import activate as obs_activate
    from .reader.stream import source_size

    total = 0
    with obs_activate(ObsContext(io_stats=io_stats)
                      if io_stats is not None else None):
        for f in files:
            try:
                if path_scheme(f) in (None, "file"):
                    if active_codec(f, io) is not None:
                        total += source_size(f, io=io, retry=retry,
                                             on_retry=on_retry)
                    elif os.path.exists(f):
                        total += os.path.getsize(f)
                else:
                    total += source_size(f, io=io, retry=retry,
                                         on_retry=on_retry)
            except Exception:
                continue
    return total


def _plan_var_len_shards(reader, files, params,
                         retry: Optional[RetryPolicy] = None,
                         on_retry=None, io=None,
                         split_mbs=None) -> List["WorkShard"]:
    """Byte-range shard plan for a variable-length read (the sparse-index
    chunk planner, engine/chunks.py). Shared by the in-process threaded
    scan, the pipelined executor, and the multi-host (process) executor."""
    from .engine.chunks import plan_var_len_chunks

    return plan_var_len_chunks(reader, files, params, retry, on_retry,
                               io=io, split_mbs=split_mbs)


def read_parallelism(opts) -> int:
    """Local concurrency for the indexed shard scan (the analogue of the
    reference's executor count; not a reference option)."""
    return opts.get_int("parallelism", 0) or min(16, os.cpu_count() or 1)


def _scan_var_len(reader, files, params, backend: str, prefix: str,
                  parallelism: int, metrics=None,
                  retry: Optional[RetryPolicy] = None,
                  on_retry=None, io=None) -> List["FileResult"]:
    """The indexed parallel scan — the reference's flagship execution
    strategy (CobolScanners.buildScanForVarLenIndex, CobolScanners.scala:
    38-55 + IndexBuilder.buildIndex, IndexBuilder.scala:49-66): a sparse
    index per file turns the sequential record stream into byte-range
    shards; shards decode concurrently (each from its own bounded stream,
    Record_Id seeded from the index entry) and results reassemble in
    record order.

    Two routes, chosen by `reader.index.preframed_route` from each file's
    record density and nothing a caller sets: the index is planned whole
    and every shard then frames its own byte range; or, for a dense RDW
    file, the index pass is the file's one framing and a shard starts,
    with its slice of the pass's tables, as soon as its cut is found
    (`engine.chunks.preframed_var_len_chunks`). Same shards, same
    tables. The split is `reader.index.index_split`'s, a file at a time:
    a dense file smaller than the pool's work is cut to the pool, every
    other file at the read's split. Same tables again."""
    from .engine.chunks import preframed_var_len_chunks
    from .obs.context import activate as obs_activate
    from .obs.context import current as obs_current
    from .reader.index import index_split

    obs = obs_current()
    tracer = obs.tracer if obs is not None else None
    progress = obs.progress if obs is not None else None

    split_mbs = None
    if params.is_index_generation_needed:
        splits = [index_split(reader, path, params, parallelism, io)
                  for path in files]
        split_mbs = [s.mb if s.why == "pool" else None for s in splits]

    def planned(n_shards: int) -> None:
        if metrics is not None:
            metrics.shards = n_shards
            metrics.device_stats.note_plan(
                n_shards, sum(mb is not None for mb in split_mbs or ()))
        if progress is not None:
            progress.set_plan(chunks_total=n_shards)

    preframed = preframed_var_len_chunks(reader, files, params, retry,
                                         on_retry, io, split_mbs)
    if preframed is None:
        with stage(metrics, "plan_index"):
            shards = _plan_var_len_shards(reader, files, params, retry,
                                          on_retry, io, split_mbs)
        planned(len(shards))
    shard_times = None
    if tracer is not None or (metrics is not None
                              and metrics.field_costs_acc is not None):
        # tracing on: per-stage spans from inside the readers (read /
        # frame / decode) via a tracer-wired StageTimes, published on
        # the read metrics like the pipelined path's. Field-cost
        # attribution wants the same stage busy breakdown even
        # untraced — the explain report compares the per-field decode
        # sum against the decode-stage busy time
        from .profiling import StageTimes

        shard_times = StageTimes(tracer=tracer)
        if metrics is not None and metrics.stage_busy is None:
            metrics.stage_busy = shard_times
        if progress is not None and progress.stage_times is None:
            progress.stage_times = shard_times

    def scan(shard, framed) -> "FileResult":
        max_bytes = (0 if shard.offset_to < 0
                     else shard.offset_to - shard.offset_from)
        with open_stream(shard.file_path, start_offset=shard.offset_from,
                         maximum_bytes=max_bytes, retry=retry,
                         on_retry=on_retry, io=io) as stream:
            return reader.read_result_columnar(
                stream, file_id=shard.file_order, backend=backend,
                segment_id_prefix=prefix,
                start_record_id=shard.record_index,
                starting_file_offset=shard.offset_from,
                stage_times=shard_times, framed=framed)

    def run_shard(indexed, framed=None) -> "FileResult":
        seq, shard = indexed
        if metrics is not None:
            metrics.device_stats.note_shard(preframed=framed is not None)
        # re-activate the read's ObsContext: pool threads must attribute
        # cache events and spans to this read, not to nothing
        with obs_activate(obs):
            if progress is not None:
                progress.chunk_started()
            if tracer is not None:
                with tracer.span("shard", "shard",
                                 args={"seq": seq,
                                       "file": shard.file_path,
                                       "offset_from": shard.offset_from,
                                       "offset_to": shard.offset_to}):
                    result = scan(shard, framed)
            else:
                result = scan(shard, framed)
        if progress is not None:
            from .engine.chunks import shard_progress_bytes

            progress.chunk_done(bytes_done=shard_progress_bytes(shard),
                                records=result.n_rows)
        return result

    from concurrent.futures import ThreadPoolExecutor

    if preframed is not None:
        if parallelism <= 1:
            with stage(metrics, "plan_index"):
                handed = list(preframed)
            planned(len(handed))
            return [run_shard((seq, shard), framed)
                    for seq, (shard, framed) in enumerate(handed)]
        # a worker starts as a shard is handed over and an idle one is
        # taken first: `parallelism` bounds the pool, the shards in
        # flight size it
        ex = ThreadPoolExecutor(max_workers=parallelism)
        try:
            with stage(metrics, "plan_index"):
                futures = [ex.submit(run_shard, (seq, shard), framed)
                           for seq, (shard, framed) in enumerate(preframed)]
            planned(len(futures))
            with PoolWait():
                return [future.result() for future in futures]
        finally:
            # an error of the pass or of a shard: nothing new starts
            ex.shutdown(wait=True, cancel_futures=True)

    # <= 1: zone-map skipping can leave no shard at all, and a pool of
    # zero workers is a ValueError
    if len(shards) <= 1 or parallelism <= 1:
        return [run_shard(s) for s in enumerate(shards)]
    with ThreadPoolExecutor(max_workers=min(parallelism, len(shards))) as ex:
        with PoolWait():
            return list(ex.map(run_shard, enumerate(shards)))


def read_cobol(path=None,
               copybook: Optional[str] = None,
               copybook_contents=None,
               backend: str = "numpy",
               progress_callback=None,
               batch_callback=None,
               explain: bool = False,
               tracer=None,
               **options) -> "Union[CobolData, ScanReport]":
    """Read mainframe file(s) into decoded rows.

    `copybook` is a path (or list of paths) to copybook file(s);
    `copybook_contents` passes the text directly. Remaining keyword options
    use the reference's option names (README.md:1070-1155).

    `progress_callback`: optional callable receiving monotonic
    `obs.ScanProgress` snapshots while the scan runs (throttled by the
    `progress_interval_s` option; the final `done=True` snapshot always
    fires). The `trace_file` option writes a Chrome-trace/Perfetto JSON
    of the whole scan — see the README's Observability section.

    `batch_callback(chunk_index, table)`: optional streaming tap — each
    assembled per-chunk Arrow table is handed out as soon as it exists.
    On the pipelined paths (`pipeline_workers` != 0) batches arrive
    WHILE later chunks are still decoding (chunk completion order;
    re-order by `chunk_index` if record order matters — indexes come
    from the scan plan). A chunk that terminally fails under a partial
    shard policy delivers `(chunk_index, None)` — the gap is permanent;
    reorder buffers must flush past it instead of waiting (may arrive
    on a different thread than table deliveries). Other execution paths
    deliver the per-file/shard tables after the scan, in order. The
    concatenation of all delivered tables in index order equals
    `to_arrow()` minus the diagnostics schema metadata. A callback
    exception aborts the scan under fail_fast (ledgers the chunk under
    a partial shard policy) — the serving tier relies on that to cancel
    scans whose client went away.

    `explain=True` returns a `ScanReport` instead of the bare
    CobolData: the parsed field plan (offsets/widths/codecs), the
    execution plan, cache-plane status, and — because it forces the
    `field_costs` option on — the measured per-field cost table and
    roofline anchoring. The decoded data rides on `report.data`.

    `tracer`: an `obs.Tracer` to record scan spans into instead of
    creating one. The request-scoped surface for embedders (the serving
    tier passes its per-request tracer here so queue-wait and scan
    spans share one timeline and one trace_id); spans are collected
    in memory (`data.metrics.spans`) and only written to disk when
    `trace_file` is also set. The string options `trace_id` /
    `request_id` are the wire-friendly subset: they tag a read's OWN
    tracer with inbound identity.
    """
    if tracer is not None and not hasattr(tracer, "record_span"):
        raise ValueError("'tracer' must be an obs.Tracer (it receives "
                         "scan spans).")
    if progress_callback is not None and not callable(progress_callback):
        raise ValueError("'progress_callback' must be callable (it "
                         "receives ScanProgress snapshots).")
    if batch_callback is not None and not callable(batch_callback):
        raise ValueError("'batch_callback' must be callable (it receives "
                         "(chunk_index, pyarrow.Table) pairs).")
    batch_tap = _BatchTap(batch_callback) if batch_callback else None
    # exclusive-source validation before any option is consumed
    # ('copybook'/'copybook_contents' are named parameters and can never
    # reach **options; only 'copybooks' arrives as an option key —
    # reference CobolParametersValidator.checkSanity combination rules)
    has_multi = "copybooks" in options
    if copybook is not None and copybook_contents is not None:
        raise ValueError("Both 'copybook' and 'copybook_contents' options "
                         "cannot be specified at the same time")
    if has_multi and copybook_contents is not None:
        raise ValueError("Both 'copybooks' and 'copybook_contents' options "
                         "cannot be specified at the same time")
    if copybook is not None and has_multi:
        raise ValueError("Both 'copybook' and 'copybooks' options "
                         "cannot be specified at the same time")
    if has_multi:
        copybook = options.pop("copybooks").split(",")

    copybook_contents = load_copybook_contents(copybook,
                                               copybook_contents)
    if path is None:
        raise ValueError("'path' must be specified for read_cobol.")

    validate_backend(backend)
    params, opts = parse_options(options)
    if params.filter and backend == "host":
        raise ValueError(
            "The 'filter' option requires a columnar execution path; "
            "backend='host' walks records through the scalar oracle "
            "and does not support pushdown. Drop the filter or use "
            "the numpy/jax backend.")
    if explain and not params.field_costs:
        # explain wants the measured cost table; flip attribution on
        from dataclasses import replace as _dc_replace

        params = _dc_replace(params, field_costs=True)
    debug_ignore_file_size = opts.get_bool("debug_ignore_file_size")
    parallelism = read_parallelism(opts)
    # hosts > 1: fork one worker process per host and run the shard plan
    # there (parallel/hosts.py — the executor-process analogue); the
    # result is Arrow-backed
    hosts = opts.get_int("hosts", 0)
    files = list_input_files(path)
    if not files:
        raise FileNotFoundError(f"No input files found for path {path}")

    is_var_len = params.needs_var_len_reader

    # chunked pipeline executor (cobrix_tpu.engine): overlap storage read,
    # framing, decode, and Arrow assembly across a bounded thread pool.
    # Off by default (pipeline_workers=0 keeps the sequential path); the
    # host (oracle) backend and the multi-host process executor have their
    # own execution models
    pipe_workers = params.resolved_pipeline_workers()
    use_pipeline = pipe_workers > 0 and hosts <= 1 and backend != "host"
    if use_pipeline and is_var_len:
        from .engine.chunks import auto_split_mb

        split_mb = auto_split_mb(params)
        if split_mb is not None:
            # default the sparse-index split to the pipeline chunk size so
            # mid-size files actually produce multiple chunks (explicit
            # input_split options always win; see auto_split_mb for the
            # configurations where this is pinned row-identical)
            from dataclasses import replace as _dc_replace

            params = _dc_replace(params, input_split_size_mb=split_mb)

    metrics = ReadMetrics(files=len(files), backend=backend,
                          hosts=max(hosts, 1))
    io_cfg = _io_config(params)
    prescan_retries: List[int] = []
    metrics.bytes_read = _total_input_bytes(
        files, metrics.io_stats, io=io_cfg, retry=_retry_policy(params),
        on_retry=lambda: prescan_retries.append(1))
    # sizing-probe retries fold into the read ledger downstream (both
    # execution paths see them via the metrics object)
    metrics.prescan_io_retries = len(prescan_retries)
    if params.field_costs:
        from .obs.fieldcost import FieldCostAccumulator

        metrics.field_costs_acc = FieldCostAccumulator()

    # the read's observability context: per-read cache-counter scope
    # always; tracer/progress only when asked for. Activated on this
    # thread and re-activated by every pool the scan fans out to.
    from .obs.context import activate as obs_activate

    obs_ctx = _build_obs_context(params, metrics, progress_callback,
                                 tracer=tracer)
    try:
        with obs_activate(obs_ctx):
            if hosts > 1:
                if backend != "numpy":
                    raise ValueError(
                        f"hosts={hosts} runs worker processes on the "
                        f"native/numpy kernels; backend={backend!r} is "
                        f"not supported there (drop `hosts` for the "
                        f"{backend!r} backend)")
                data = _read_cobol_multihost(
                    files, copybook_contents, params, hosts,
                    debug_ignore_file_size, metrics)
            else:
                data = _read_cobol_single_host(
                    files, copybook_contents, params, backend,
                    parallelism, pipe_workers, use_pipeline, is_var_len,
                    debug_ignore_file_size, metrics, io_cfg,
                    batch_tap=batch_tap)
    except BaseException:
        # a failed scan still flushes its telemetry: the final done=True
        # progress snapshot fires (a progress bar must not freeze) and
        # the PARTIAL trace — exactly what diagnoses the failure — is
        # written; flush errors never mask the scan's own exception
        _abort_obs(obs_ctx, params)
        raise
    _finish_obs(obs_ctx, params, data)
    if batch_tap is not None and batch_tap.count == 0:
        # execution paths without an incremental tap (sequential,
        # threaded shard scan, host backend, multihost) still honor the
        # streaming contract: the per-result tables go out now, in
        # record order — the callback sees the same batches, just with
        # one-shot latency
        batch_tap.emit_data(data)
    if params.collect_stats:
        # the profiling pass runs AFTER the read (an explicit,
        # separately-billed cost — never hidden inside scan time);
        # warm profiles load instead of rebuilding. Gated on the option
        # so a stats-off read never imports the stats package
        from .stats.collect import build_and_store_profiles

        profiles = build_and_store_profiles(files, copybook_contents,
                                            params, backend,
                                            io=_io_config(params))
        data.stats_profiles = {url: profile.summary()
                               for url, profile in profiles.items()}
    from .plan.cache import parse_fingerprint

    data.plan_fingerprint = parse_fingerprint(copybook_contents, params)
    if explain:
        from .explain import build_scan_report

        return build_scan_report(params, files=files, data=data,
                                 backend=backend,
                                 copybook_contents=copybook_contents)
    return data


class _BatchTap:
    """Adapter between the engine's `on_batch(index, table)` hook and a
    user `batch_callback`: counts deliveries so read_cobol knows whether
    the incremental path already streamed, and provides the whole-result
    fallback for paths without a mid-scan tap."""

    __slots__ = ("callback", "count")

    def __init__(self, callback):
        self.callback = callback
        self.count = 0

    def emit(self, index: int, table) -> None:
        if table is not None:
            # failed-chunk signals (table=None) forward but don't count
            # as deliveries — an all-chunks-failed pipelined scan must
            # still take the whole-result fallback below
            self.count += 1
        self.callback(index, table)

    def emit_data(self, data: "CobolData") -> None:
        """Per-result tables of a finished read, in record order."""
        if data._arrow_tables is not None:
            for i, table in enumerate(data._arrow_tables):
                self.emit(i, table)
        elif data._results:
            for i, result in enumerate(data._results):
                self.emit(i, result.to_arrow(data.output_schema))


def _build_obs_context(params: ReaderParameters, metrics: ReadMetrics,
                       progress_callback, tracer=None):
    """The read's ObsContext: tracer when `trace_file` is set (or one
    was injected by an embedder like the serving tier), progress
    tracker when a callback was passed, the default metrics registry's
    scan metric set, and the metrics object's per-read cache scope."""
    from .obs.context import ObsContext
    from .obs.metrics import scan_metrics

    if tracer is None and params.trace_file:
        from .obs.trace import Tracer

        tracer = Tracer(trace_id=params.trace_id or None)
    if tracer is not None:
        if params.request_id:
            tracer.meta.setdefault("request_id", params.request_id)
        metrics.tracer = tracer
    progress = None
    if progress_callback is not None:
        from .obs.progress import ProgressTracker

        progress = ProgressTracker(
            progress_callback, bytes_total=metrics.bytes_read,
            min_interval_s=params.progress_interval_s)
    return ObsContext(tracer=tracer, metrics=scan_metrics(),
                      progress=progress,
                      cache_scope=metrics.cache_scope,
                      io_stats=metrics.io_stats,
                      field_costs=metrics.field_costs_acc,
                      pass_counts=metrics.pass_counts,
                      device_stats=metrics.device_stats)


def _finish_obs(obs_ctx, params: ReaderParameters, data) -> None:
    """End-of-read observability: the final done=True progress snapshot
    and the Chrome-trace artifact (metrics.finalize already closed the
    scan-root span and captured the span list)."""
    if obs_ctx.progress is not None:
        obs_ctx.progress.finish(records_total=len(data))
    if obs_ctx.tracer is not None and params.trace_file:
        if data.metrics is not None:
            # lazy post-read assembly refreshes the artifact with its
            # accrued field costs (ReadMetrics.refresh_trace_field_costs)
            data.metrics._trace_file = params.trace_file
        try:
            obs_ctx.tracer.write_chrome_trace(params.trace_file)
        except OSError:
            # the destination was validated up front, but it can still
            # vanish (or the disk fill) during a long scan — a lost
            # trace must not discard a fully successful read
            import logging

            logging.getLogger(__name__).warning(
                "failed to write trace_file %r; the read succeeded",
                params.trace_file, exc_info=True)


def _abort_obs(obs_ctx, params: ReaderParameters) -> None:
    """Best-effort telemetry flush when the scan raised: every step is
    individually guarded so nothing here can shadow the real error."""
    if obs_ctx.progress is not None:
        try:
            obs_ctx.progress.finish()
        except Exception:
            pass
    if obs_ctx.tracer is not None and params.trace_file:
        try:
            obs_ctx.tracer.write_chrome_trace(params.trace_file)
        except Exception:
            pass


def _read_cobol_single_host(files, copybook_contents,
                            params: ReaderParameters, backend: str,
                            parallelism: int,
                            pipe_workers: int, use_pipeline: bool,
                            is_var_len: bool,
                            debug_ignore_file_size: bool,
                            metrics: ReadMetrics,
                            io=None, batch_tap=None) -> "CobolData":
    """The in-process execution paths (sequential, threaded shard scan,
    chunked pipeline) — read_cobol minus option parsing and multihost."""
    on_batch = batch_tap.emit if batch_tap is not None else None
    results: List[FileResult] = []
    copybook_obj: Optional[Copybook] = None
    # attribution on: give the SEQUENTIAL paths a StageTimes too, so the
    # per-field decode costs have a decode-stage busy total to anchor
    # against (pipelined paths attach the executor's own; _scan_var_len
    # builds its shard-pool one)
    seq_stage_times = None
    if (metrics.field_costs_acc is not None and not use_pipeline
            and not is_var_len and backend != "host"):
        from .profiling import StageTimes

        seq_stage_times = StageTimes()
        metrics.stage_busy = seq_stage_times

    with stage(metrics, "parse_copybook"):
        if is_var_len:
            reader = VarLenReader(copybook_contents, params)
        else:
            reader = FixedLenReader(copybook_contents, params)
        copybook_obj = reader.copybook

    if params.use_stats:
        # arm zone-map chunk skipping from warm profiles (stats/skip.py);
        # gated on the option so a stats-off read never imports the
        # stats package at all
        from .stats.skip import maybe_attach_skipper

        maybe_attach_skipper(reader, files, params, io=io)

    # the output schema is a pure function of copybook + options; built
    # before the scan so the pipelined path can assemble per-chunk Arrow
    # tables against it while later chunks are still decoding
    from .reader.schema import output_schema_for

    schema = output_schema_for(copybook_obj, params, is_var_len)

    retry = _retry_policy(params)
    retries_seen: List[int] = []  # list.append is GIL-atomic across shards
    # chunks the supervised pipeline gave up on (partial policy only;
    # fail_fast raises from inside the executor instead)
    shard_failures: List[ShardFailureInfo] = []

    def on_retry():
        retries_seen.append(1)

    with stage(metrics, "scan"):
        if is_var_len:
            prefix = (params.multisegment.segment_id_prefix
                      if params.multisegment
                      and params.multisegment.segment_id_prefix
                      else default_segment_id_prefix())
            if backend == "host":
                for file_order, file_path in enumerate(files):
                    ledger = (params.new_diagnostics()
                              if params.is_permissive else None)
                    reasons: dict = {}
                    with open_stream(file_path, retry=retry,
                                     on_retry=on_retry, io=io) as stream:
                        result = rows_file_result(list(
                            reader.iter_rows(
                                stream, file_id=file_order,
                                segment_id_prefix=prefix,
                                start_record_id=file_order
                                * DEFAULT_FILE_RECORD_ID_INCREMENT,
                                ledger=ledger,
                                corrupt_reasons_out=reasons)))
                    result.diagnostics = ledger
                    result.corrupt_record_field = \
                        params.corrupt_record_column
                    result.corrupt_row_reasons = reasons or None
                    results.append(result)
            elif use_pipeline:
                from .engine.pipeline import pipelined_var_len_scan

                with stage(metrics, "plan_index"):
                    shards = _plan_var_len_shards(reader, files, params,
                                                  retry, on_retry, io)
                metrics.shards = len(shards)
                results, failed = pipelined_var_len_scan(
                    reader, shards, params, backend, prefix, schema,
                    pipe_workers, metrics=metrics, retry=retry,
                    on_retry=on_retry, io=io, on_batch=on_batch)
                shard_failures.extend(failed)
                results = [r for r in results if r is not None]
            else:
                results = _scan_var_len(reader, files, params, backend,
                                        prefix, parallelism,
                                        metrics=metrics, retry=retry,
                                        on_retry=on_retry, io=io)
        elif use_pipeline:
            from .engine.pipeline import pipelined_fixed_scan

            results, failed = pipelined_fixed_scan(
                reader, files, params, backend, schema, pipe_workers,
                ignore_file_size=debug_ignore_file_size, metrics=metrics,
                retry=retry, on_retry=on_retry, io=io, on_batch=on_batch)
            shard_failures.extend(failed)
            results = [r for r in results if r is not None]
        else:
            for file_order, file_path in enumerate(files):
                base = file_order * DEFAULT_FILE_RECORD_ID_INCREMENT
                if backend == "host":
                    ledger = (params.new_diagnostics()
                              if params.is_permissive else None)
                    reasons = {}
                    data = _read_file_bytes(file_path, retry, on_retry,
                                            io)
                    result = rows_file_result(list(
                        reader.iter_rows_host(
                            data, file_id=file_order,
                            first_record_id=base,
                            input_file_name=file_path,
                            ignore_file_size=debug_ignore_file_size,
                            ledger=ledger,
                            corrupt_reasons_out=reasons)))
                    result.diagnostics = ledger
                    result.corrupt_record_field = \
                        params.corrupt_record_column
                    result.corrupt_row_reasons = reasons or None
                    results.append(result)
                else:
                    results.extend(_read_fixed_len_chunked(
                        reader, file_path, params, backend, file_order,
                        base, debug_ignore_file_size, retry, on_retry,
                        io, stage_times=seq_stage_times))

    data = CobolData.from_results(results, schema, parallelism=parallelism)
    data.diagnostics = _aggregate_diagnostics(
        params, results,
        len(retries_seen) + getattr(metrics, "prescan_io_retries", 0),
        shard_failures)
    pushdown = getattr(reader, "pushdown", None)
    if pushdown is not None:
        # pruning counters into the read's metrics BEFORE finalize, so
        # the registry publication (Prometheus) sees them too
        metrics.pushdown = pushdown.stats.as_dict()
    metrics.finalize(data, len(results))
    return data


def _aggregate_diagnostics(params: ReaderParameters,
                           results: List["FileResult"],
                           io_retries: int,
                           shard_failures: Sequence[ShardFailureInfo] = (),
                           ) -> Optional[ReadDiagnostics]:
    """Merge per-file/shard ledgers into the read-level ledger. None under
    fail_fast with no IO incidents and no lost shards (the read either
    succeeded cleanly or raised). Deterministic: entries sort by
    (file, offset) with stable cap truncation (ReadDiagnostics.merged),
    so sequential, threaded, and pipelined scans over the same bytes
    produce byte-identical ledgers."""
    if (not params.is_permissive and io_retries == 0
            and not shard_failures):
        return None
    merged = ReadDiagnostics.merged(
        (getattr(r, "diagnostics", None) for r in results),
        max_entries=params.max_corrupt_ledger_entries)
    merged.io_retries += io_retries
    for failure in shard_failures:
        merged.record_shard_failure(failure)
    return merged


# fixed-length files stream through bounded chunk reads instead of one
# whole-file read(): peak memory stays ~one chunk + its decoded columns
# (FileStreamer.scala:37-130's buffered role on the fixed path)
FIXED_READ_CHUNK_BYTES = 64 * 1024 * 1024


def _read_file_bytes(path: str, retry: Optional[RetryPolicy] = None,
                     on_retry=None, io=None):
    """Whole-file bytes-like payload: a read-only mmap memoryview for
    local files (FSStream.next_view), plain bytes otherwise — consumers
    must stick to buffer-protocol operations (len/slice/np.frombuffer)."""
    from .reader.stream import open_stream

    with open_stream(path, retry=retry, on_retry=on_retry,
                     io=io) as stream:
        return stream.next_view(stream.size())


def _read_fixed_len_chunked(reader, file_path: str, params, backend: str,
                            file_order: int, base_record_id: int,
                            ignore_file_size: bool,
                            retry: Optional[RetryPolicy] = None,
                            on_retry=None, io=None,
                            stage_times=None) -> List["FileResult"]:
    from .obs.context import current as obs_current
    from .reader.stream import open_stream, source_size

    from .engine.chunks import fixed_file_chunkable

    obs = obs_current()
    progress = obs.progress if obs is not None else None

    def track(result, nbytes: int) -> "FileResult":
        if progress is not None:
            progress.chunk_started()
            progress.chunk_done(bytes_done=nbytes,
                                records=result.n_rows)
        return result

    rs = reader.record_size
    size = source_size(file_path, retry=retry, on_retry=on_retry, io=io)
    skipper = getattr(reader, "chunk_skipper", None)
    # fixed chunking is output-invariant (record-aligned strides,
    # absolute Record_Id bases), so with zone-map skipping armed the
    # scan stride shrinks to the profile grid — skip granularity then
    # matches what the profile can actually prove
    stride_bytes = FIXED_READ_CHUNK_BYTES
    if skipper is not None:
        from .reader.parameters import MEGABYTE

        stride_bytes = min(stride_bytes, max(
            rs, int(params.stats_chunk_mb * MEGABYTE) // rs * rs))
    from .io.compress import compressed_chunkable

    # the SAME predicates drive the pipelined chunk planner — the
    # pipelined-vs-sequential parity guarantee needs one split rule
    # (compressed inputs without a decompressed cache plane stay whole:
    # chunk offsets would re-inflate the prefix per chunk)
    if not fixed_file_chunkable(size, rs, params, stride_bytes,
                                ignore_file_size) \
            or not compressed_chunkable(file_path, io):
        if skipper is not None and skipper.should_skip(file_path, 0, -1):
            return []
        with timed_stage(stage_times, "read"):
            whole = _read_file_bytes(file_path, retry, on_retry, io)
        return [track(reader.read_result(
            whole, backend=backend,
            file_id=file_order, first_record_id=base_record_id,
            input_file_name=file_path, ignore_file_size=ignore_file_size,
            stage_times=stage_times),
            size)]
    chunk_bytes = max(rs, (stride_bytes // rs) * rs)
    results: List[FileResult] = []
    if skipper is not None:
        # zone-map skipping armed: bounded per-chunk streams, so a
        # skipped range's bytes are never read at all (the single-stream
        # loop below would have to read past them)
        done = 0
        while done < size:
            nbytes = min(chunk_bytes, size - done)
            if skipper.should_skip(file_path, done, done + nbytes):
                done += nbytes
                continue
            with open_stream(file_path, start_offset=done,
                             maximum_bytes=nbytes, retry=retry,
                             on_retry=on_retry, io=io) as stream:
                with timed_stage(stage_times, "read"):
                    data = stream.next_view(nbytes)
                if not data:
                    break
                if len(data) % rs and done + len(data) < size:
                    raise IOError(
                        f"Short read from {file_path} at {done}")
                results.append(track(reader.read_result(
                    data, backend=backend, file_id=file_order,
                    first_record_id=base_record_id + done // rs,
                    input_file_name=file_path,
                    ignore_file_size=ignore_file_size,
                    stage_times=stage_times), len(data)))
            done += len(data)
        return results
    return [track(reader.read_result(
        data, backend=backend, file_id=file_order,
        first_record_id=base_record_id + done // rs,
        input_file_name=file_path, ignore_file_size=ignore_file_size,
        stage_times=stage_times), len(data))
        for done, data in _fixed_len_chunks(
            file_path, size, rs, chunk_bytes, retry, on_retry, io,
            stage_times)]


def _fixed_len_chunks(file_path: str, size: int, rs: int, chunk_bytes: int,
                      retry=None, on_retry=None, io=None, stage_times=None):
    """(offset, bytes) of each read chunk of a fixed-length file, one
    stream, `chunk_bytes` of whole records at a time: what a read decodes
    and a device aggregate reduces, chunk after chunk."""
    from .reader.stream import open_stream

    done = 0
    with open_stream(file_path, retry=retry, on_retry=on_retry,
                     io=io) as stream:
        while done < size:
            with timed_stage(stage_times, "read"):
                data = stream.next_view(min(chunk_bytes, size - done))
            if not data:
                break
            if len(data) % rs and done + len(data) < size:
                raise IOError(f"Short read from {file_path} at {done}")
            yield done, data
            done += len(data)


def aggregate_on_device(files, copybook_contents, options: dict,
                        backend: str, specs, filter_expr, keys, schema):
    """``dataset(...).aggregate()`` answered on the chip: (result, the
    call's ReadMetrics), or parallel.query.NotOnDevice before a byte is
    read where the chip cannot answer exactly (the caller then decodes).

    The route is taken from what can be seen: plain fixed-length records
    (no variable-length reader, segments, offsets, permissive policy,
    statistics, pipeline or worker hosts), and a query `bind_query`
    accepts. Each file is read in the fixed-length reader's own chunks
    (`_fixed_len_chunks`); a chunk's records are packed to the query's
    bytes, launched, and its groups' partials merged on the host
    (parallel/query.QueryRun), under the stages and DeviceStats counts of
    a read plus `query.bind`, `query.merge`, `query.fallback` and the
    `query_*` counts."""
    from .io.compress import compressed_chunkable
    from .obs.context import activate as obs_activate
    from .parallel.query import NotOnDevice, aggregator_for, bind_query
    from .profiling import Stage
    from .reader.stream import source_size

    params, opts = parse_options(dict(options))
    if (params.needs_var_len_reader or params.multisegment is not None
            or params.is_permissive or params.use_stats
            or params.select or params.filter
            or params.start_offset or params.end_offset
            or params.file_start_offset or params.file_end_offset
            or opts.get_int("hosts", 0) > 1
            or opts.get_bool("debug_ignore_file_size")
            or params.resolved_pipeline_workers() > 0):
        raise NotOnDevice("not a plain fixed-length read")
    metrics = ReadMetrics(files=len(files), backend=backend)
    io_cfg = _io_config(params)
    if not all(compressed_chunkable(path, io_cfg) for path in files):
        raise NotOnDevice("a compressed input without a block cache")
    retry = _retry_policy(params)
    stats = metrics.device_stats
    obs_ctx = _build_obs_context(params, metrics, None)
    with obs_activate(obs_ctx):
        with Stage("query.bind", stats):
            reader = FixedLenReader(copybook_contents, params)
            if reader.record_size != reader.copybook.record_size:
                raise NotOnDevice("records wider than the copybook")
            query = bind_query(reader.copybook, specs, filter_expr, keys,
                               schema)
            aggregator = aggregator_for(query, backend)
        rs = reader.record_size
        chunk_bytes = max(rs, (FIXED_READ_CHUNK_BYTES // rs) * rs)
        run = aggregator.start(stats)
        with stage(metrics, "scan"):
            for file_path in files:
                size = source_size(file_path, retry=retry, io=io_cfg)
                reader.check_binary_data_validity(size)
                metrics.bytes_read += size
                for _done, data in _fixed_len_chunks(
                        file_path, size, rs, chunk_bytes, retry, None,
                        io_cfg):
                    with Stage("frame"):
                        matrix = reader.to_record_matrix(data)
                    run.add(matrix)
                # the chunk in flight views the stream's bytes
                run.drain()
            result = run.finish()
    metrics.records = stats.query_rows_scanned
    return result, metrics


def _read_cobol_multihost(files, copybook_contents, params, hosts: int,
                          debug_ignore_file_size: bool,
                          metrics: Optional[ReadMetrics] = None
                          ) -> "CobolData":
    """The multi-host execution path: plan + fork + reassemble
    (parallel/hosts.multihost_scan). Output is Arrow-backed; row order and
    Record_Ids are byte-identical to the single-process read."""
    from .parallel.hosts import multihost_scan, plan_fixed_len_shards

    is_var_len = params.needs_var_len_reader
    with stage(metrics, "parse_copybook"):
        if is_var_len:
            reader = VarLenReader(copybook_contents, params)
            prefix = (params.multisegment.segment_id_prefix
                      if params.multisegment
                      and params.multisegment.segment_id_prefix
                      else default_segment_id_prefix())
        else:
            reader = FixedLenReader(copybook_contents, params)
            prefix = ""
    if params.use_stats and is_var_len:
        # multihost VRL shards come from the same sparse-index planner
        # as single-host scans, so warm profiles skip there too (fixed
        # multihost shards are host-balanced ranges, left unfiltered)
        from .stats.skip import maybe_attach_skipper

        maybe_attach_skipper(reader, files, params,
                             io=_io_config(params))
    with stage(metrics, "plan_index"):
        if is_var_len:
            shards = _plan_var_len_shards(reader, files, params,
                                          io=_io_config(params))
        else:
            shards = plan_fixed_len_shards(reader, files, params, hosts)
    from .reader.schema import output_schema_for

    schema = output_schema_for(reader.copybook, params, is_var_len)
    with stage(metrics, "scan"):
        tables, shard_failures, supervision = multihost_scan(
            reader, shards, is_var_len, schema, hosts, prefix,
            ignore_file_size=debug_ignore_file_size)
    if metrics is not None:
        metrics.supervision = supervision
        pushdown = getattr(reader, "pushdown", None)
        if pushdown is not None:
            # planning runs in-parent, so chunk-skip counters are real;
            # per-record pruning counters stay in the forked workers
            metrics.pushdown = pushdown.stats.as_dict()
    # merge the per-shard ledgers the workers shipped back as IPC schema
    # metadata (stripped here so shard keys don't leak into — or break
    # concatenation of — the unified table); shard order is canonical, so
    # entry order matches a single-process read. Workers ship a ledger
    # under fail_fast too when IO retries fired, matching
    # _aggregate_diagnostics.
    shard_ledgers: List[ReadDiagnostics] = []
    found = False
    cleaned = []
    for table in tables:
        metadata = dict(table.schema.metadata or {})
        raw = metadata.pop(b"cobrix_tpu.shard_diagnostics", None)
        if raw:
            found = True
            shard_ledgers.append(ReadDiagnostics.from_json(raw))
            table = table.replace_schema_metadata(metadata or None)
        cleaned.append(table)
    diagnostics = ReadDiagnostics.merged(
        shard_ledgers, max_entries=params.max_corrupt_ledger_entries)
    # shards the supervisor gave up on (partial policy): the rows are
    # missing from the output — say so on the read's ledger
    for failure in shard_failures:
        diagnostics.record_shard_failure(failure)
    # sizing-probe retries happened in-parent, before the fork: fold
    # them in here (workers ledger their own retries per shard)
    prescan = (getattr(metrics, "prescan_io_retries", 0)
               if metrics is not None else 0)
    diagnostics.io_retries += prescan
    data = CobolData.from_arrow_tables(cleaned, schema)
    data.diagnostics = (diagnostics
                        if params.is_permissive or found or shard_failures
                        or prescan else None)
    if metrics is not None:
        metrics.finalize(data, len(shards))
    return data
