"""Chunk planning: split a scan into independently-executable byte ranges.

Two planners, one chunk contract:

* fixed-length files split on record-size-aligned byte strides — chunk
  boundaries are pure arithmetic, no scan needed;
* variable-length streams split on sparse-index entries
  (reader/index.py) — the index pass turns the inherently-sequential
  record stream into restartable byte ranges, exactly the mechanism the
  reference uses to parallelize VRL files across Spark partitions
  (IndexBuilder.scala:49-66).

Chunk plans are EXECUTION plans only: a pipelined read with the same
split options decodes the same records with the same Record_Ids as the
sequential path, so turning the pipeline on can never change results.

When a read armed a chunk skipper (``use_stats=true`` + a filter + a
warm profile, stats/skip.py), both planners drop ranges the profile
PROVES cannot frame a matching record — before any byte is read.
Offsets and Record_Id bases of surviving chunks are absolute, so
skipping never renumbers or reorders what remains.
"""
from __future__ import annotations

import os
from dataclasses import dataclass
from typing import List, Optional

from ..reader.index import (
    file_index_entries,
    preframed_entries,
    preframed_route,
)
from ..reader.parameters import DEFAULT_FILE_RECORD_ID_INCREMENT
from ..reader.stream import RetryPolicy, path_scheme, source_size


def shard_progress_bytes(shard) -> int:
    """Best-effort byte size of a var-len shard for progress/telemetry
    accounting: closed ranges are exact; an open tail range
    (offset_to < 0 = 'to end of file') falls back to the local file
    size, so bytes_done can actually reach bytes_total."""
    if shard.offset_to >= 0:
        return max(0, shard.offset_to - shard.offset_from)
    if path_scheme(shard.file_path) in (None, "file"):
        try:
            return max(0, os.path.getsize(shard.file_path)
                       - shard.offset_from)
        except OSError:
            return 0
    return 0


@dataclass(frozen=True)
class FixedChunk:
    """One fixed-length unit of pipelined work: a record-aligned byte
    range of one input file (the whole file when the file is too small or
    not cleanly divisible)."""

    file_path: str
    file_order: int
    offset: int            # byte offset of the chunk in the file
    nbytes: int            # bytes to read (0 = to end of file)
    first_record_id: int   # Record_Id of the chunk's first record
    whole_file: bool       # single-chunk file (offset trims / odd tails)


def fixed_file_chunkable(size: int, record_size: int, params,
                         chunk_bytes: int, ignore_file_size: bool) -> bool:
    """THE fixed-length split predicate — shared by the sequential
    chunked read (api._read_fixed_len_chunked) and the pipelined planner,
    because the parity guarantee rests on both making the identical
    split/whole-file decision: no file-level header/footer trims and a
    record-divisible payload (or debug_ignore_file_size)."""
    payload = size - params.file_start_offset - params.file_end_offset
    return (size > chunk_bytes
            and not params.file_start_offset
            and not params.file_end_offset
            and (payload % record_size == 0 or ignore_file_size))


def plan_fixed_chunks(reader, files, params, chunk_bytes: int,
                      ignore_file_size: bool,
                      retry: Optional[RetryPolicy] = None,
                      on_retry=None, io=None) -> List[FixedChunk]:
    """Byte-stride chunk plan over fixed-length input files.

    A file splits only when the same conditions hold that make the
    sequential chunked read safe (api._read_fixed_len_chunked): no
    file-level header/footer trims and a record-size-divisible payload
    (or debug_ignore_file_size). Anything else — including a truncated
    tail a permissive policy will ledger — stays a single whole-file
    chunk, so tail handling and ledger offsets match the sequential read
    byte for byte.
    """
    from ..reader.parameters import MEGABYTE

    rs = reader.record_size
    skipper = getattr(reader, "chunk_skipper", None)
    if skipper is not None:
        # chunking is output-invariant: with skipping armed, plan on
        # the profile grid so skip granularity matches the proofs
        chunk_bytes = min(chunk_bytes, max(
            rs, int(params.stats_chunk_mb * MEGABYTE)))
    from ..io.compress import compressed_chunkable

    chunk_bytes = max(rs, (chunk_bytes // rs) * rs)  # record-aligned
    chunks: List[FixedChunk] = []
    for file_order, file_path in enumerate(files):
        base = file_order * DEFAULT_FILE_RECORD_ID_INCREMENT
        size = source_size(file_path, retry=retry, on_retry=on_retry,
                           io=io)
        if not fixed_file_chunkable(size, rs, params, chunk_bytes,
                                    ignore_file_size) \
                or not compressed_chunkable(file_path, io):
            if skipper is not None \
                    and skipper.should_skip(file_path, 0, -1):
                continue
            chunks.append(FixedChunk(file_path, file_order, 0, 0, base,
                                     whole_file=True))
            continue
        done = 0
        while done < size:
            n = min(chunk_bytes, size - done)
            if skipper is None \
                    or not skipper.should_skip(file_path, done, done + n):
                chunks.append(FixedChunk(file_path, file_order, done, n,
                                         base + done // rs,
                                         whole_file=False))
            done += n
    return chunks


def plan_var_len_chunks(reader, files, params,
                        retry: Optional[RetryPolicy] = None,
                        on_retry=None, io=None,
                        split_mbs=None) -> List["WorkShard"]:
    """Byte-range shard plan for a variable-length read: the sparse index
    per file turns the sequential record stream into shards; files
    without a useful index become one whole-file shard. Shared by the
    in-process threaded scan, the pipelined executor, and the multi-host
    (process) executor. `split_mbs`: a file's split where the threaded
    scan's `index_split` set one, else None."""
    shards: List["WorkShard"] = []
    for file_order, file_path in enumerate(files):
        shards.extend(_file_shards(reader, file_path, file_order, params,
                                   retry, on_retry, io,
                                   split_mbs[file_order] if split_mbs
                                   else None))
    return shards


def _file_shards(reader, file_path: str, file_order: int, params,
                 retry, on_retry, io, split_mb=None) -> List["WorkShard"]:
    """One file's shards of `plan_var_len_chunks`."""
    from ..parallel.planner import WorkShard

    skipper = getattr(reader, "chunk_skipper", None)
    base = file_order * DEFAULT_FILE_RECORD_ID_INCREMENT
    entries = None
    if params.is_index_generation_needed:
        entries = file_index_entries(reader, file_path, file_order,
                                     params, retry, on_retry, io=io,
                                     split_mb=split_mb)
    if entries is not None and len(entries) > 1:
        # an open-ended last entry (-1) flows into the shard unchanged:
        # streams bound it to the file end themselves, so no extra
        # size round trip is needed for registry-backed storage
        return [WorkShard(file_path, file_order, e.offset_from, e.offset_to,
                          base + e.record_index)
                for e in entries
                if skipper is None or not skipper.should_skip(
                    file_path, e.offset_from, e.offset_to)]
    if skipper is None or not skipper.should_skip(file_path, 0, -1):
        return [WorkShard(file_path, file_order, 0, -1, base)]
    return []


def preframed_var_len_chunks(reader, files, params,
                             retry: Optional[RetryPolicy] = None,
                             on_retry=None, io=None, split_mbs=None):
    """`plan_var_len_chunks` for the in-process threaded scan where some
    file is dense enough for its index pass to be its one framing
    (`reader.index.preframed_route`: the rule, read off each file): an
    iterator of (WorkShard, FramedRecords or None), the same shards in
    the same order, each of such a file with its slice of the pass's
    tables and as soon as its cut is found, so it can be scanned while
    the pass walks on. None where no file is: the caller plans as ever.
    The pipelined engine and the multihost executor, whose shards cross
    threads of stages and processes, take `plan_var_len_chunks`' list.
    `split_mbs` as there."""
    from ..parallel.planner import WorkShard

    if not params.is_index_generation_needed:
        return None
    split_mbs = split_mbs or [None] * len(files)
    dense = [preframed_route(reader, path, params, io, split_mb)
             for path, split_mb in zip(files, split_mbs)]
    if not any(dense):
        return None

    def shards():
        for file_order, file_path in enumerate(files):
            if not dense[file_order]:
                for shard in _file_shards(reader, file_path, file_order,
                                          params, retry, on_retry, io,
                                          split_mbs[file_order]):
                    yield shard, None
                continue
            base = file_order * DEFAULT_FILE_RECORD_ID_INCREMENT
            for e, framed in preframed_entries(reader, file_path,
                                               file_order,
                                               split_mbs[file_order]):
                yield WorkShard(file_path, file_order, e.offset_from,
                                e.offset_to, base + e.record_index), framed

    return shards()


def auto_split_mb(params) -> Optional[int]:
    """The sparse-index split size (MB) a pipelined variable-length read
    should default to, or None to leave the plan untouched.

    Only configurations where split granularity provably cannot change
    results get the default: plain RDW streams (fast framing, which the
    indexed-scan invariants pin row-identical) with no file header/footer
    regions (whose counted-invalid-record quirk shifts Record_Ids between
    indexed and unindexed reads — reference IndexGenerator.scala:117-120).
    Explicit input_split options always win.
    """
    if (params.input_split_records is not None
            or params.input_split_size_mb is not None):
        return None
    if not params.is_index_generation_needed:
        return None
    if params.file_start_offset or params.file_end_offset:
        return None
    if not params.supports_fast_framing:
        return None
    # fractional chunk sizes pass through (file_index_entries multiplies
    # by MEGABYTE); below 1 MB the split-option validation floor applies,
    # so tiny-chunk runs use explicit input_split options instead
    mb = float(params.pipeline_chunk_mb)
    return mb if mb >= 1 else None
