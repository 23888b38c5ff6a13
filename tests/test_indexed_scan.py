"""Indexed parallel scan: sparse index -> byte-range shards -> concurrent
decode, row-identical to the sequential read.

Ports the reference's index regression pins (Test12MultiRootSparseIndex —
multi-root splits; Test02SparseIndexGenerator semantics) and proves the
integration VERDICT round 1 flagged: enable_indexes/input_split_records/
input_split_size_mb drive a real sharded execution path in read_cobol.
"""
import os
import tempfile

import numpy as np
import pytest

from cobrix_tpu import read_cobol
from cobrix_tpu.reader.header_parsers import FixedLengthHeaderParser
from cobrix_tpu.reader.index import sparse_index_generator
from cobrix_tpu.reader.parameters import (
    MultisegmentParameters,
    ReaderParameters,
)
from cobrix_tpu.reader.stream import MemoryStream
from cobrix_tpu.reader.var_len_reader import VarLenReader
from cobrix_tpu.copybook.copybook import parse_copybook
from cobrix_tpu.testing.generators import ebcdic_encode


def _rdw_le(length: int) -> bytes:
    """Little-endian RDW (the default): length in bytes [3..2]."""
    return bytes([0, 0]) + length.to_bytes(2, "little")


MULTIROOT_COPYBOOK = """
       01  R.
                03 S     PIC X(1).
                03 V     PIC X(2).
"""


class TestSparseIndexMultiRoot:
    """Port of Test12MultiRootSparseIndex.scala: fixed-length records,
    2 root segment ids ('0' and '1'), splits land only on root records."""

    # segment ids per record: 0 2 1 3 4 1 3 1 3 1 3 4  (reference data)
    SEGS = "021341313134"

    def _data(self, drop: int = 0) -> bytes:
        recs = b"".join(
            ebcdic_encode(f"{s}{s}{v}"[:3])
            for s, v in zip(self.SEGS, "5678901234 56"))
        data = recs[:len(self.SEGS) * 3]
        return data[: len(data) - drop] if drop else data

    def _index(self, data: bytes):
        cb = parse_copybook(MULTIROOT_COPYBOOK)
        seg_field = cb.get_field_by_name("S")
        return sparse_index_generator(
            0, MemoryStream(data),
            record_header_parser=FixedLengthHeaderParser(3, 0, 0),
            records_per_index_entry=4,
            copybook=cb,
            segment_field=seg_field,
            is_hierarchical=True,
            root_segment_id="0,1")

    def test_two_root_ids(self):
        index = self._index(self._data())
        assert len(index) == 3
        # splits land on records whose segment id is a root id
        for e in index[1:]:
            assert self.SEGS[e.record_index] in "01"

    def test_non_divisible_file(self):
        index = self._index(self._data(drop=2))
        assert len(index) == 3


def _multiseg_file(n_roots: int = 40, children_per_root: int = 3) -> bytes:
    """RDW multisegment EBCDIC file: root 'C' records with trailing child
    'P' records (multi-root: roots alternate id C and D)."""
    out = []
    for r in range(n_roots):
        sid = "C" if r % 2 == 0 else "D"
        body = f"{sid}COMP{r:04d}"
        out.append(_rdw_le(len(body)) + ebcdic_encode(body))
        for c in range(children_per_root):
            child = f"PPHONE{r:03d}{c:01d}"
            out.append(_rdw_le(len(child)) + ebcdic_encode(child))
    return b"".join(out)


MULTISEG_COPYBOOK = """
       01  RECORD.
           05  SEG-ID        PIC X(1).
           05  COMPANY.
               10  NAME      PIC X(8).
           05  CONTACT REDEFINES COMPANY.
               10  PHONE     PIC X(9).
"""


def _write(tmp, name, data):
    p = os.path.join(tmp, name)
    with open(p, "wb") as f:
        f.write(data)
    return p


MULTISEG_OPTS = dict(
    is_record_sequence="true",
    segment_field="SEG-ID",
    segment_id_level0="C,D",
    segment_id_level1="P",
    generate_record_id="true",
    segment_id_prefix="ID",
    schema_retention_policy="collapse_root",
    **{"redefine-segment-id-map:1": "COMPANY => C,D",
       "redefine-segment-id-map:2": "CONTACT => P"})


class TestIndexedReadParity:
    def test_indexed_multiseg_read_matches_sequential(self):
        data = _multiseg_file()
        with tempfile.TemporaryDirectory() as tmp:
            path = _write(tmp, "m.bin", data)
            seq = read_cobol(path, copybook_contents=MULTISEG_COPYBOOK,
                             enable_indexes="false", **MULTISEG_OPTS)
            for split in (4, 7, 1000):
                idx = read_cobol(path, copybook_contents=MULTISEG_COPYBOOK,
                                 input_split_records=str(split),
                                 **MULTISEG_OPTS)
                assert idx.to_rows() == seq.to_rows(), f"split={split}"
                assert idx.to_arrow().equals(seq.to_arrow()), f"split={split}"

    def test_indexed_read_single_worker_matches(self):
        data = _multiseg_file()
        with tempfile.TemporaryDirectory() as tmp:
            path = _write(tmp, "m.bin", data)
            seq = read_cobol(path, copybook_contents=MULTISEG_COPYBOOK,
                             enable_indexes="false", **MULTISEG_OPTS)
            idx = read_cobol(path, copybook_contents=MULTISEG_COPYBOOK,
                             input_split_records="5", parallelism="1",
                             **MULTISEG_OPTS)
            assert idx.to_rows() == seq.to_rows()

    def test_indexed_split_by_size(self):
        data = _multiseg_file(200, 5)
        with tempfile.TemporaryDirectory() as tmp:
            path = _write(tmp, "m.bin", data)
            seq = read_cobol(path, copybook_contents=MULTISEG_COPYBOOK,
                             enable_indexes="false", **MULTISEG_OPTS)
            idx = read_cobol(path, copybook_contents=MULTISEG_COPYBOOK,
                             input_split_size_mb="1", **MULTISEG_OPTS)
            # 1MB splits on a small file: single shard, still identical
            assert idx.to_rows() == seq.to_rows()

    def test_indexed_hierarchical_read_matches(self):
        data = _multiseg_file(30, 2)
        opts = dict(
            is_record_sequence="true",
            segment_field="SEG-ID",
            generate_record_id="true",
            schema_retention_policy="collapse_root",
            **{"redefine-segment-id-map:1": "COMPANY => C,D",
               "redefine-segment-id-map:2": "CONTACT => P",
               "segment-children:1": "COMPANY => CONTACT"})
        with tempfile.TemporaryDirectory() as tmp:
            path = _write(tmp, "h.bin", data)
            seq = read_cobol(path, copybook_contents=MULTISEG_COPYBOOK,
                             enable_indexes="false", **opts)
            idx = read_cobol(path, copybook_contents=MULTISEG_COPYBOOK,
                             input_split_records="6", **opts)
            assert idx.to_rows() == seq.to_rows()

    def test_invalid_split_sizes_raise(self):
        data = _multiseg_file(4, 1)
        with tempfile.TemporaryDirectory() as tmp:
            path = _write(tmp, "m.bin", data)
            with pytest.raises(ValueError, match="number of records"):
                read_cobol(path, copybook_contents=MULTISEG_COPYBOOK,
                           input_split_records="0", **MULTISEG_OPTS)
            with pytest.raises(ValueError, match="input split size"):
                read_cobol(path, copybook_contents=MULTISEG_COPYBOOK,
                           input_split_size_mb="9999", **MULTISEG_OPTS)


class TestFastIndexMatchesGeneric:
    """The vectorized RDW index must reproduce the per-record generator
    exactly (split positions, record_index counting quirks, size drift)."""

    def _compare(self, data: bytes, params: ReaderParameters):
        reader = VarLenReader(MULTISEG_COPYBOOK, params)
        fast = reader.generate_index_fast(data, file_id=7)
        assert fast is not None
        slow = reader.generate_index(MemoryStream(data), file_id=7)
        assert fast == slow

    def test_records_mode(self):
        data = _multiseg_file(25, 2)
        for split in (1, 3, 4, 10, 500):
            self._compare(data, ReaderParameters(
                is_record_sequence=True, input_split_records=split))

    def test_records_mode_with_root_boundaries(self):
        data = _multiseg_file(25, 3)
        for split in (2, 5, 9):
            self._compare(data, ReaderParameters(
                is_record_sequence=True, input_split_records=split,
                multisegment=MultisegmentParameters(
                    segment_id_field="SEG-ID",
                    segment_level_ids=["C,D", "P"],
                    segment_id_redefine_map={"C": "COMPANY", "D": "COMPANY",
                                             "P": "CONTACT"})))

    def test_records_mode_with_file_header(self):
        data = b"HDRBYTES" + _multiseg_file(20, 2)
        self._compare(data, ReaderParameters(
            is_record_sequence=True, input_split_records=4,
            file_start_offset=8))

    def test_size_mode_drift(self):
        # force many size splits with a tiny artificial MB by monkeypatching
        # is impossible (min 1MB); use a larger file instead
        data = _multiseg_file(30000, 3)  # ~2.6 MB
        self._compare(data, ReaderParameters(
            is_record_sequence=True, input_split_size_mb=1))

    def test_size_mode_with_roots(self):
        data = _multiseg_file(30000, 3)
        self._compare(data, ReaderParameters(
            is_record_sequence=True, input_split_size_mb=1,
            multisegment=MultisegmentParameters(
                segment_id_field="SEG-ID",
                segment_level_ids=["C,D", "P"],
                segment_id_redefine_map={"C": "COMPANY", "D": "COMPANY",
                                         "P": "CONTACT"})))


class TestShardFooterRule:
    def test_footer_applies_only_at_true_eof(self):
        """Review pin: a shard's bounded stream ends mid-file; the
        file_end_offset footer rule must measure against the file's true
        end, not the shard limit — otherwise every non-final shard's tail
        record is silently truncated."""
        body = _multiseg_file(30, 3)
        data = body + b"FTRBYTES"
        opts = dict(MULTISEG_OPTS)
        with tempfile.TemporaryDirectory() as tmp:
            path = _write(tmp, "f.bin", data)
            seq = read_cobol(path, copybook_contents=MULTISEG_COPYBOOK,
                             enable_indexes="false", file_end_offset="8",
                             **opts)
            idx = read_cobol(path, copybook_contents=MULTISEG_COPYBOOK,
                             input_split_records="7", file_end_offset="8",
                             **opts)
            assert idx.to_rows() == seq.to_rows()

    def test_multi_root_segment_children_split_on_all_roots(self):
        """Review pin: with segment-children, every root id (not just the
        first) is a split boundary."""
        from cobrix_tpu.reader.var_len_reader import VarLenReader
        from cobrix_tpu.reader.parameters import (
            MultisegmentParameters, ReaderParameters)

        params = ReaderParameters(
            is_record_sequence=True,
            multisegment=MultisegmentParameters(
                segment_id_field="SEG-ID",
                segment_id_redefine_map={"C": "COMPANY", "D": "COMPANY",
                                         "P": "CONTACT"},
                field_parent_map={"CONTACT": "COMPANY"}))
        reader = VarLenReader(MULTISEG_COPYBOOK, params)
        _, root_id = reader._index_split_config()
        assert set(root_id.split(",")) == {"C", "D"}


# ---------------------------------------------------------------------------
# The second route of the indexed scan: a dense RDW file is framed once, by
# its index pass, and a shard starts with its slice of the pass's tables as
# soon as its cut is found (reader.index.preframed_route,
# VarLenReader.frame_index_fast, engine.chunks.preframed_var_len_chunks).
# The parent's route, index whole and every shard framing itself, is the
# reference: same entries, same tables.
# ---------------------------------------------------------------------------
import functools

from cobrix_tpu import native
from cobrix_tpu.api import parse_options
from cobrix_tpu.engine import chunks as engine_chunks
from cobrix_tpu.profiling import DeviceStats
from cobrix_tpu.reader import index as reader_index
from cobrix_tpu.reader import var_len_reader as vlr
from cobrix_tpu.reader.stream import open_stream


@functools.lru_cache(maxsize=None)
def _rdw_file(n_roots: int, children: int, big_endian: bool = False,
              stored_less: int = 0, header: bytes = b"",
              footer: bytes = b"", tail_children: int = 0) -> bytes:
    """`_multiseg_file` with the RDW written as the options under test
    read it: the header holds the payload's length less `stored_less`;
    `tail_children` more 'P' records follow the last root's own."""
    def record(body: str) -> bytes:
        value = (len(body) - stored_less).to_bytes(2, "big" if big_endian
                                                   else "little")
        rdw = value + b"\0\0" if big_endian else b"\0\0" + value
        return rdw + ebcdic_encode(body)

    out = [header]
    for r in range(n_roots):
        out.append(record(f"{'C' if r % 2 == 0 else 'D'}COMP{r:04d}"))
        out += [record(f"PPHONE{r % 1000:03d}{c % 10:01d}")
                for c in range(children)]
    out += [record(f"PPHONE999{c % 10:01d}") for c in range(tail_children)]
    return b"".join(out + [footer])


NO_LEVELS = {k: v for k, v in MULTISEG_OPTS.items()
             if not k.startswith("segment_id_level")}
PLAIN = dict(is_record_sequence="true", generate_record_id="true",
             schema_retention_policy="collapse_root")

# id -> (arguments of _rdw_file, read_cobol options)
PREFRAMED_CASES = {
    "le_records_at_roots": ((40, 3), dict(MULTISEG_OPTS,
                                          input_split_records="7")),
    "be_records_at_roots": ((40, 3, True), dict(
        MULTISEG_OPTS, input_split_records="7", is_rdw_big_endian="true")),
    "rdw_adjustment": ((40, 3, False, 2), dict(
        MULTISEG_OPTS, input_split_records="9", rdw_adjustment="2")),
    "rdw_part_of_record_length": ((40, 3, True, -4), dict(
        MULTISEG_OPTS, input_split_records="9", is_rdw_big_endian="true",
        is_rdw_part_of_record_length="true")),
    "file_header_and_footer": ((40, 3, False, 0, b"HDRBYTES", b"FTRBYTES"),
                               dict(MULTISEG_OPTS, input_split_records="7",
                                    file_start_offset="8",
                                    file_end_offset="8")),
    "file_header_no_segments": ((40, 3, False, 0, b"HDRBYTES"), dict(
        PLAIN, input_split_records="4", file_start_offset="8")),
    "size_split_at_roots": ((70000, 3), dict(MULTISEG_OPTS,
                                             input_split_size_mb="1")),
    "size_split_no_segments": ((70000, 3), dict(PLAIN,
                                                input_split_size_mb="1")),
    "segment_ids_without_levels": ((40, 3), dict(NO_LEVELS,
                                                 input_split_records="6")),
    "segment_id_filter": ((40, 3), dict(MULTISEG_OPTS, segment_filter="P",
                                        input_split_records="10")),
    "a_root_exactly_at_each_cut": ((40, 3), dict(MULTISEG_OPTS,
                                                 input_split_records="4")),
    "a_tail_without_roots": ((12, 2, False, 0, b"", b"", 300), dict(
        MULTISEG_OPTS, input_split_records="5")),
    "one_entry_for_the_file": ((40, 3), dict(MULTISEG_OPTS,
                                             input_split_records="100000")),
}


def _case(name: str, tmp_path):
    args, options = PREFRAMED_CASES[name]
    path = tmp_path / f"{name}.bin"
    path.write_bytes(_rdw_file(*args))
    return str(path), options


@pytest.fixture
def parents_route(monkeypatch):
    """A callable that sends every file down the parent's route from then
    on: no record is short enough for the other."""
    return lambda: monkeypatch.setattr(reader_index,
                                       "DENSE_MAX_MEAN_RECORD", 0)


def _shard_counts(data):
    stats = data.metrics.device_stats
    return stats.preframed_shards, stats.self_framed_shards


@pytest.mark.parametrize("slack", [48, 512, vlr.INDEX_WINDOW_SLACK],
                         ids=["slack48", "slack512", "slack1m"])
@pytest.mark.parametrize("name", sorted(PREFRAMED_CASES))
def test_preframed_index_is_the_parents_index_with_each_shards_tables(
        name, slack, tmp_path, monkeypatch):
    """Entry for entry what `generate_index_fast` and the per-record
    generator give, and for each entry the tables that `_frame_fast`
    scans off the entry's own byte range; whatever the windows' length
    (48 B of slack: windows that must grow; 1 MiB: the file in one)."""
    monkeypatch.setattr(vlr, "INDEX_WINDOW_SLACK", slack)
    path, options = _case(name, tmp_path)
    params, _ = parse_options(dict(options))
    reader = VarLenReader(MULTISEG_COPYBOOK, params)
    image = open(path, "rb").read()
    handed = list(reader.frame_index_fast(image, 7))
    entries = [entry for entry, _ in handed]
    assert entries == reader.generate_index_fast(image, 7)
    assert entries == reader.generate_index(MemoryStream(image), 7)
    for entry, framed in handed:
        nbytes = (0 if entry.offset_to < 0
                  else entry.offset_to - entry.offset_from)
        with open_stream(path, start_offset=entry.offset_from,
                         maximum_bytes=nbytes) as stream:
            _, _, offsets, lengths, segment_ids, _ = reader._frame_fast(
                stream)
        assert np.array_equal(framed.offsets, offsets)
        assert np.array_equal(framed.lengths, lengths)
        # and handed to _frame_fast, the same tables and the same ids
        with open_stream(path, start_offset=entry.offset_from,
                         maximum_bytes=nbytes) as stream:
            _, _, offsets2, lengths2, handed_ids, _ = reader._frame_fast(
                stream, framed=framed)
        assert offsets2 is framed.offsets and lengths2 is framed.lengths
        if segment_ids is None:
            assert framed.seg_bytes is None and handed_ids is None
        else:
            assert len(framed.seg_bytes) == len(offsets)
            assert handed_ids.uniq == segment_ids.uniq
            assert np.array_equal(handed_ids.codes, segment_ids.codes)
    # every record of the file in exactly one entry
    all_offsets, _ = native.rdw_scan(
        image, params.is_rdw_big_endian, reader._rdw_length_adjustment(),
        params.file_start_offset, params.file_end_offset)
    assert sum(len(f.offsets) for _, f in handed) == len(all_offsets)


@pytest.mark.parametrize("parallelism", ["1", "4"])
@pytest.mark.parametrize("name", sorted(PREFRAMED_CASES))
def test_preframed_read_equals_the_parents_read(name, parallelism, tmp_path,
                                                parents_route):
    path, options = _case(name, tmp_path)
    new = read_cobol(path, copybook_contents=MULTISEG_COPYBOOK,
                     parallelism=parallelism, **options)
    table = new.to_arrow()
    assert _shard_counts(new) == (new.metrics.shards, 0)
    parents_route()
    old = read_cobol(path, copybook_contents=MULTISEG_COPYBOOK,
                     parallelism=parallelism, **options)
    assert _shard_counts(old) == (0, old.metrics.shards)
    assert old.metrics.shards == new.metrics.shards
    assert table.equals(old.to_arrow())
    assert new.to_rows() == old.to_rows()
    options = {k: v for k, v in options.items()
               if not k.startswith("input_split")}
    whole = read_cobol(path, copybook_contents=MULTISEG_COPYBOOK,
                       enable_indexes="false", **options)
    if "file_start_offset" not in options:
        # (a counted file header shifts an indexed read's Record_Ids:
        # reference behaviour, IndexGenerator.scala:117-120)
        assert table.equals(whole.to_arrow())


def _sparse_file(n: int = 3000, width: int = 400) -> bytes:
    body = "C" + "X" * (width - 1)
    return b"".join(_rdw_le(width) + ebcdic_encode(body) for _ in range(n))


SPARSE_COPYBOOK = """
       01  RECORD.
           05  SEG-ID        PIC X(1).
           05  FILLER        PIC X(399).
"""


def _route(path, copybook=MULTISEG_COPYBOOK, io=None, **options):
    params, _ = parse_options(dict(options))
    reader = VarLenReader(copybook, params)
    return reader_index.preframed_route(reader, str(path), params, io)


class TestPreframedRule:
    """The decisions of `preframed_route`, read off the file and the
    read's configuration; nothing a caller sets chooses the route."""

    def test_a_dense_file_is_framed_once(self, tmp_path):
        path = tmp_path / "dense.bin"
        path.write_bytes(_rdw_file(40, 3))
        assert _route(path, input_split_records="7", **MULTISEG_OPTS)
        data = read_cobol(str(path), copybook_contents=MULTISEG_COPYBOOK,
                          input_split_records="7", **MULTISEG_OPTS)
        assert _shard_counts(data) == (data.metrics.shards, 0)
        assert data.metrics.shards > 3

    def test_a_sparse_file_is_not(self, tmp_path):
        path = tmp_path / "sparse.bin"
        path.write_bytes(_sparse_file())
        options = dict(is_record_sequence="true", input_split_records="500")
        assert not _route(path, SPARSE_COPYBOOK, **options)
        data = read_cobol(str(path), copybook_contents=SPARSE_COPYBOOK,
                          **options)
        assert _shard_counts(data) == (0, data.metrics.shards)
        assert data.metrics.shards == 6

    def test_the_constant_lies_between_the_two_cells(self):
        # exp2's records are 64-68 B, the orders' 722 B at the mean
        assert 70 < reader_index.DENSE_MAX_MEAN_RECORD < 700

    def test_density_is_read_off_the_first_mebibyte_only(self, tmp_path):
        dense_head = _rdw_file(70000, 3) + _sparse_file(200)
        assert len(_rdw_file(70000, 3)) > reader_index.DENSE_PROBE_BYTES
        path = tmp_path / "dense_head.bin"
        path.write_bytes(dense_head)
        options = dict(is_record_sequence="true", input_split_size_mb="1")
        assert _route(path, **options)
        path.write_bytes(_sparse_file(3000) + _rdw_file(40, 3))
        assert not _route(path, **options)

    def test_a_head_shorter_than_the_probe_is_walked_whole(self, tmp_path):
        path = tmp_path / "short.bin"
        path.write_bytes(_rdw_file(40, 3, False, 0, b"HDRBYTES",
                                   b"FTRBYTES"))
        options = dict(MULTISEG_OPTS, input_split_records="7",
                       file_start_offset="8", file_end_offset="8")
        assert path.stat().st_size < reader_index.DENSE_PROBE_BYTES
        assert _route(path, **options)
        # two records: the mean is theirs, the footer is not walked
        path.write_bytes(_rdw_file(1, 1, False, 0, b"HDRBYTES", b"FTRBYTES"))
        assert _route(path, **options)

    def test_a_file_one_shard_covers_is_not(self, tmp_path):
        path = tmp_path / "small.bin"
        path.write_bytes(_rdw_file(40, 3))
        assert not _route(path, **MULTISEG_OPTS)
        path.write_bytes(b"")
        assert not _route(path, input_split_records="7", **MULTISEG_OPTS)

    def test_a_head_the_walk_cannot_follow_is_left_to_the_index_pass(
            self, tmp_path):
        path = tmp_path / "zero.bin"
        path.write_bytes(b"\0\0\0\0" + _rdw_file(40, 3))
        options = dict(MULTISEG_OPTS, input_split_records="7")
        assert not _route(path, **options)
        with pytest.raises(ValueError, match="zero size record at 0 "):
            read_cobol(str(path), copybook_contents=MULTISEG_COPYBOOK,
                       **options)

    @pytest.mark.parametrize("policy", ["permissive", "drop_malformed"])
    def test_a_permissive_policy_is_not(self, tmp_path, policy):
        path = tmp_path / "dense.bin"
        path.write_bytes(_rdw_file(40, 3))
        options = dict(MULTISEG_OPTS, input_split_records="7",
                       record_error_policy=policy)
        assert not _route(path, **options)
        data = read_cobol(str(path), copybook_contents=MULTISEG_COPYBOOK,
                          **options)
        assert _shard_counts(data) == (0, data.metrics.shards)

    def test_a_stored_index_is_not(self, tmp_path):
        path = tmp_path / "dense.bin"
        path.write_bytes(_rdw_file(40, 3))
        options = dict(MULTISEG_OPTS, input_split_records="7",
                       cache_dir=str(tmp_path / "cache"))
        for _ in range(2):  # the pass that saves, the read that loads
            data = read_cobol(str(path),
                              copybook_contents=MULTISEG_COPYBOOK, **options)
            assert _shard_counts(data) == (0, data.metrics.shards)
            assert data.metrics.shards > 3
        assert data.metrics.as_dict()["io"]["index_hits"] == 1

    def test_framing_the_native_scan_does_not_do_is_not(self, tmp_path):
        path = tmp_path / "text.bin"
        path.write_bytes(_rdw_file(40, 3))
        assert not _route(path, is_record_sequence="true", is_text="true",
                          input_split_records="7")
        hierarchical = dict(
            is_record_sequence="true", segment_field="SEG-ID",
            input_split_records="7",
            **{"redefine-segment-id-map:1": "COMPANY => C,D",
               "redefine-segment-id-map:2": "CONTACT => P",
               "segment-children:1": "COMPANY => CONTACT"})
        assert not _route(path, **hierarchical)

    def test_other_planners_get_entries_only(self, tmp_path, monkeypatch):
        """The pipelined engine and the multihost executor plan through
        `plan_var_len_chunks`: a list of WorkShards that carry no table,
        and the dense route's pass is never run for them."""
        from cobrix_tpu.parallel.planner import WorkShard

        path = tmp_path / "dense.bin"
        path.write_bytes(_rdw_file(40, 3))
        options = dict(MULTISEG_OPTS, input_split_records="7")
        params, _ = parse_options(dict(options))
        reader = VarLenReader(MULTISEG_COPYBOOK, params)
        planned = engine_chunks.plan_var_len_chunks(reader, [str(path)],
                                                    params)
        assert isinstance(planned, list) and len(planned) > 3
        assert all(type(shard) is WorkShard for shard in planned)
        handed = list(engine_chunks.preframed_var_len_chunks(
            reader, [str(path)], params))
        assert [shard for shard, _ in handed] == planned
        assert all(framed is not None for _, framed in handed)

        def never(*args, **kwargs):
            raise AssertionError("the dense route's pass ran")

        monkeypatch.setattr(VarLenReader, "frame_index_fast", never)
        whole = read_cobol(str(path), copybook_contents=MULTISEG_COPYBOOK,
                           enable_indexes="false", **MULTISEG_OPTS)
        piped = read_cobol(str(path), copybook_contents=MULTISEG_COPYBOOK,
                           pipeline_workers="2", **options)
        assert _shard_counts(piped) == (0, 0)
        assert piped.to_arrow().equals(whole.to_arrow())
        hosts = read_cobol(str(path), copybook_contents=MULTISEG_COPYBOOK,
                           hosts="2", **options)
        assert _shard_counts(hosts) == (0, 0)
        assert hosts.to_arrow().equals(whole.to_arrow())

    def test_a_read_of_dense_and_sparse_files_routes_each(self, tmp_path):
        (tmp_path / "in").mkdir()
        wide = "C" + "W" * 299
        (tmp_path / "in" / "a.bin").write_bytes(_rdw_file(40, 3))
        (tmp_path / "in" / "b.bin").write_bytes(b"".join(
            _rdw_le(300) + ebcdic_encode(wide) for _ in range(30)))
        (tmp_path / "in" / "c.bin").write_bytes(_rdw_file(20, 2))
        copybook = """
       01  RECORD.
           05  SEG-ID        PIC X(1).
           05  COMPANY.
               10  NAME      PIC X(8).
           05  CONTACT REDEFINES COMPANY.
               10  PHONE     PIC X(9).
           05  FILLER        PIC X(290).
"""
        options = dict(MULTISEG_OPTS, input_split_records="8")
        data = read_cobol(str(tmp_path / "in"), copybook_contents=copybook,
                          **options)
        table = data.to_arrow()
        preframed, self_framed = _shard_counts(data)
        assert preframed > 5 and self_framed > 2
        assert preframed + self_framed == data.metrics.shards
        whole = read_cobol(str(tmp_path / "in"), copybook_contents=copybook,
                           enable_indexes="false", **MULTISEG_OPTS)
        assert table.equals(whole.to_arrow())


def test_the_shard_counts_ride_the_device_record():
    stats = DeviceStats()
    assert "preframed_shards" not in stats.as_dict()
    stats.note_shard(preframed=True)
    stats.note_shard(preframed=False)
    stats.note_shard(preframed=True)
    stats.note_plan(3, 1)
    said = stats.as_dict()
    assert (said["preframed_shards"], said["self_framed_shards"]) == (2, 1)
    assert (said["index_shards"], said["pool_split_files"]) == (3, 1)


def test_a_bad_header_past_the_first_window_is_named_where_it_lies(
        tmp_path, monkeypatch):
    """The pass walks windows; the error it raises counts from the file's
    first byte, as the parent's pass counts."""
    monkeypatch.setattr(vlr, "INDEX_WINDOW_SLACK", 64)
    good = _rdw_file(40, 3)
    path = tmp_path / "bad.bin"
    path.write_bytes(good + b"\0\0\0\0" + _rdw_file(4, 1))
    options = dict(MULTISEG_OPTS, input_split_records="7")
    with pytest.raises(ValueError,
                       match=f"zero size record at {len(good)} ") as new:
        read_cobol(str(path), copybook_contents=MULTISEG_COPYBOOK, **options)
    monkeypatch.setattr(reader_index, "DENSE_MAX_MEAN_RECORD", 0)
    with pytest.raises(ValueError) as old:
        read_cobol(str(path), copybook_contents=MULTISEG_COPYBOOK, **options)
    assert str(new.value) == str(old.value)
    assert new.value.offset == old.value.offset == len(good)


# ---------------------------------------------------------------------------
# The split follows the pool for a dense file (reader.index.index_split): a
# file under `parallelism` default splits whose records average under
# DENSE_MAX_MEAN_RECORD is cut into SHARDS_PER_THREAD shards a pool thread,
# at whole MiB.
# SPLIT_FLOOR is patched down to a few KiB so that files of a few MiB show
# it. The read at the file's own split is the reference: same tables.
# ---------------------------------------------------------------------------
from benchmark.generators import hier_companies as hier
from cobrix_tpu.api import _io_config
from cobrix_tpu.explain import explain

# 70,000 roots of 13 B and 210,000 children of 14 B: 3.67 MiB, so a pool
# of four or two cuts it at 1 MiB (the whole MiB above 3.67 / 8 or 4)
# into four shards
DENSE_ARGS = (70000, 3)
HIER_OPTIONS = dict(
    is_record_sequence="true", segment_field="SEGMENT-ID",
    generate_record_id="true",
    **{f"redefine_segment_id_map:{i}": f"{name} => {i + 1}"
       for i, name in enumerate(hier.SEGMENTS)},
    **{f"segment-children:{i}": f"{parent} => {child}"
       for i, (child, parent) in enumerate(hier.PARENT.items())})


@pytest.fixture
def low_floor(monkeypatch):
    monkeypatch.setattr(reader_index, "SPLIT_FLOOR", 4096)


def _split(path, parallelism, copybook=MULTISEG_COPYBOOK, **options):
    params, _ = parse_options(dict(options))
    reader = VarLenReader(copybook, params)
    return reader_index.index_split(reader, str(path), params, parallelism,
                                    _io_config(params))


# id -> (file bytes, read_cobol options, parallelism, the split and why)
SPLIT_CASES = {
    "dense_under_the_pools_work": (
        _rdw_file(*DENSE_ARGS), MULTISEG_OPTS, 4, ("pool", 1)),
    "dense_without_segments": (
        _rdw_file(*DENSE_ARGS), PLAIN, 4, ("pool", 1)),
    "a_pool_of_two": (_rdw_file(*DENSE_ARGS), MULTISEG_OPTS, 2, ("pool", 1)),
    "wide_records": (_sparse_file(), dict(is_record_sequence="true"), 4,
                     ("wide_records", 100)),
    "a_size_option": (_rdw_file(*DENSE_ARGS), dict(
        MULTISEG_OPTS, input_split_size_mb="2"), 4, ("option", 2)),
    "a_records_option": (_rdw_file(*DENSE_ARGS), dict(
        MULTISEG_OPTS, input_split_records="7"), 4, ("option", None)),
    "a_pool_of_one": (_rdw_file(*DENSE_ARGS), MULTISEG_OPTS, 1,
                      ("default", 100)),
    "under_two_floors": (_rdw_file(40, 3), MULTISEG_OPTS, 4,
                         ("default", 100)),
    "a_permissive_policy": (_rdw_file(*DENSE_ARGS), dict(
        MULTISEG_OPTS, record_error_policy="permissive"), 4,
        ("default", 100)),
    "a_file_header": (_rdw_file(70000, 3, False, 0, b"HDRBYTES"), dict(
        PLAIN, file_start_offset="8"), 4, ("default", 100)),
    "text_framing": (_rdw_file(*DENSE_ARGS), dict(
        is_record_sequence="true", is_text="true"), 4, ("default", 100)),
    "a_head_the_walk_cannot_follow": (
        b"\0\0\0\0" + _rdw_file(*DENSE_ARGS), MULTISEG_OPTS, 4,
        ("default", 100)),
}


@pytest.mark.parametrize("name", sorted(SPLIT_CASES))
def test_index_split_decides(name, tmp_path, low_floor):
    data, options, parallelism, expected = SPLIT_CASES[name]
    path = tmp_path / f"{name}.bin"
    path.write_bytes(data)
    copybook = (SPARSE_COPYBOOK if name == "wide_records"
                else MULTISEG_COPYBOOK)
    assert _split(path, parallelism, copybook, **options) == expected


def test_a_file_of_the_pools_work_or_more_keeps_the_default(
        tmp_path, low_floor, monkeypatch):
    path = tmp_path / "dense.bin"
    path.write_bytes(_rdw_file(*DENSE_ARGS))
    assert _split(path, 4, **MULTISEG_OPTS).why == "pool"
    # a default split of 1 MiB: four threads' work is 4 MiB, the file 3.67
    monkeypatch.setattr(reader_index, "DEFAULT_INDEX_ENTRY_SIZE_MB", 1)
    assert _split(path, 4, **MULTISEG_OPTS).why == "pool"
    assert _split(path, 3, **MULTISEG_OPTS) == ("default", 1)


def test_a_stored_index_keeps_the_default(tmp_path, low_floor):
    path = tmp_path / "dense.bin"
    path.write_bytes(_rdw_file(*DENSE_ARGS))
    options = dict(MULTISEG_OPTS, cache_dir=str(tmp_path / "cache"))
    assert _split(path, 4, **options) == ("default", 100)
    data = read_cobol(str(path), copybook_contents=MULTISEG_COPYBOOK,
                      parallelism="4", **options)
    said = data.metrics.device_stats.as_dict()
    assert (said["index_shards"], said["pool_split_files"]) == (1, 0)


def _hier_file():
    return hier.generate(3000, 2147483777)[0]


# id -> (file bytes, copybook, read_cobol options, whether the index pass
# is the file's one framing)
POOL_READ_CASES = {
    "exp2_shaped_framed_once": (
        lambda: _rdw_file(*DENSE_ARGS), MULTISEG_COPYBOOK, MULTISEG_OPTS,
        True),
    "no_segments": (lambda: _rdw_file(*DENSE_ARGS), MULTISEG_COPYBOOK,
                    PLAIN, True),
    "big_endian_ids_without_levels": (
        lambda: _rdw_file(70000, 3, True), MULTISEG_COPYBOOK,
        dict(NO_LEVELS, is_rdw_big_endian="true"), True),
    "hierarchical_test17": (_hier_file, hier.COPYBOOK, HIER_OPTIONS, False),
}


@pytest.mark.parametrize("name", sorted(POOL_READ_CASES))
def test_a_pool_split_read_equals_the_read_at_its_own_split(
        name, tmp_path, monkeypatch):
    """Table for table, Seg_Id and Record_Id and nested lists included:
    the file cut into four shards for a pool of four against the same
    file at the split it has without the rule (one shard: it is under
    100 MiB)."""
    make, copybook, options, framed_once = POOL_READ_CASES[name]
    path = tmp_path / f"{name}.bin"
    path.write_bytes(make())
    monkeypatch.setattr(reader_index, "SPLIT_FLOOR", 4096)
    pool = read_cobol(str(path), copybook_contents=copybook,
                      parallelism="4", **options)
    table = pool.to_arrow()
    said = pool.metrics.device_stats.as_dict()
    assert (said["index_shards"], said["pool_split_files"]) == (4, 1)
    assert _shard_counts(pool) == ((4, 0) if framed_once else (0, 4))
    monkeypatch.setattr(reader_index, "SPLIT_FLOOR", 1 << 40)
    own = read_cobol(str(path), copybook_contents=copybook,
                     parallelism="4", **options)
    assert own.metrics.shards == 1
    assert own.metrics.device_stats.as_dict()["pool_split_files"] == 0
    assert table.equals(own.to_arrow())
    if name == "exp2_shaped_framed_once":
        assert {"Seg_Id0", "Seg_Id1", "Record_Id"} <= set(table.column_names)
        assert pool.to_rows() == own.to_rows()


def test_explicit_options_and_other_planners_keep_their_splits(
        tmp_path, low_floor, monkeypatch):
    path = tmp_path / "dense.bin"
    path.write_bytes(_rdw_file(*DENSE_ARGS))
    sized = read_cobol(str(path), copybook_contents=MULTISEG_COPYBOOK,
                       parallelism="4", input_split_size_mb="2",
                       **MULTISEG_OPTS)
    counted = read_cobol(str(path), copybook_contents=MULTISEG_COPYBOOK,
                         parallelism="4", input_split_records="100000",
                         **MULTISEG_OPTS)
    alone = read_cobol(str(path), copybook_contents=MULTISEG_COPYBOOK,
                       parallelism="1", **MULTISEG_OPTS)
    for data, shards in ((sized, 2), (counted, 3), (alone, 1)):
        said = data.metrics.device_stats.as_dict()
        assert (said["index_shards"], said["pool_split_files"]) == \
            (shards, 0)
    whole = alone.to_arrow()
    assert sized.to_arrow().equals(whole)
    assert counted.to_arrow().equals(whole)

    def never(*args, **kwargs):
        raise AssertionError("the pool's split was asked for")

    monkeypatch.setattr(reader_index, "index_split", never)
    for options in (dict(pipeline_workers="2"), dict(hosts="2")):
        data = read_cobol(str(path), copybook_contents=MULTISEG_COPYBOOK,
                          parallelism="4", **options, **MULTISEG_OPTS)
        assert "index_shards" not in data.metrics.device_stats.as_dict()
        assert data.to_arrow().equals(whole)


def test_explain_says_which_split_a_read_takes_and_why(tmp_path,
                                                       low_floor):
    (tmp_path / "in").mkdir()
    (tmp_path / "in" / "a.bin").write_bytes(_rdw_file(*DENSE_ARGS))
    (tmp_path / "in" / "b.bin").write_bytes(_rdw_file(40, 3))
    path = str(tmp_path / "in")
    said = [{"why": "pool", "mb": 1}, {"why": "default", "mb": 100}]
    report = explain(copybook_contents=MULTISEG_COPYBOOK, path=path,
                     parallelism="4", **MULTISEG_OPTS)
    assert report.plan["index_split"] == said
    data = read_cobol(path, copybook_contents=MULTISEG_COPYBOOK,
                      parallelism="4", explain=True, **MULTISEG_OPTS)
    assert data.plan["index_split"] == said
    assert data.metrics.device_stats.as_dict()["pool_split_files"] == 1
    sized = explain(copybook_contents=MULTISEG_COPYBOOK, path=path,
                    parallelism="4", input_split_size_mb="2",
                    **MULTISEG_OPTS)
    assert sized.plan["index_split"] == [{"why": "option", "mb": 2}] * 2
    piped = explain(copybook_contents=MULTISEG_COPYBOOK, path=path,
                    parallelism="4", pipeline_workers="2", **MULTISEG_OPTS)
    assert "index_split" not in piped.plan
