"""Device-resident query path tests (parallel/query.py): the decode +
aggregate program whose only D2H traffic is scalars — the architectural
answer to the remote-TPU transfer wall (VERDICT r1/r2 ask #1).

Parity is pinned against aggregates computed directly from the values the
generator encoded, with batch sizes that FORCE padding: all-zero pad rows
decode as valid zeros for the binary codecs, so an unmasked reduction
inflates count and drags min to 0 — the round-2 advisor finding.
"""
import struct

import numpy as np
import pytest

from cobrix_tpu import read_cobol
from cobrix_tpu.copybook.copybook import parse_copybook
from cobrix_tpu.copybook.datatypes import FloatingPointFormat
from cobrix_tpu.parallel import (DeviceAggregator, aggregate_file,
                                 merge_aggregates)
from cobrix_tpu.testing.generators import (
    encode_comp3_unsigned,
    encode_comp_be,
    encode_display_unsigned,
)

pytestmark = pytest.mark.jax

COPYBOOK = """
        01  R.
            05  A       PIC 9(4)      COMP.
            05  B       PIC S9(5)V99  COMP-3.
            05  C       PIC 9(3).
            05  CV      PIC 9(3)V99.
            05  D       COMP-2.
            05  BAD     PIC 9(5)      COMP-3.
            05  E OCCURS 3.
               10  X    PIC 9(7)      COMP.
"""

N = 37  # NOT a power-of-two bucket: forces zero-padding on device


@pytest.fixture(scope="module")
def dataset():
    rng = np.random.default_rng(7)
    a = rng.integers(1, 9999, size=N)
    b = rng.integers(1, 9999999, size=N)          # mantissa of S9(5)V99
    c = rng.integers(1, 999, size=N)
    cv = rng.integers(1, 99999, size=N)           # mantissa of 9(3)V99
    d = rng.uniform(-1000.0, 1000.0, size=N)
    x = rng.integers(1, 9999999, size=(N, 3))
    parts = [
        encode_comp_be(a, 2),
        encode_comp3_unsigned(b, 7),
        encode_display_unsigned(c, 3),
        encode_display_unsigned(cv, 5),
        np.frombuffer(
            b"".join(struct.pack(">d", v) for v in d),
            dtype=np.uint8).reshape(N, 8),
        np.full((N, 3), 0xFF, dtype=np.uint8),    # BAD: malformed BCD
        encode_comp_be(x[:, 0], 4),
        encode_comp_be(x[:, 1], 4),
        encode_comp_be(x[:, 2], 4),
    ]
    data = np.concatenate(parts, axis=1)
    return data, dict(a=a, b=b, c=c, cv=cv, d=d, x=x)


@pytest.fixture(scope="module")
def copybook():
    return parse_copybook(
        COPYBOOK, floating_point_format=FloatingPointFormat.IEEE754)


def test_aggregate_masks_batch_padding(copybook, dataset):
    data, v = dataset
    agg = DeviceAggregator(copybook)
    res = agg.aggregate(data)

    # counts must be the true record count — zero pad rows decode as
    # VALID zeros for COMP/COMP-3/COMP-2 and would otherwise inflate it
    for name in ("A", "B", "C", "D", "X"):
        expected = 3 * N if name == "X" else N
        assert res[name]["count"] == expected, name

    # values generated strictly positive: an unmasked pad row would pull
    # min to 0
    assert res["A"]["min"] == v["a"].min()
    assert res["A"]["max"] == v["a"].max()
    assert res["A"]["sum"] == v["a"].sum()

    # COMP-3 with V99: aggregates come back in field units (scaled)
    assert res["B"]["sum"] == pytest.approx(v["b"].sum() / 100.0)
    assert res["B"]["min"] == pytest.approx(v["b"].min() / 100.0)

    assert res["C"]["sum"] == v["c"].sum()

    # zoned DISPLAY with implied V99: static PIC scale applies (the
    # dot_scale plane only carries literal '.' positions)
    assert res["CV"]["sum"] == pytest.approx(v["cv"].sum() / 100.0)
    assert res["CV"]["min"] == pytest.approx(v["cv"].min() / 100.0)

    # OCCURS slots aggregate together
    assert res["X"]["sum"] == v["x"].sum()
    assert res["X"]["min"] == v["x"].min()
    assert res["X"]["max"] == v["x"].max()


def test_aggregate_doubles_on_device(copybook, dataset):
    data, v = dataset
    res = DeviceAggregator(copybook).aggregate(data)
    assert res["D"]["count"] == N
    assert res["D"]["sum"] == pytest.approx(v["d"].sum())
    assert res["D"]["min"] == pytest.approx(v["d"].min())
    assert res["D"]["max"] == pytest.approx(v["d"].max())


def test_all_invalid_field_reports_none_not_inf(copybook, dataset):
    data, _ = dataset
    res = DeviceAggregator(copybook).aggregate(data)
    assert res["BAD"]["count"] == 0
    assert res["BAD"]["sum"] is None
    assert res["BAD"]["min"] is None   # not +inf
    assert res["BAD"]["max"] is None   # not -inf


def test_aggregate_projects_to_selected_columns(copybook, dataset):
    data, v = dataset
    res = DeviceAggregator(copybook, columns=["A"]).aggregate(data)
    assert set(res) == {"A"}
    assert res["A"]["sum"] == v["a"].sum()
    assert res["A"]["count"] == N


def test_streamed_blocks_merge_to_single_shot(copybook, dataset):
    """chip_smoke.py's streaming loop: fixed-size padded blocks H2D,
    partial aggregates merged host-side — must equal the one-shot aggregate."""
    data, _ = dataset
    agg = DeviceAggregator(copybook)
    one = agg.aggregate(data)
    block = 16
    parts = []
    for i in range(0, N, block):
        x, n = agg.put(data[i:i + block], block=block)
        parts.append(agg.aggregate_device(x, n))
    merged = merge_aggregates(parts)
    for name in one:
        assert merged[name]["count"] == one[name]["count"], name
        for k in ("min", "max"):
            assert merged[name][k] == one[name][k], (name, k)
        if one[name]["sum"] is None:
            assert merged[name]["sum"] is None
        else:
            assert merged[name]["sum"] == pytest.approx(one[name]["sum"])


def test_aggregate_file_helper(copybook, dataset):
    data, v = dataset
    res = aggregate_file(copybook, data.tobytes())
    assert res["A"]["sum"] == v["a"].sum()
    assert res["X"]["count"] == 3 * N


def test_byte_projection_cuts_transfer_and_keeps_parity(copybook, dataset):
    """A narrow `columns` selection must byte-project the H2D payload
    (DeviceAggregator._build_byte_projection rewrites the plan offsets into
    a packed layout) and still aggregate identically to the unprojected
    query. The middle COMP-2/BAD/OCCURS bytes are not shipped at all."""
    data, v = dataset
    # A sits at the record start, X at the tail: the bytes between (B, C,
    # CV, D, BAD — ~29 of 43) are never shipped. A prefix selection would
    # be handled by max_extent alone; the gather covers the scattered case.
    agg = DeviceAggregator(copybook, columns=["A", "X"])
    assert agg.gather_index is not None
    assert len(agg.gather_index) < agg.record_extent
    res = agg.aggregate(data)
    assert set(res) == {"A", "X"}
    assert res["A"]["sum"] == v["a"].sum()
    assert res["A"]["count"] == N
    assert res["X"]["sum"] == v["x"].sum()
    assert res["X"]["min"] == v["x"].min()

    # dense selections skip the gather entirely
    dense = DeviceAggregator(copybook)
    assert dense.gather_index is None


def test_byte_projection_streamed_blocks(copybook, dataset):
    """Projection composes with the streaming put/submit/fetch loop."""
    data, v = dataset
    agg = DeviceAggregator(copybook, columns=["X"])
    parts = []
    for i in range(0, N, 16):
        x, n = agg.put(data[i:i + 16], block=16)
        parts.append(agg.aggregate_device(x, n))
    merged = merge_aggregates(parts)
    assert merged["X"]["count"] == 3 * N
    assert merged["X"]["sum"] == v["x"].sum()
