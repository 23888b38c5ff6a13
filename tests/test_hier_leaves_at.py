"""A hierarchical read builds each string leaf at its own segment's rows
(`ArrowBatchBuilder.leaf_strings_at`) from what the batch holds: the
file image on the host kernels, the fetched matrix of code points on a
device backend. The tables equal the ones built at full length and
taken, `backend="numpy"`'s and the generator's reference tree for tree;
and every input the route declines (16-bit code points, a field past its
record's end, a column whose UTF-8 outgrows its buffer, no native
library) keeps the full-length build and `take`, counted as such."""
import pytest

from benchmark.generators import hier_companies as hier
from cobrix_tpu import native, read_cobol
from cobrix_tpu.reader.arrow_out import ArrowBatchBuilder

from test_hier_tracing import OPTIONS, SEED

pytestmark = pytest.mark.jax

# leaves of the copybook: 18 strings, 5 integers, the AMOUNT decimal
STRINGS, INTEGERS = 18, 5
CUSTOMER_ID, EMPLOYEE_ID = hier._ROOT_ID + 4, hier._ROOT_ID + 2
CP037_E_ACUTE = 0x51       # 'é': two bytes of UTF-8 where one came in


@pytest.fixture(scope="module")
def small(tmp_path_factory):
    data, facts = hier.generate(600, SEED)
    path = tmp_path_factory.mktemp("hier_leaves") / "hier.dat"
    path.write_bytes(data)
    return str(path), facts


def records(data: bytes):
    """(payload offset, payload length) of every RDW record."""
    out, at = [], 0
    while at < len(data):
        size = data[at + 2] | data[at + 3] << 8
        out.append((at + 4, size))
        at += 4 + size
    return out


@pytest.fixture
def taken_route(monkeypatch):
    """The full-length build and `take` for every string leaf of a
    batch that holds no file image: the route as it was before the
    leaves were built from the fetched code points."""
    built_at = ArrowBatchBuilder.leaf_strings_at

    def from_image_only(self, sts, positions):
        if self.batch.raw_source is None:
            return {}
        return built_at(self, sts, positions)

    def off():
        monkeypatch.setattr(ArrowBatchBuilder, "leaf_strings_at",
                            from_image_only)
    return off


def read(path, backend="jax", **options):
    data = read_cobol(path, backend=backend, **OPTIONS, **options)
    return data.to_arrow(), data.metrics.as_dict()


def leaf_counts(metrics):
    hier_counts = metrics["hier"]
    return hier_counts["hier_leaves_at"], hier_counts["hier_leaves_taken"]


def assert_same_tree(table, other):
    assert table.equals(other)
    assert table.to_pylist() == other.to_pylist()


@pytest.mark.parametrize("split", [
    {"input_split_records": "6500"},
    {"input_split_records": "3000"},
    {"input_split_records": "1100"},
])
def test_leaves_at_positions_equal_taken_numpy_and_reference(
        small, taken_route, split):
    path, facts = small
    table, metrics = read(path, **split)
    shards = metrics["shards"]
    assert shards >= 2
    assert leaf_counts(metrics) == ((STRINGS + INTEGERS) * shards, shards)
    assert "point_strings" not in metrics.get("native_passes", {})
    assert hier.check_table(table, facts) == []
    assert_same_tree(table, read(path, backend="numpy", **split)[0])

    taken_route()
    taken, taken_metrics = read(path, **split)
    assert leaf_counts(taken_metrics) == (INTEGERS * shards,
                                          (STRINGS + 1) * shards)
    assert taken_metrics["native_passes"]["point_strings"] == shards
    assert_same_tree(table, taken)


def test_a_16_bit_matrix_keeps_the_take(small, taken_route):
    path, _ = small
    options = dict(ebcdic_code_page="cp875", input_split_records="3000")
    table, metrics = read(path, **options)
    shards = metrics["shards"]
    assert leaf_counts(metrics) == (INTEGERS * shards,
                                    (STRINGS + 1) * shards)
    assert_same_tree(table, read(path, backend="numpy", **options)[0])
    taken_route()
    assert_same_tree(table, read(path, **options)[0])


def rewritten(tmp_path, small, edit):
    path, _ = small
    data = bytearray(open(path, "rb").read())
    data = edit(data, records(bytes(data)))
    out = tmp_path / "edited.dat"
    out.write_bytes(bytes(data))
    return str(out)


def test_a_field_past_its_records_end_keeps_the_take(
        tmp_path, small, taken_route):
    """One EMPLOYEE record cut to 100 B: PHONE-NUM (bytes 91-107) runs
    past its end, so that leaf of that shard is taken; the other four
    EMPLOYEE strings are still built at their rows."""
    def cut_one_employee(data, recs):
        at, size = next((at, size) for at, size in recs
                        if data[at] == EMPLOYEE_ID)
        del data[at + 100:at + size]
        data[at - 2] = 100
        return data

    path = rewritten(tmp_path, small, cut_one_employee)
    table, metrics = read(path, input_split_records="3000")
    shards = metrics["shards"]
    assert leaf_counts(metrics) == ((STRINGS + INTEGERS) * shards - 1,
                                    shards + 1)
    assert_same_tree(table, read(path, backend="numpy",
                                 input_split_records="3000")[0])
    taken_route()
    assert_same_tree(table, read(path, input_split_records="3000")[0])


def test_a_column_that_outgrows_its_buffer_keeps_the_take(
        tmp_path, small, taken_route):
    """Every CUSTOMER's ZIP all cp037 'é': 20 B of UTF-8 a row where the
    buffer holds 10 and 64 B, so ZIP alone is taken (in shards of over
    six customers: two of about 6,000 records here) and the struct's
    other strings are built at their rows."""
    def accented_zips(data, recs):
        for at, _ in recs:
            if data[at] == CUSTOMER_ID:
                data[at + 51:at + 61] = bytes([CP037_E_ACUTE]) * 10
        return data

    path = rewritten(tmp_path, small, accented_zips)
    options = dict(ebcdic_code_page="cp037", input_split_records="6500")
    table, metrics = read(path, **options)
    shards = metrics["shards"]
    assert leaf_counts(metrics) == ((STRINGS + INTEGERS - 1) * shards,
                                    2 * shards)
    customers = (table.column("ENTITY").combine_chunks()
                 .field("COMPANY").field("CUSTOMER").flatten())
    assert set(customers.field("ZIP").to_pylist()) == {"é" * 10}
    assert_same_tree(table, read(path, backend="numpy", **options)[0])
    taken_route()
    assert_same_tree(table, read(path, **options)[0])


def test_without_the_native_library_every_string_is_taken(small):
    path, facts = small
    native.set_disabled(True)
    try:
        table, metrics = read(path, input_split_records="3000")
    finally:
        native.set_disabled(False)
    shards = metrics["shards"]
    assert leaf_counts(metrics) == (INTEGERS * shards,
                                    (STRINGS + 1) * shards)
    assert hier.check_table(table, facts) == []
    assert_same_tree(table, read(path, input_split_records="3000")[0])
