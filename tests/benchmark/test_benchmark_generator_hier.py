"""The frozen hierarchical generator: RDW bytes of the seven stated
widths in upstream's three levels, whole companies a chunk from
`seed + k`, the same bytes from the same seed; a plain reference of the
assembly that equals the scalar oracle; and an account of the decoded
table that a wrong nesting or a shifted `Record_Id` fails."""
import numpy as np
import pyarrow as pa
import pytest

from benchmark_testing import REPO  # noqa: F401  (puts the repo on sys.path)

from benchmark import manifest
from benchmark.generators import hier_companies as hier
from cobrix_tpu import read_cobol
from cobrix_tpu.testing import generators as original

SEED = 2147483999  # past 2**31, as the driver's seeds are
CONFIG = manifest.load_json("configs", "hier_companies_test17.json")


def walk(data: bytes) -> list:
    """[(payload length, segment id)] of every RDW record."""
    records, pos = [], 0
    while pos < len(data):
        assert data[pos] == 0 and data[pos + 1] == 0  # little-endian RDW
        length = data[pos + 2] | data[pos + 3] << 8
        records.append((length, data[pos + 4] - 0xF0))
        pos += 4 + length
    assert pos == len(data)
    return records


def options(**more):
    return dict(CONFIG["reader_options"], copybook_contents=hier.COPYBOOK,
                **more)


def test_bytes_parse_as_rdw_with_the_stated_widths_and_levels():
    companies = hier.records_for(2 << 20)
    data, facts = hier.generate(companies, SEED)
    records = walk(data)
    assert facts["records"] == companies and facts["bytes"] == len(data)
    assert abs(len(data) - (2 << 20)) < (2 << 20) * 0.05
    widths = CONFIG["record_bytes"]
    assert set(records) == {(widths[name], i + 1)
                            for i, name in enumerate(hier.SEGMENTS)}
    ids = np.array([segment for _, segment in records])
    assert ids[0] == 1
    assert np.array_equal(np.bincount(ids, minlength=8)[1:],
                          facts["segment_records"])
    assert facts["segment_records"][0] == companies
    # the counts drawn are the runs in the file, level by level, and a
    # child only ever follows its own parent's subtree
    for child, parent in hier.PARENT.items():
        c, p = hier.SEGMENTS.index(child) + 1, hier.SEGMENTS.index(parent) + 1
        owner = np.maximum.accumulate(np.where(ids == p, np.arange(len(ids)),
                                               -1))
        per_parent = np.bincount(owner[ids == c],
                                 minlength=len(ids))[ids == p]
        drawn = facts[f"{child.lower()}_counts"]
        assert drawn.dtype == np.uint8 and np.array_equal(per_parent, drawn)
        most = hier.MAX_CHILDREN[child]
        assert CONFIG["children_per_parent"][f"{parent} => {child}"] == (
            f"0 to {most}, uniform")
        share = np.bincount(drawn, minlength=most + 1) / len(drawn)
        assert drawn.max() == most and np.all(
            np.abs(share - 1 / (most + 1)) < 0.03)
    # every company's offset is that of a COMPANY record's RDW
    assert all(data[at + 4] == 0xF1 for at in facts["company_offset"][:50])
    assert hier.COPYBOOK == original.HIERARCHICAL_COPYBOOK
    assert dict(zip(map(str, range(1, 8)), hier.SEGMENTS)) == \
        original.HIERARCHICAL_SEGMENT_MAP == CONFIG["segment_ids"]
    assert hier.PARENT == original.HIERARCHICAL_PARENT_MAP


def test_the_same_seed_gives_the_same_bytes_and_another_seed_others():
    data, facts = hier.generate(300, SEED)
    again, facts_again = hier.generate(300, SEED)
    assert data == again and facts["chunks"] == facts_again["chunks"]
    other, _ = hier.generate(300, SEED + 1)
    assert other != data
    assert data != original.generate_hierarchical(300, seed=SEED)
    assert "NOT that port's" in hier.__doc__


def test_a_chunk_is_generated_in_seconds():
    import time

    companies = hier.records_for(CONFIG["full"]["generate_chunk_bytes"])
    t0 = time.perf_counter()
    data, facts = hier.generate(companies, SEED)
    assert time.perf_counter() - t0 < 20  # about 1.5 s on an idle core
    assert abs(len(data) - (32 << 20)) < (32 << 20) * 0.02
    assert 19.5 < facts["segment_records"].sum() / companies < 20.5


def test_nothing_of_the_program_is_imported():
    import ast

    tree = ast.parse(open(hier.__file__).read())
    imported = [node.module or "" for node in ast.walk(tree)
                if isinstance(node, ast.ImportFrom)]
    imported += [alias.name for node in ast.walk(tree)
                 if isinstance(node, ast.Import) for alias in node.names]
    assert not [name for name in imported if "cobrix_tpu" in name]
    assert set(imported) <= {"decimal", "zlib", "numpy", "ebcdic",
                             "pyarrow", "pyarrow.compute"}


@pytest.fixture(scope="module")
def decoded(tmp_path_factory):
    """(path, facts, the host kernels' table) of a file of three chunks,
    each from `seed + k`, each beginning with a COMPANY."""
    parts = [hier.generate(120, SEED + k) for k in range(3)]
    path = tmp_path_factory.mktemp("hier") / "input.dat"
    path.write_bytes(b"".join(data for data, _ in parts))
    facts = hier.merge_facts([f for _, f in parts])
    assert [c["seed"] for c in facts["chunks"]] == [SEED, SEED + 1, SEED + 2]
    data = path.read_bytes()
    assert facts["bytes"] == len(data) and facts["records"] == 360
    assert all(data[at + 4] == 0xF1 for at in facts["company_offset"])
    starts = np.cumsum([0] + [c["bytes"] for c in facts["chunks"]])[:-1]
    assert set(starts) <= set(facts["company_offset"])
    table = read_cobol(str(path), **options(backend="numpy")).to_arrow()
    return str(path), facts, table


def test_reference_rows_equal_the_scalar_oracle(decoded):
    path, facts, table = decoded
    oracle = read_cobol(path, **options(backend="host")).to_arrow()
    assert oracle.equals(table)
    expected = oracle.to_pylist()
    rows = hier.reference_rows(path, range(360))
    assert [rows[i] for i in range(360)] == expected
    # a chosen few, and a later piece of the file read as bytes
    assert hier.reference_rows(path, [7, 200]) == {7: expected[7],
                                                   200: expected[200]}
    at = int(facts["company_offset"][120])
    first_record = expected[119]["Record_Id"]
    piece = hier.reference_rows(open(path, "rb").read()[at:], [0, 5],
                                first_record)
    assert piece == {0: expected[120], 5: expected[125]}


def test_sample_is_a_prefix_of_whole_companies(decoded, tmp_path):
    path, _, table = decoded
    out = tmp_path / "sample.dat"
    idx = hier.sample(path, str(out), 480, SEED)
    records = walk(out.read_bytes())
    assert 240 <= len(records) <= 480 and records[0][1] == 1
    assert sum(segment == 1 for _, segment in records) == len(idx)
    assert np.array_equal(idx, np.arange(len(idx)))
    # the next byte of the file begins a COMPANY: the prefix ends where
    # a company ends
    assert open(path, "rb").read()[out.stat().st_size + 4] == 0xF1
    oracle = read_cobol(str(out), **options(backend="host")).to_arrow()
    assert table.take(pa.array(idx)).equals(oracle)


def replaced(table, path: list, array):
    """`table` with the nested field at `path` under ENTITY replaced."""
    def put(struct, names):
        fields = [struct.field(i) for i in range(struct.type.num_fields)]
        at = struct.type.get_field_index(names[0])
        if len(names) == 1:
            fields[at] = array
        elif pa.types.is_list(fields[at].type):
            fields[at] = pa.ListArray.from_arrays(
                fields[at].offsets, put(fields[at].values, names[1:]))
        else:
            fields[at] = put(fields[at], names[1:])
        return pa.StructArray.from_arrays(
            fields, names=[f.name for f in struct.type])

    entity = put(table.column("ENTITY").combine_chunks(), path)
    return table.set_column(table.schema.get_field_index("ENTITY"),
                            "ENTITY", entity)


def test_check_table_holds_and_names_what_is_wrong(decoded):
    _, facts, table = decoded
    assert hier.check_table(table, facts) == []
    assert "rows" in hier.check_table(table.slice(1), facts)[0]
    company = table.column("ENTITY").combine_chunks().field("COMPANY")
    depts = company.field("DEPT")
    offsets = depts.offsets.to_numpy().copy()
    at = int(np.flatnonzero(np.diff(offsets) > 0)[3])

    # one child moved to the neighbouring parent: the same structs, one
    # list a child longer and the next a child shorter
    moved = offsets.copy()
    moved[at + 1] -= 1
    wrong = hier.check_table(replaced(table, ["COMPANY", "DEPT"],
                                      pa.ListArray.from_arrays(
                                          pa.array(moved), depts.values)),
                             facts)
    assert wrong and "DEPT lists' lengths" in wrong[0]

    # one child dropped
    keep = np.ones(len(depts.values), dtype=bool)
    keep[offsets[at]] = False
    dropped = offsets - (offsets > offsets[at])
    wrong = hier.check_table(replaced(
        table, ["COMPANY", "DEPT"], pa.ListArray.from_arrays(
            pa.array(dropped.astype(np.int32)),
            depts.values.filter(pa.array(keep)))), facts)
    assert any("DEPT structs" in w for w in wrong)

    # a Record_Id shifted by one, as a shard's start counted wrong
    ids = table.column("Record_Id").to_numpy().copy()
    ids[200:] += 1
    wrong = hier.check_table(table.set_column(
        table.schema.get_field_index("Record_Id"), "Record_Id",
        pa.array(ids)), facts)
    assert wrong and "the first row 200" in wrong[0]

    # a leaf that no count or sum reads, in a child of the third level:
    # only the plain reference sees it
    first = company.field("CUSTOMER").values.field("CONTACT").values.field(
        "FIRST_NAME")
    other = pa.array(["X" + v for v in first.to_pylist()])
    wrong = hier.check_table(replaced(
        table, ["COMPANY", "CUSTOMER", "CONTACT", "FIRST_NAME"], other),
        facts)
    assert wrong and "plain reference" in wrong[0]


def test_the_reference_sample_takes_the_companies_at_the_index_splits():
    offsets = np.arange(0, 512 << 20, 1341)
    near = hier._boundary_companies(offsets, 512 << 20)
    assert len(near) == 5 * 4
    for k in range(1, 6):
        cut = k * (100 << 20)
        first_behind = int(np.searchsorted(offsets, cut))
        assert {first_behind - 2, first_behind - 1, first_behind,
                first_behind + 1} <= set(near)
    assert len(hier._boundary_companies(offsets[:100], 100 * 1341)) == 0
