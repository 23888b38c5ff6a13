"""The frozen exp2 generator: RDW bytes of the stated widths and mix, the
same bytes from the same seed, and an account of the decoded table
(segments, both Seg_Id columns, nulls, every string as drawn, sums) that
a wrong table fails."""
import numpy as np
import pyarrow as pa
import pyarrow.compute as pc
import pytest

from benchmark_testing import REPO  # noqa: F401  (puts the repo on sys.path)

from benchmark import manifest
from benchmark.generators import exp2_companies_narrow as exp2
from cobrix_tpu.testing import generators as original

SEED = 2147483999  # past 2**31, as the driver's seeds are
CONFIG = manifest.load_json("configs", "exp2_multiseg_narrow.json")


def walk(data: bytes) -> list:
    """[(payload length, EBCDIC segment byte)] of every RDW record."""
    records, pos = [], 0
    while pos < len(data):
        assert data[pos] == 0 and data[pos + 1] == 0  # little-endian RDW
        length = data[pos + 2] | data[pos + 3] << 8
        records.append((length, data[pos + 4]))
        pos += 4 + length
    assert pos == len(data)
    return records


def test_bytes_parse_as_rdw_with_the_stated_widths_and_mix():
    records_asked = exp2.records_for(4 << 20)
    data, facts = exp2.generate(records_asked, SEED)
    records = walk(data)
    assert len(records) == records_asked == facts["records"]
    assert len(data) == facts["bytes"] and abs(len(data) - (4 << 20)) < 4096
    widths = CONFIG["record_bytes"]
    assert set(records) == {(widths["C"] - 4, 0xC3), (widths["P"] - 4, 0xD7)}
    is_c = np.array([segment == 0xC3 for _, segment in records])
    assert is_c[0] and is_c.sum() == facts["c_records"]
    assert (~is_c).sum() == facts["p_records"]
    # one 'C' then zero to four 'P', uniform
    contacts = np.diff(np.append(np.flatnonzero(is_c), len(records))) - 1
    assert np.array_equal(contacts, facts["contacts"])
    assert facts["contacts"].dtype == np.uint8
    share = np.bincount(contacts, minlength=5) / len(contacts)
    assert contacts.max() == 4 and np.all(np.abs(share - 0.2) < 0.01)
    assert exp2.COPYBOOK == original.EXP2_COPYBOOK


def test_the_same_seed_gives_the_same_bytes_and_another_seed_others():
    data, facts = exp2.generate(5000, SEED)
    again, facts_again = exp2.generate(5000, SEED)
    assert data == again
    assert facts["company_id_sum"] == facts_again["company_id_sum"]
    other, _ = exp2.generate(5000, SEED + 1)
    assert other != data
    # its draws are not the program's generator's, and it says so
    assert data != bytes(original.generate_exp2(5000, seed=SEED))
    assert "NOT that generator's" in exp2.__doc__


def test_a_chunk_is_generated_in_about_a_second():
    import time

    records = exp2.records_for(CONFIG["full"]["generate_chunk_bytes"])
    t0 = time.perf_counter()
    data, _ = exp2.generate(records, SEED)
    assert time.perf_counter() - t0 < 10  # about 1 s on an idle core
    assert abs(len(data) - (32 << 20)) < 4096


def options():
    return dict(CONFIG["reader_options"], copybook_contents=exp2.COPYBOOK)


@pytest.fixture(scope="module")
def decoded(tmp_path_factory):
    """(path, facts, the host kernels' table) of a file of two chunks."""
    from cobrix_tpu import read_cobol

    parts = [exp2.generate(700, SEED + k) for k in range(2)]
    path = tmp_path_factory.mktemp("exp2") / "input.dat"
    path.write_bytes(b"".join(data for data, _ in parts))
    facts = exp2.merge_facts([f for _, f in parts])
    table = read_cobol(str(path), **dict(options(),
                                         backend="numpy")).to_arrow()
    return str(path), facts, table


def test_merged_facts_follow_the_chunks(decoded):
    _, facts, table = decoded
    assert facts["records"] == 1400 == table.num_rows
    assert len(facts["contacts"]) == facts["c_records"]
    assert int(facts["contacts"].sum()) == facts["p_records"]
    # what was drawn for the strings: one entry per company, per contact
    for key in ("company_name", "street_number"):
        assert len(facts[key]) == facts["c_records"]
    for key in ("phone_area", "phone_digits", "contact_person"):
        assert len(facts[key]) == facts["p_records"]


def test_check_table_passes_on_a_true_table(decoded):
    _, facts, table = decoded
    assert exp2.check_table(table, facts) == []


def replace_column(table, name, values):
    return table.set_column(table.schema.get_field_index(name),
                            table.schema.field(name), values)


def with_seg_id(table, name, row, value):
    values = table.column(name).to_pylist()
    values[row] = value
    return replace_column(table, name, pa.array(values, pa.string()))


@pytest.mark.parametrize("spoil,names", [
    (lambda t: with_seg_id(t, "Seg_Id0", 900, "A_0_1"), ["Seg_Id0"]),
    (lambda t: with_seg_id(t, "Seg_Id0", 0, "B_0_0"), ["Seg_Id0"]),
    (lambda t: with_seg_id(t, "Seg_Id1", 0, "A_0_0_L1_0"), ["Seg_Id1"]),
    (lambda t: with_seg_id(t, "Seg_Id1", 1, None), ["Seg_Id1"]),
    (lambda t: t.slice(1), ["rows"]),
], ids=["one_seg_id0", "seg_id0_prefix", "seg_id1_on_a_root",
        "seg_id1_missing", "a_row_lost"])
def test_check_table_fails_on_an_altered_table(decoded, spoil, names):
    _, facts, table = decoded
    wrong = exp2.check_table(spoil(table), facts)
    assert len(wrong) == len(names)
    assert all(w.startswith(n) for w, n in zip(wrong, names))


def with_string(table, group, field, row, value):
    """`table` with one string of a redefine replaced; `row` counts the
    rows on which that redefine is visible, from the end where negative."""
    root = table.column("COMPANY_DETAILS").combine_chunks()
    inner = pc.struct_field(root, [group])
    values = pc.struct_field(inner, [field]).to_pylist()
    visible = [i for i, v in enumerate(values) if v is not None]
    values[visible[row]] = value
    children = {f.name: pc.struct_field(inner, [f.name]) for f in inner.type}
    children[field] = pa.array(values, pa.string())
    inner = pa.StructArray.from_arrays(
        list(children.values()), fields=list(inner.type),
        mask=pc.is_null(inner))
    fields = {f.name: pc.struct_field(root, [f.name]) for f in root.type}
    fields[group] = inner
    root = pa.StructArray.from_arrays(list(fields.values()),
                                      fields=list(root.type))
    return replace_column(table, "COMPANY_DETAILS", root)


@pytest.mark.parametrize("group,field,row,value", [
    ("STATIC_DETAILS", "COMPANY_NAME", -1, "Test Bnak"),
    ("STATIC_DETAILS", "ADDRESS", -2, "500 Main Street"),
    ("CONTACTS", "PHONE_NUMBER", -1, "+(1) 100 10 10"),
    ("CONTACTS", "CONTACT_PERSON", 300, "Jene Mork "),
    ("CONTACTS", "CONTACT_PERSON", -1, None),
], ids=["company_name", "address", "phone_number", "contact_person",
        "contact_person_null"])
def test_check_table_fails_on_one_wrong_string_far_from_the_head(
        decoded, group, field, row, value):
    """Past the oracle's prefix sample, in the second chunk: only the
    generator's own account of what it drew holds these rows."""
    _, facts, table = decoded
    wrong = exp2.check_table(with_string(table, group, field, row, value),
                             facts)
    assert len(wrong) == 1
    assert wrong[0].startswith(field) and "1 rows differ" in wrong[0]


def test_check_table_fails_where_two_rows_swapped_their_strings(decoded):
    """What a sum or a count of values would let through."""
    _, facts, table = decoded
    root = table.column("COMPANY_DETAILS").combine_chunks()
    persons = pc.struct_field(root, ["CONTACTS", "CONTACT_PERSON"])
    values = persons.to_pylist()
    visible = [i for i, v in enumerate(values) if v is not None]
    a = visible[5]
    b = next(i for i in visible[6:] if values[i] != values[a])
    swapped = with_string(with_string(
        table, "CONTACTS", "CONTACT_PERSON", visible.index(a), values[b]),
        "CONTACTS", "CONTACT_PERSON", visible.index(b), values[a])
    wrong = exp2.check_table(swapped, facts)
    assert len(wrong) == 1 and "2 rows differ" in wrong[0]


@pytest.mark.parametrize("fact,what", [
    ("c_records", "segment counts"), ("taxpayer_num_sum", "sum(TAXPAYER_NUM"),
    ("company_id_sum", "sum(COMPANY_ID")])
def test_check_table_fails_on_an_altered_count_or_sum(decoded, fact, what):
    _, facts, table = decoded
    wrong = exp2.check_table(table, dict(facts, **{fact: facts[fact] + 1}))
    assert len(wrong) == 1 and wrong[0].startswith(what)


def test_check_table_fails_where_a_contact_moved_to_the_next_company(decoded):
    _, facts, table = decoded
    contacts = facts["contacts"].copy()
    first = int(np.flatnonzero(contacts[:-1] > 0)[0])
    contacts[first] -= 1
    contacts[first + 1] += 1
    wrong = exp2.check_table(table, dict(facts, contacts=contacts))
    assert any(w.startswith("Seg_Id0") for w in wrong)
    assert any("STATIC_DETAILS" in w for w in wrong)


def test_taxpayer_num_is_null_where_the_type_is_a(decoded):
    _, facts, table = decoded
    taxpayer = pc.struct_field(table.column("COMPANY_DETAILS"),
                               ["STATIC_DETAILS", "TAXPAYER"])
    kinds = pc.struct_field(taxpayer, "TAXPAYER_TYPE").to_pylist()
    nums = pc.struct_field(taxpayer, "TAXPAYER_NUM").to_pylist()
    assert {"A", "N", None} == set(kinds)
    assert all((n is None) == (k != "N") for k, n in zip(kinds, nums))


@pytest.mark.parametrize("size", [10, 64, 5000])
def test_sample_is_a_prefix_of_whole_companies(decoded, tmp_path, size):
    from cobrix_tpu import read_cobol

    path, facts, table = decoded
    out = tmp_path / "sample.dat"
    idx = exp2.sample(path, str(out), size, SEED)
    assert np.array_equal(idx, np.arange(len(idx)))
    assert min(size // 2, facts["records"]) - 4 <= len(idx) <= size
    with open(path, "rb") as f:
        whole = f.read()
    prefix = out.read_bytes()
    assert whole.startswith(prefix) and len(walk(prefix)) == len(idx)
    # it ends where a company ends: a 'C' follows, or nothing
    assert len(prefix) == len(whole) or whole[len(prefix) + 4] == 0xC3
    oracle = read_cobol(str(out), **dict(options(),
                                         backend="host")).to_arrow()
    assert table.take(pa.array(idx)).equals(oracle)


def test_a_scattered_sample_would_not_reproduce_the_seg_ids(decoded,
                                                           tmp_path):
    """Why `sample` is a prefix: the same records in a file of their own
    get other Seg_Ids from the oracle."""
    from cobrix_tpu import read_cobol

    path, _, table = decoded
    with open(path, "rb") as f:
        whole = f.read()
    start = 0
    for _ in range(5):  # skip the first companies
        start = whole.index(b"\x00\x00\x40\x00\xc3", start + 1)
    end = whole.index(b"\x00\x00\x40\x00\xc3", start + 1)
    out = tmp_path / "scattered.dat"
    out.write_bytes(whole[start:end])  # one whole company
    oracle = read_cobol(str(out), **dict(options(),
                                         backend="host")).to_arrow()
    rows = len(walk(whole[:start]))
    picked = table.slice(rows, oracle.num_rows)
    assert picked.column("COMPANY_DETAILS").equals(
        oracle.column("COMPANY_DETAILS"))
    assert not picked.column("Seg_Id0").equals(oracle.column("Seg_Id0"))
