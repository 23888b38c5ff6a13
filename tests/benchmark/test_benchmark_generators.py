"""The frozen generators write today what the program's own write: the
yardstick was copied, not changed."""
import pytest

from benchmark_testing import REPO  # noqa: F401  (puts the repo on sys.path)

from benchmark.generators import exp1_type_variety, exp3_companies_wide
from cobrix_tpu.testing import generators as original

SEED = 2147483999  # past 2**31, as the driver's seeds are


def test_exp3_bytes_equal_the_program_generator():
    data, facts = exp3_companies_wide.generate(120, SEED)
    assert data == original.generate_exp3(120, seed=SEED)
    assert exp3_companies_wide.COPYBOOK == original.EXP3_COPYBOOK
    assert facts["records"] == 120 == facts["c_records"] + facts["p_records"]
    assert facts["bytes"] == len(data) == (
        facts["c_bytes"] + facts["p_bytes"] + 4 * 120)


def test_exp1_bytes_equal_the_program_generator():
    data, facts = exp1_type_variety.generate(64, SEED)
    assert data == original.generate_exp1(64, seed=SEED).tobytes()
    assert exp1_type_variety.COPYBOOK == original.EXP1_COPYBOOK
    assert exp1_type_variety.RECORD_SIZE == original.EXP1_RECORD_SIZE
    assert facts["bytes"] == 64 * exp1_type_variety.RECORD_SIZE


def test_a_read_chunk_of_exp1_has_no_ragged_tail():
    from cobrix_tpu.api import FIXED_READ_CHUNK_BYTES

    n = exp1_type_variety.records_for(FIXED_READ_CHUNK_BYTES)
    assert n == FIXED_READ_CHUNK_BYTES // exp1_type_variety.RECORD_SIZE


@pytest.mark.parametrize("module,records", [(exp3_companies_wide, 40),
                                            (exp1_type_variety, 30)])
def test_generator_facts_hold_on_the_host_decode(tmp_path, module, records):
    """check_table() passes on a right table and names what is wrong on
    one that lost a row."""
    from cobrix_tpu import read_cobol

    parts = [module.generate(records, SEED + k) for k in range(2)]
    path = tmp_path / "input.dat"
    path.write_bytes(b"".join(data for data, _ in parts))
    facts = module.merge_facts([f for _, f in parts])
    options = {"copybook_contents": module.COPYBOOK}
    if module is exp3_companies_wide:
        options.update(is_record_sequence="true",
                       segment_field="SEGMENT-ID",
                       redefine_segment_id_map="STATIC-DETAILS => C",
                       redefine_segment_id_map_1="CONTACTS => P")
    table = read_cobol(str(path), backend="numpy", **options).to_arrow()
    assert module.check_table(table, facts) == []
    assert module.check_table(table.slice(1), facts)
    sample = tmp_path / "sample.dat"
    idx = module.sample(str(path), str(sample), 7, SEED)
    assert len(idx) == 7
    picked = read_cobol(str(sample), backend="numpy", **options).to_arrow()
    import pyarrow as pa

    assert table.take(pa.array(idx)).equals(picked)
