"""The stage counters and the device scopes on exp1's plan (195 fields of
every numeric codec): a small read through the interpreted kernel, and the
lowered program's text. A file of its own because the program takes about
a minute to compile in interpret mode when the compile cache is cold."""
import time

import pytest

from cobrix_tpu import read_cobol
from cobrix_tpu.plan.compiler import Codec
from cobrix_tpu.reader import columnar
from cobrix_tpu.testing.generators import EXP1_COPYBOOK, generate_exp1

from util import check_stage_record

EXP1_STAGES = {
    "parse_copybook", "scan", "read", "frame", "decode", "pack", "h2d",
    "launch", "d2h_wait", "merge", "collect", "to_arrow", "assemble.table",
    "assemble.scalar", "assemble.decimal", "assemble.string"}


@pytest.fixture(scope="module")
def exp1_read(tmp_path_factory):
    """(CobolData, the wall of read_cobol() plus .to_arrow()) of 3,000
    records in blocks of 512 rows: six launches, the last one ragged."""
    path = tmp_path_factory.mktemp("exp1") / "exp1.bin"
    path.write_bytes(bytes(generate_exp1(3000, seed=24)))
    patch = pytest.MonkeyPatch()
    patch.setattr(columnar, "DEVICE_BLOCK_BYTES", 1 << 20)
    try:
        t0 = time.perf_counter()
        data = read_cobol(str(path), backend="pallas",
                          copybook_contents=EXP1_COPYBOOK)
        table = data.to_arrow()
        wall_s = time.perf_counter() - t0
    finally:
        patch.undo()
    assert table.num_rows == 3000
    return data, wall_s


def test_exp1_read_counts_every_stage(exp1_read):
    data, wall_s = exp1_read
    device = data.metrics.as_dict()["device"]
    assert device["launches"] == {"512x1493": 6}
    check_stage_record(device, wall_s, EXP1_STAGES)
    assert device["stage_n"]["pack"] == 1      # the ragged last block
    assert device["stage_n"]["merge"] == 1
    if device["compiles"]:
        # the program was built in this read: lowering is part of it
        assert 0.0 < device["lower_s"] < device["compile_s"]
        assert device["stage_s"]["compile"] >= device["compile_s"] - 0.01


def test_lowered_exp1_program_names_every_step(exp1_read):
    import jax
    import numpy as np

    data, _ = exp1_read
    decoder = data._results[0].segments[0].batch.decoder
    text = decoder.device_program()._jit.lower(jax.ShapeDtypeStruct(
        (512, decoder.plan.max_extent), np.uint8)).as_text(debug_info=True)
    for scope in ("cobrix.planes", "cobrix.kernel", "cobrix.outputs"):
        assert scope in text, scope
    gathered = [g for g in decoder.kernel_groups
                if g.codec is not Codec.HOST_FALLBACK
                and columnar._pallas_group_spec(g) is None]
    assert gathered          # exp1 has groups the fused kernel leaves out
    # an EBCDIC string group whose columns lie side by side has no
    # operation of its own: its bytes are a slice of the one lookup's
    # input, and its code points leave in the lookup's matrix
    points = decoder.device_program().points
    own_blocks = {gi for gi, _ in points.blocks}
    assert "cobrix.lookup.ebcdic" in text
    for g in gathered:
        if g.codec is Codec.EBCDIC_STRING \
                and decoder.kernel_groups.index(g) not in own_blocks:
            continue
        scope = "cobrix.group." + g.label.replace("/", "_")
        assert "/" not in scope and scope in text, scope
