"""Layer executor: what no stage of the program covers: the benchmark's
seconds round `read_cobol()` and `.to_arrow()` less the sum of the
program's stage seconds, per 10^9 input bytes, the median over the
window's scans. In-process cells only: a served request has no such
pair of spans."""
from .stage_s import per_scan
from ..harness import median


def read(record: dict):
    values = per_scan(
        record, lambda r, stage_s: (r["read_cobol_s"] + r["to_arrow_s"]
                                    - sum(stage_s.values())))
    return median(values) if values else None
