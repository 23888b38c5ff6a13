"""Traffic driver `inprocess_scan_proved`: `inprocess_scan` to the letter,
with one thing before it. `set_up()` writes the generator's sample of the
traffic file's `proof_records` records, reads it once with the cell's
reader options, and stops the run unless that read launched on the device
as the platform promises (`harness.device_proof`).

Why: a program that decodes the cell's records on the host (a
`variable_size_occurs` read before the plan had regions walked every
record, at under 1 MB/s, on any backend) would spend `inprocess_scan`'s
warm-up, one whole scan of the file, before the first scan failed on "no
device launch": minutes in which the run looks hung. With the proof it
exits non-zero within its set-up.
"""
import os

from ..harness import BenchFault, device_proof
from .inprocess_scan import Driver as ScanDriver


class Driver(ScanDriver):
    def set_up(self) -> None:
        from cobrix_tpu import read_cobol

        run = self.run
        path = os.path.join(run.workdir, "device_proof.dat")
        idx = run.generator.sample(run.files[0]["path"], path,
                                   run.traffic["proof_records"], run.seed)
        try:
            data = read_cobol(path, **run.reader_options())
            table = data.to_arrow()
        finally:
            os.unlink(path)
        wrong = device_proof(data.metrics.as_dict().get("device"),
                             run.device["platform"], run.device["first"])
        if not wrong and table.num_rows != len(idx):
            wrong = f"{table.num_rows} rows of {len(idx)} sampled"
        if wrong:
            raise BenchFault(
                f"the cell's reader options do not reach the device on a "
                f"sample of {len(idx)} records: {wrong}")
