"""The comparison that decides `correct`, made after the window.

For each distinct input file:
  (a) the table the device path gave equals, whole, the host kernels'
      (`reference_options.whole` of the configuration);
  (b) a seeded sample of whole records equals the scalar oracle's decode
      (`reference_options.sample`), bit for bit, nulls included;
  (c) what the frozen generator knows without the program holds: records
      and segments as written, the values it drew;
  (d) where the tables are in other processes (a served cell's clients),
      the host kernels' table is left as an Arrow file and each client
      holds its tables to it, schema metadata included — (b) and (c) are
      then made on that reference, which (d) ties to the served tables.
"""
import os

from .harness import now, say


def reference_table(run, file: dict):
    from cobrix_tpu import read_cobol

    return read_cobol(file["path"],
                      **run.reference_options("whole")).to_arrow()


def oracle_failures(run, file: dict, table, records: int) -> list:
    import pyarrow as pa

    from cobrix_tpu import read_cobol

    sample_path = file["path"] + ".sample"
    idx = run.generator.sample(file["path"], sample_path, records, run.seed)
    oracle = read_cobol(sample_path,
                        **run.reference_options("sample")).to_arrow()
    os.unlink(sample_path)
    if not table.take(pa.array(idx)).equals(oracle):
        return [f"{os.path.basename(file['path'])}: differs from the "
                f"scalar oracle on a sample of {len(idx)} records"]
    return []


def check_files(run, device_tables: dict, write_references: bool) -> dict:
    """`device_tables`: {file index: the last table the device path gave},
    or empty where other processes hold them. Returns {"failures": [...],
    "references": {file index: path of the Arrow file}}."""
    import pyarrow as pa

    per_file = max(1, run.config[run.scale]["oracle_sample_records"]
                   // len(run.files))
    failures = []
    references = {}
    t0 = now()
    for i, file in enumerate(run.files):
        base = os.path.basename(file["path"])
        reference = reference_table(run, file)
        subject = device_tables.get(i)
        if subject is None:
            subject = reference
        elif not subject.equals(reference, check_metadata=True):
            failures.append(f"{base}: the device path's table differs "
                            f"from the host kernels'")
        failures += oracle_failures(run, file, subject, per_file)
        failures += [f"{base}: {what}" for what in
                     run.generator.check_table(subject, file["facts"])]
        if write_references:
            path = file["path"] + ".reference.arrow"
            with pa.OSFile(path, "wb") as sink, \
                    pa.ipc.new_file(sink, reference.schema) as writer:
                writer.write_table(reference)
            references[i] = path
    say(phase="check", files=len(run.files), oracle_records_per_file=per_file,
        seconds=round(now() - t0, 3), failures=failures)
    return {"failures": failures, "references": references}
