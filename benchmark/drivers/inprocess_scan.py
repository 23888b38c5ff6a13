"""Traffic driver `inprocess_scan`: callers in this process, closed loop,
`read_cobol(file, ...).to_arrow()` back to back over the cell's files.

One caller (the traffic file's `callers`) is what a batch job on one host
is. No scan starts after the window's seconds; the one in flight finishes
and counts.
"""
from ..harness import BenchFault, device_proof, now


class Driver:
    def __init__(self, run):
        self.run = run
        if run.traffic["callers"] != 1:
            raise BenchFault("inprocess_scan drives one caller")
        self.tables = {}

    def set_up(self) -> None:
        pass

    def scan(self, index: int, keep: bool) -> dict:
        from cobrix_tpu import read_cobol

        run = self.run
        file = run.files[index]
        tracer = run.tracer
        request = {"file": index, "bytes": file["bytes"], "sent": now()}
        try:
            with tracer.span("bench.read_cobol"):
                data = read_cobol(file["path"], **run.reader_options())
                called = now()
            with tracer.span("bench.to_arrow"):
                table = data.to_arrow()
                done = now()
        except Exception as exc:  # a failed scan is counted, not fatal
            request.update(done=now(), ok=False, error=repr(exc))
            return request
        with tracer.span("bench.between_scans"):
            stats = data.metrics.as_dict().get("device")
            wrong = device_proof(stats, run.device["platform"],
                                 run.device["first"])
            if not wrong and table.num_rows != file["facts"]["records"]:
                wrong = (f"{table.num_rows} rows, "
                         f"{file['facts']['records']} written")
            request.update(
                first=done, done=done, read_cobol_s=called - request["sent"],
                to_arrow_s=done - called, rows=table.num_rows,
                table_nbytes=table.nbytes, device=stats, ok=not wrong,
                error=wrong or None)
            if keep:
                self.tables[index] = table
        return request

    def warm_up(self) -> list:
        """Each file once, whole: whatever shapes the window will launch."""
        return [self.scan(i, keep=False) for i in range(len(self.run.files))]

    def window(self, seconds: float) -> dict:
        run = self.run
        requests = []
        start = now()
        run.tracer.start()
        while now() - start < seconds:
            requests.append(self.scan(len(requests) % len(run.files),
                                      keep=True))
            # one whole scan is traced, not the window
            run.tracer.stop()
        return {"start": start, "requests": requests}

    def check(self, check_files) -> list:
        return check_files(self.run, self.tables,
                           write_references=False)["failures"]

    def close(self) -> None:
        self.tables.clear()
