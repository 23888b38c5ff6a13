"""Traffic driver `inprocess_query`: one caller in this process, closed
loop; a request is one round of the configuration's `queries`, each
`dataset(file, ...).aggregate(aggs, filter=, group_by=)` over the whole
file, back to back in the order the configuration gives them.

A round's `bytes` is the file's once per query (each scans it), its
`rows` the groups returned, its `table_nbytes` the results' bytes, its
`device` the queries' DeviceStats records added up. No round starts after
the window's seconds; the one in flight finishes and counts. The traced
part of a traced window is one round.

The check holds every kept round to three witnesses, digit for digit:
the generator's own answers from the integers it drew, the generator's
plain pyarrow.compute reference over the host kernels' whole table, and,
on the oracle's sample of records, the scalar oracle's decode.
"""
import os

from ..harness import BenchFault, device_proof, now

# a result that is not a table: one decimal128 a value
VALUE_BYTES = 16


def add_records(total, part):
    """Two DeviceStats.as_dict() records as one: counts and seconds add,
    lists join, `has_kernel` holds if it held in both, `interpreted` if
    in either."""
    if total is None:
        return part
    out = dict(total)
    for key, value in part.items():
        held = out.get(key)
        if held is None:
            out[key] = value
        elif key == "has_kernel":
            out[key] = bool(held and value)
        elif isinstance(value, bool):
            out[key] = bool(held or value)
        elif isinstance(value, dict):
            out[key] = {k: held.get(k, 0) + value.get(k, 0)
                        for k in sorted(set(held) | set(value))}
        elif isinstance(value, list):
            out[key] = sorted(set(held) | set(value))
        else:
            out[key] = held + value
    return out


def plain(result):
    """A result as plain Python: a dict as it is, a table as its rows."""
    return result if isinstance(result, dict) else result.to_pylist()


class Driver:
    def __init__(self, run):
        self.run = run
        if run.traffic["callers"] != 1 or len(run.files) != 1:
            raise BenchFault("inprocess_query drives one caller, one file")
        self.queries = run.config["queries"]
        self.dataset = None
        self.kept = []        # each kept round's {query name: result}

    def open(self, path: str, options: dict):
        from cobrix_tpu import query

        return query.dataset(path, **options)

    def set_up(self) -> None:
        self.dataset = self.open(self.run.files[0]["path"],
                                 self.run.reader_options())

    def ask(self, dataset, name: str):
        q = self.queries[name]
        return dataset.aggregate(q["aggs"], filter=q.get("filter"),
                                 group_by=q.get("group_by"))

    def round(self, keep: bool) -> dict:
        run = self.run
        file = run.files[0]
        request = {"file": 0, "file_bytes": file["bytes"],
                   "bytes": file["bytes"] * len(self.queries),
                   "sent": now(), "query_s": {}}
        results, device, wrong = {}, None, ""
        try:
            for name in self.queries:
                t0 = now()
                with run.tracer.span(f"bench.query.{name}"):
                    results[name] = self.ask(self.dataset, name)
                    request["query_s"][name] = now() - t0
                metrics = self.dataset.metrics
                stats = (metrics.as_dict().get("device")
                         if metrics is not None else None)
                wrong = wrong or device_proof(
                    stats, run.device["platform"], run.device["first"])
                device = add_records(device, stats or {})
        except Exception as exc:  # a failed round is counted, not fatal
            request.update(done=now(), ok=False, error=repr(exc),
                           device=device)
            return request
        done = now()
        with run.tracer.span("bench.between_rounds"):
            request.update(
                first=done, done=done, device=device, ok=not wrong,
                error=wrong or None,
                rows=sum(1 if isinstance(r, dict) else r.num_rows
                         for r in results.values()),
                table_nbytes=sum(
                    VALUE_BYTES * len(r) if isinstance(r, dict)
                    else r.nbytes for r in results.values()))
            if keep:
                self.kept.append(results)
        return request

    def warm_up(self) -> list:
        """One round: every shape the window's rounds will launch."""
        return [self.round(keep=False)]

    def window(self, seconds: float) -> dict:
        requests = []
        start = now()
        self.run.tracer.start()
        while now() - start < seconds:
            requests.append(self.round(keep=True))
            # one whole round is traced, not the window
            self.run.tracer.stop()
        return {"start": start, "requests": requests}

    def check(self, check_files) -> list:
        import pyarrow as pa

        run = self.run
        file = run.files[0]
        outcome = check_files(run, {}, write_references=True)
        failures = list(outcome["failures"])
        with pa.memory_map(outcome["references"][0]) as source:
            table = pa.ipc.open_file(source).read_all()
            witnesses = {
                "the generator's own answers":
                    run.generator.query_answers(file["facts"]),
                "pyarrow.compute over the host kernels' table":
                    run.generator.reference_answers(table)}
        os.unlink(outcome["references"][0])
        for what, expected in witnesses.items():
            for i, results in enumerate(self.kept):
                for name in self.queries:
                    if plain(results[name]) != expected[name]:
                        failures.append(
                            f"round {i}: {name} differs from {what}: "
                            f"{plain(results[name])!r} != {expected[name]!r}")
                        break
        failures += self.oracle_failures()
        chunks = [(r.get("device") or {}) for r in
                  run.record["window"]["requests"]]
        fallback = sum(d.get("query_fallback_chunks", 0) for d in chunks)
        if fallback:
            failures.append(f"{fallback} chunk(s) fell back to the host")
        return failures

    def oracle_failures(self) -> list:
        """The device path's answers over the oracle's sample of records
        against the scalar oracle's (`reference_options.sample`)."""
        run = self.run
        file = run.files[0]
        sample_path = file["path"] + ".query_sample"
        run.generator.sample(
            file["path"], sample_path,
            run.config[run.scale]["oracle_sample_records"], run.seed)
        device = self.open(sample_path, run.reader_options())
        oracle = self.open(sample_path, run.reference_options("sample"))
        wrong = []
        for name in self.queries:
            got, want = self.ask(device, name), self.ask(oracle, name)
            if device.metrics is None:
                wrong.append(f"{name} on the sample did not run on the "
                             f"device")
            if plain(got) != plain(want) or type(got) is not type(want):
                wrong.append(f"{name} on the sample differs from the "
                             f"scalar oracle: {plain(got)!r} != "
                             f"{plain(want)!r}")
        os.unlink(sample_path)
        return wrong

    def close(self) -> None:
        self.kept.clear()
        self.dataset = None
