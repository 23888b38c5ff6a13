"""One closed-loop caller of a served cell, in a process of its own.

Started by served_closed_loop.py with JAX_PLATFORMS=cpu; reads one JSON
command per line on standard input and answers each with one JSON line.
It imports the serve client only (which loads no JAX) and never touches
the chip. Request times are CLOCK_MONOTONIC seconds, shared with the
process that holds the server.
"""
import json
import random
import sys
import time


def request(state: dict, index: int) -> dict:
    """One request: sent, first batch in hand, last batch and the stream
    closed. The table is kept for the check after the window."""
    import pyarrow as pa

    from cobrix_tpu.serve import stream_scan

    file = state["files"][index]
    record = {"file": index, "bytes": file["bytes"], "sent": time.monotonic()}
    try:
        batches = []
        with stream_scan(tuple(state["address"]), file["path"],
                         tenant=f"bench-client-{state['number']}",
                         read_timeout_s=state["request_timeout_s"],
                         **state["options"]) as stream:
            for batch in stream:
                if not batches:
                    record["first"] = time.monotonic()
                batches.append(batch)
            summary = stream.summary
            schema = stream.schema
        record["done"] = time.monotonic()
        # as ScanStream.table() assembles it, diagnostics metadata included
        table = pa.Table.from_batches(batches, schema=schema)
        if summary.get("diagnostics"):
            metadata = dict(table.schema.metadata or {})
            metadata[b"cobrix_tpu.read_diagnostics"] = \
                summary["diagnostics"].encode()
            table = table.replace_schema_metadata(metadata)
        record.update(
            rows=table.num_rows, table_nbytes=table.nbytes,
            device=summary["metrics"].get("device"),
            trailer={key: summary.get(key) for key in
                     ("scan_s", "queue_wait_s", "first_batch_s", "bytes",
                      "rows")})
        if table.num_rows != file["rows"]:
            raise RuntimeError(f"{table.num_rows} rows, "
                               f"{file['rows']} written")
        state["tables"][index] = table
        record.update(ok=True, error=None)
    except Exception as exc:  # a failed request is counted, not fatal
        record.setdefault("done", time.monotonic())
        record.update(ok=False, error=repr(exc))
    return record


def check(state: dict, references: dict) -> dict:
    """Hold the last table of each file to the reference the server's
    process left as an Arrow file, schema metadata included."""
    import pyarrow as pa

    failures = []
    for index, table in sorted(state["tables"].items()):
        with pa.memory_map(references[str(index)]) as source:
            reference = pa.ipc.open_file(source).read_all()
            if not table.equals(reference, check_metadata=True):
                failures.append(
                    f"client {state['number']}: served table of file "
                    f"{index} differs from the in-process one")
    return {"failures": failures, "compared": len(state["tables"])}


def main() -> int:
    import pyarrow  # noqa: F401  (loaded on the main thread, once)

    state = {"tables": {}}
    for line in sys.stdin:
        message = json.loads(line)
        cmd = message["cmd"]
        if cmd == "init":
            state.update(message)
            # every client its own seeded shuffle of the same files
            order = list(range(len(state["files"])))
            random.Random(state["seed"] * 1000 + state["number"]).shuffle(
                order)
            state["order"] = order
            reply = {"ready": True, "jax_loaded": "jax" in sys.modules}
        elif cmd == "warm":
            reply = {"requests": [request(state, i)
                                  for i in message["files"]]}
            state["tables"].clear()
        elif cmd == "window":
            time.sleep(max(0.0, message["start"] - time.monotonic()))
            done = []
            while time.monotonic() < message["end"]:
                done.append(request(
                    state, state["order"][len(done) % len(state["order"])]))
            reply = {"requests": done}
        elif cmd == "check":
            reply = check(state, message["references"])
        elif cmd == "exit":
            break
        else:
            reply = {"error": f"unknown command {cmd!r}"}
        reply["jax_loaded"] = "jax" in sys.modules
        print(json.dumps(reply), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
