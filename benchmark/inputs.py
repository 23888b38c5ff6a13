"""Input files, made from the seed by the configuration's frozen generator
in a pool of processes, before this process touches JAX.

A traffic mix says into how many files the configuration's bytes are cut;
each file is one or more generated chunks, chunk k of the run drawing from
`seed + k`. The pool's workers import numpy and the generator and nothing
of the program.
"""
import multiprocessing
import os

from .harness import load_named


def _write_chunk(job):
    generator_name, path, records, seed = job
    generator = load_named("generators", generator_name)
    data, facts = generator.generate(records, seed)
    with open(path, "wb") as f:
        f.write(data)
    return facts


def sizes(config: dict, traffic: dict, scale: str):
    """(number of files, chunks per file, bytes per chunk)."""
    n_files = traffic[scale]["files"]
    per_file = config[scale]["file_bytes"] // n_files
    chunk = min(per_file, config[scale]["generate_chunk_bytes"])
    return n_files, -(-per_file // chunk), chunk


def make(config: dict, traffic: dict, workdir: str, seed: int,
         scale: str) -> list:
    """Write the cell's input files under `workdir`; returns
    [{"path", "bytes", "facts"}] in file order."""
    name = config["generator"]
    generator = load_named("generators", name)
    n_files, chunks_per_file, chunk_bytes = sizes(config, traffic, scale)
    records = generator.records_for(chunk_bytes)
    jobs = []
    for f in range(n_files):
        for c in range(chunks_per_file):
            k = f * chunks_per_file + c
            jobs.append((name, os.path.join(workdir, f"input_{f}.part{c}"),
                         records, seed + k))
    workers = min(len(jobs), os.cpu_count() or 1)
    if workers > 1:
        with multiprocessing.get_context("spawn").Pool(workers) as pool:
            facts = pool.map(_write_chunk, jobs, chunksize=1)
    else:
        facts = [_write_chunk(job) for job in jobs]
    files = []
    for f in range(n_files):
        parts = range(f * chunks_per_file, (f + 1) * chunks_per_file)
        path = os.path.join(workdir, f"input_{f}.dat")
        if chunks_per_file == 1:
            os.replace(jobs[parts[0]][1], path)
        else:
            with open(path, "wb") as out:
                for k in parts:
                    with open(jobs[k][1], "rb") as part:
                        while block := part.read(1 << 24):
                            out.write(block)
                    os.unlink(jobs[k][1])
        files.append({"path": path, "bytes": os.path.getsize(path),
                      "facts": generator.merge_facts([facts[k]
                                                      for k in parts])})
    return files
