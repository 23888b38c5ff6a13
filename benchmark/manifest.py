"""BENCHMARK.json and the files it names: loading, and the driver's rules
of spelling and shape as far as they can be checked without a run.

`problems()` is what tests/benchmark/test_benchmark_manifest.py runs, and
`run.py --validate` prints: PR 22's whole benchmark was refused before any
run because a `layer` was written as prose.
"""
import json
import os
import re

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HERE = os.path.dirname(os.path.abspath(__file__))

NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}\Z")
UNIT = re.compile(r"[A-Za-z0-9_/%.\-]{1,16}\Z")
PATH = re.compile(r"[A-Za-z0-9_.\-/]{1,200}\Z")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
DATA_SUFFIXES = (".json", ".jsonl", ".toml", ".txt", ".csv")
TOP_KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}
CONFIG_KEYS = {"name", "source", "file", "reduced", "why"}
CELL_KEYS = {"name", "config", "traffic", "chips", "why"}
END_TO_END_KEYS = {"name", "unit", "better", "bound", "source"}
PER_LAYER_KEYS = {"name", "unit", "better", "source", "layer", "moves"}


def load(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def load_json(*parts: str) -> dict:
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def find(entries: list, name: str, what: str) -> dict:
    for entry in entries:
        if entry["name"] == name:
            return entry
    raise SystemExit(f"benchmark: no {what} named {name!r} in BENCHMARK.json")


def cells_of(metric: dict, manifest: dict) -> list:
    """The names of the cells in which `metric` is reported."""
    return metric.get("workloads",
                      [cell["name"] for cell in manifest["workloads"]])


def metrics_of(cell_name: str, manifest: dict, kind: str) -> list:
    return [m for m in manifest[kind] if cell_name in cells_of(m, manifest)]


def _line(text, limit=200) -> bool:
    return (isinstance(text, str) and 1 <= len(text) <= limit
            and "\n" not in text and "\t" not in text)


def problems(manifest: dict, root: str = ROOT) -> list:
    """Everything in `manifest` that the driver would refuse before a run,
    as far as this file knows the rules. Empty when it would be taken."""
    bad = []

    def need(ok, message):
        if not ok:
            bad.append(message)

    need(set(manifest) == TOP_KEYS, f"top-level keys {sorted(manifest)}")
    paths = manifest.get("paths", [])
    need(1 <= len(paths) <= 16 and all(
        PATH.match(p) and not p.startswith("/") and ".." not in p.split("/")
        for p in paths), f"paths {paths}")
    command = manifest.get("command", [])
    need(1 <= len(command) <= 32 and all(_line(w) for w in command),
         "command")
    for word in command:
        if "/" in word or os.path.exists(os.path.join(root, word)):
            need(any(word == p or word.startswith(p + "/") for p in paths)
                 and not word.startswith("/") and ".." not in word.split("/"),
                 f"command names {word!r} outside paths")
    seconds = manifest.get("run_seconds")
    need(isinstance(seconds, int) and not isinstance(seconds, bool)
         and 1 <= seconds <= 51, f"run_seconds {seconds!r}")

    def under_paths(file):
        return any(file.startswith(p + "/") for p in paths)

    configs = manifest.get("configs", [])
    cells = manifest.get("workloads", [])
    need(1 <= len(configs) <= 24, "1 to 24 configs")
    need(1 <= len(cells) <= 24, "1 to 24 workloads")
    files = set()
    for c in configs:
        need(set(c) == CONFIG_KEYS, f"config keys {sorted(c)}")
        need(NAME.match(c.get("name", "")), f"config name {c.get('name')!r}")
        need(_line(c.get("source")), f"config {c.get('name')}: source")
        need(_line(c.get("why")), f"config {c.get('name')}: why")
        file = c.get("file", "")
        need(PATH.match(file) and under_paths(file) and file not in files
             and os.path.isfile(os.path.join(root, file)),
             f"config {c.get('name')}: file {file!r}")
        files.add(file)
        reduced = c.get("reduced", [])
        need(len(reduced) <= 16 and all(NAME.match(k) for k in reduced),
             f"config {c.get('name')}: reduced {reduced}")
        need(any(cell.get("config") == c.get("name") for cell in cells),
             f"config {c.get('name')} is used by no cell")
    pairs = set()
    for cell in cells:
        name = cell.get("name")
        need(set(cell) == CELL_KEYS, f"cell keys {sorted(cell)}")
        for key in ("name", "config", "traffic"):
            need(NAME.match(str(cell.get(key, ""))), f"cell {name}: {key}")
        need(cell.get("chips") in (1, 4), f"cell {name}: chips")
        need(_line(cell.get("why")), f"cell {name}: why")
        need(any(c.get("name") == cell.get("config") for c in configs),
             f"cell {name}: no config {cell.get('config')!r}")
        pair = (cell.get("config"), cell.get("traffic"))
        need(pair not in pairs, f"cell {name}: pair {pair} twice")
        pairs.add(pair)
        traffic = os.path.join(root, os.path.relpath(HERE, ROOT), "traffic")
        found = [f for f in (os.listdir(traffic)
                             if os.path.isdir(traffic) else [])
                 if os.path.splitext(f)[0] == cell.get("traffic")
                 and f.endswith(DATA_SUFFIXES)]
        need(len(found) == 1, f"cell {name}: traffic file {found}")
    four = sum(1 for cell in cells if cell.get("chips") == 4)
    need(four <= max(1, len(cells) // 2), f"{four} cells ask for 4 chips")

    end_to_end = manifest.get("end_to_end", [])
    per_layer = manifest.get("per_layer", [])
    need(1 <= len(end_to_end) <= 16, "1 to 16 end_to_end metrics")
    need(1 <= len(per_layer) <= 128, "1 to 128 per_layer metrics")
    cell_names = [cell.get("name") for cell in cells]
    names = [x.get("name") for x in end_to_end + per_layer]
    for group in (names, cell_names, [c.get("name") for c in configs]):
        need(len(set(group)) == len(group), f"a name twice in {group}")
    for m in end_to_end + per_layer:
        name = m.get("name")
        is_end = m in end_to_end
        keys = set(m) - {"workloads"}
        need(keys == (END_TO_END_KEYS if is_end else PER_LAYER_KEYS),
             f"metric {name}: keys {sorted(m)}")
        need(NAME.match(str(name)), f"metric name {name!r}")
        need(UNIT.match(str(m.get("unit", ""))), f"metric {name}: unit")
        need(m.get("better") in ("lower", "higher"), f"metric {name}: better")
        allowed = {"host_clock", "device_trace"} if is_end else SOURCES
        need(m.get("source") in allowed, f"metric {name}: source")
        need(set(cells_of(m, manifest)) <= set(cell_names)
             and cells_of(m, manifest), f"metric {name}: workloads")
        if is_end:
            bound = m.get("bound")
            need(isinstance(bound, (int, float)) and 0.01 <= bound <= 0.25,
                 f"metric {name}: bound {bound!r}")
        else:
            # PR 22 was refused here: a layer is an identifier, not prose
            need(NAME.match(str(m.get("layer", ""))),
                 f"metric {name}: layer {m.get('layer')!r}")
            moved = [e for e in end_to_end if e.get("name") == m.get("moves")]
            need(len(moved) == 1, f"metric {name}: moves {m.get('moves')!r}")
            if moved:
                need(set(cells_of(m, manifest))
                     <= set(cells_of(moved[0], manifest)),
                     f"metric {name}: {m.get('moves')} is not reported in "
                     f"every cell where it is")
    need("setup_s" in [e.get("name") for e in end_to_end], "no setup_s")
    for cell in cell_names:
        ends = [m["name"] for m in metrics_of(cell, manifest, "end_to_end")]
        need("setup_s" in ends and len(ends) >= 2,
             f"cell {cell}: end_to_end {ends}")
        need(metrics_of(cell, manifest, "per_layer"),
             f"cell {cell}: no per_layer metric")
    try:
        size = os.path.getsize(os.path.join(root, "BENCHMARK.json"))
        need(size <= 64 * 1024, f"BENCHMARK.json is {size} B")
    except OSError:
        pass
    for path in paths:
        for folder, _, entries in os.walk(os.path.join(root, path)):
            if "__pycache__" in folder:
                continue
            for entry in entries:
                rel = os.path.relpath(os.path.join(folder, entry), root)
                need(PATH.match(rel) or entry.endswith(".pyc"),
                     f"file name {rel!r}")
    return bad
