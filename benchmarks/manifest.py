"""Every rule BENCHMARK.json has to meet, as code.

PR 23's whole benchmark was refused before any run over one layer name
with a space in it.  `problems()` returns every breach of the contract
it can find without a chip; tests/benchmark runs it in tier-1, and
`python3 benchmarks/manifest.py` prints the list before a chip call.
"""

from __future__ import annotations

import json
import os
import re
import sys

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")
TOP_KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}
TRAFFIC_EXT = (".json", ".jsonl", ".toml", ".txt", ".csv")
# a key `reduced` may never name: widths
WIDTH = re.compile(
    r"(_dim|_rank)$|hidden|intermediate|latent|state|proj|head_size|"
    r"head_dim|expansion|experts_per_tok|ffn", re.I)
MAX_BOUND = 0.1
MIN_BOUND = 0.01
# the driver's check: 2 + 14 x cells runs of run_seconds + 60 each, 180 s
# more a cell to compile, 1200 s spare, inside 43200 s with all 24 cells
MAX_CELLS = 24


def _line(text, lo=1, hi=200):
    return isinstance(text, str) and lo <= len(text) <= hi \
        and "\n" not in text and "\t" not in text and "\r" not in text


def _under(path, roots):
    return any(path == r or path.startswith(r.rstrip("/") + "/")
               for r in roots)


def run_seconds_fits(seconds, cells=MAX_CELLS):
    return (2 + 14 * cells) * (seconds + 60) + cells * 180 + 1200 <= 43200


def problems(manifest, root, accepted_four_chip=0):
    """Every breach found, as a list of sentences; empty = valid."""
    bad = []

    def need(ok, msg):
        if not ok:
            bad.append(msg)

    need(set(manifest) == TOP_KEYS,
         f"top-level keys {sorted(manifest)} are not exactly "
         f"{sorted(TOP_KEYS)}")
    if set(manifest) != TOP_KEYS:
        return bad
    need(len(json.dumps(manifest)) <= 64 * 1024, "file over 64 KiB")

    # -- paths, command ------------------------------------------------------
    paths = manifest["paths"]
    need(isinstance(paths, list) and 1 <= len(paths) <= 16,
         "paths: 1 to 16 directories")
    for p in paths:
        need(isinstance(p, str) and PATH.match(p) and not p.startswith("/")
             and ".." not in p.split("/"),
             f"path {p!r}: a relative path of allowed characters")
        need(os.path.isdir(os.path.join(root, p)),
             f"path {p!r} is no directory")
        for dirpath, dirnames, files in os.walk(os.path.join(root, p)):
            dirnames[:] = [d for d in dirnames if d != "__pycache__"]
            for f in files:
                rel = os.path.relpath(os.path.join(dirpath, f), root)
                need(PATH.match(rel) is not None,
                     f"file {rel!r}: name outside the allowed characters")
    command = manifest["command"]
    need(isinstance(command, list) and 1 <= len(command) <= 32
         and all(_line(w) for w in command),
         "command: at most 32 words of 1 to 200 characters")
    for w in command:
        need(not w.startswith("/") and ".." not in w.split("/"),
             f"command word {w!r} leaves the repo")
        if os.path.exists(os.path.join(root, w)):
            need(_under(w, paths),
                 f"command word {w!r} is a file outside paths")

    rs = manifest["run_seconds"]
    need(isinstance(rs, int) and not isinstance(rs, bool) and 1 <= rs <= 51
         and run_seconds_fits(rs),
         f"run_seconds {rs!r}: a whole number from 1 to 51 that fits "
         "the full check with 24 cells")

    # -- configs -------------------------------------------------------------
    configs = manifest["configs"]
    need(1 <= len(configs) <= 24, "configs: 1 to 24")
    seen_files = set()
    for c in configs:
        need(set(c) == {"name", "source", "file", "reduced", "why"},
             f"config {c.get('name')!r}: keys {sorted(c)}")
        need(NAME.match(str(c.get("name", ""))) is not None,
             f"config name {c.get('name')!r}")
        need(_line(c.get("source")), f"config {c.get('name')!r}: source")
        need(_line(c.get("why")), f"config {c.get('name')!r}: why")
        f = c.get("file", "")
        need(_under(f, paths) and os.path.isfile(os.path.join(root, f)),
             f"config file {f!r} is not a file under paths")
        need(f not in seen_files, f"config file {f!r} used twice")
        seen_files.add(f)
        reduced = c.get("reduced", None)
        need(isinstance(reduced, list) and len(reduced) <= 16,
             f"config {c.get('name')!r}: reduced is a list of at most 16")
        for key in reduced or ():
            need(NAME.match(str(key)) is not None and not WIDTH.search(key),
                 f"config {c.get('name')!r}: reduced names {key!r}, a "
                 "width or a bad name")
    config_names = [c.get("name") for c in configs]
    need(len(set(config_names)) == len(config_names),
         "two configurations share a name")

    # -- workloads -----------------------------------------------------------
    cells = manifest["workloads"]
    need(1 <= len(cells) <= 24, "workloads: 1 to 24")
    pairs = set()
    for w in cells:
        need(set(w) == {"name", "config", "traffic", "chips", "why"},
             f"workload {w.get('name')!r}: keys {sorted(w)}")
        for key in ("name", "config", "traffic"):
            need(NAME.match(str(w.get(key, ""))) is not None,
                 f"workload {w.get('name')!r}: {key} {w.get(key)!r}")
        need(w.get("chips") in (1, 4),
             f"workload {w.get('name')!r}: chips {w.get('chips')!r}")
        need(_line(w.get("why")), f"workload {w.get('name')!r}: why of 1 "
             "to 200 characters on one line")
        need(w.get("config") in config_names,
             f"workload {w.get('name')!r} names no configuration")
        pair = (w.get("config"), w.get("traffic"))
        need(pair not in pairs, f"pair {pair} appears twice")
        pairs.add(pair)
    cell_names = [w.get("name") for w in cells]
    need(len(set(cell_names)) == len(cell_names), "two cells share a name")
    for c in config_names:
        need(any(w.get("config") == c for w in cells),
             f"configuration {c!r} is used by no cell")
    four = sum(1 for w in cells if w.get("chips") == 4)
    need(four <= max(len(cells) // 4, 1, accepted_four_chip),
         f"{four} of {len(cells)} cells ask for four chips")

    # -- metrics -------------------------------------------------------------
    e2e, layers = manifest["end_to_end"], manifest["per_layer"]
    need(1 <= len(e2e) <= 16, "end_to_end: 1 to 16")
    need(1 <= len(layers) <= 128, "per_layer: 1 to 128")
    names = [m.get("name") for m in e2e + layers]
    need(len(set(names)) == len(names), "two metrics share a name")

    def cells_of(m):
        return set(m.get("workloads", cell_names))

    for m in e2e + layers:
        n = m.get("name")
        need(NAME.match(str(n or "")) is not None, f"metric name {n!r}")
        need(UNIT.match(str(m.get("unit", ""))) is not None,
             f"metric {n!r}: unit {m.get('unit')!r}")
        need(m.get("better") in ("lower", "higher"),
             f"metric {n!r}: better {m.get('better')!r}")
        need(m.get("source") in SOURCES,
             f"metric {n!r}: source {m.get('source')!r}")
        if "workloads" in m:
            need(isinstance(m["workloads"], list) and m["workloads"]
                 and set(m["workloads"]) <= set(cell_names),
                 f"metric {n!r}: workloads names no cell")
    for m in e2e:
        n = m.get("name")
        need(set(m) - {"workloads"}
             == {"name", "unit", "better", "bound", "source"},
             f"end-to-end metric {n!r}: keys {sorted(m)}")
        need(m.get("source") in ("host_clock", "device_trace"),
             f"end-to-end metric {n!r}: source {m.get('source')!r}")
        b = m.get("bound")
        need(isinstance(b, (int, float)) and not isinstance(b, bool)
             and MIN_BOUND <= b <= MAX_BOUND,
             f"end-to-end metric {n!r}: bound {b!r} outside "
             f"[{MIN_BOUND}, {MAX_BOUND}]")
    setup = [m for m in e2e if m.get("name") == "setup_s"]
    need(len(setup) == 1 and "workloads" not in setup[0],
         "setup_s must be an end-to-end metric of every cell")
    e2e_cells = {m.get("name"): cells_of(m) for m in e2e}
    for m in layers:
        n = m.get("name")
        need(set(m) - {"workloads"}
             == {"name", "unit", "better", "source", "layer", "moves"},
             f"per-layer metric {n!r}: keys {sorted(m)}")
        need(NAME.match(str(m.get("layer", ""))) is not None,
             f"per-layer metric {n!r}: layer {m.get('layer')!r} must be "
             "1 to 64 letters, digits, '_', '.' and '-'")
        moved = m.get("moves")
        need(moved in e2e_cells,
             f"per-layer metric {n!r} moves {moved!r}, no end-to-end "
             "metric")
        if moved in e2e_cells:
            need(cells_of(m) <= e2e_cells[moved],
                 f"per-layer metric {n!r} is reported in "
                 f"{sorted(cells_of(m) - e2e_cells[moved])} where "
                 f"{moved!r} is not")
    for w in cell_names:
        mine = [m for m in e2e if w in cells_of(m)]
        need(len(mine) >= 2, f"cell {w!r} reports no end-to-end metric "
             "besides setup_s")
        need(any(w in cells_of(m) for m in layers),
             f"cell {w!r} reports no per-layer metric")

    # -- the files a cell names ---------------------------------------------
    bench = os.path.join(root, paths[0]) if paths else root
    for w in cells:
        mix = [os.path.join(bench, "traffic", str(w.get("traffic")) + ext)
               for ext in TRAFFIC_EXT]
        found = [p for p in mix if os.path.isfile(p)]
        need(len(found) == 1,
             f"workload {w.get('name')!r}: no traffic file for "
             f"{w.get('traffic')!r}")
        if found and found[0].endswith(".json"):
            with open(found[0]) as f:
                runner = json.load(f).get("runner", "")
            need(os.path.isfile(os.path.join(
                bench, "runners", str(runner) + ".py")),
                f"mix {w.get('traffic')!r}: no runners/{runner}.py")
    for m in layers:
        need(os.path.isfile(os.path.join(
            bench, "metrics", str(m.get("name")) + ".py")),
            f"per-layer metric {m.get('name')!r}: no reader file")
    return bad


def main():
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    found = problems(manifest, root)
    for p in found:
        print(p)
    print(f"{len(found)} problem(s)")
    return 1 if found else 0


if __name__ == "__main__":
    sys.exit(main())
