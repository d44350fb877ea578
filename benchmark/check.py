"""BENCHMARK.json against the contract's rules of form, before any chip time
is spent on it: names, units, lengths, the keys each entry may have, that
every file the manifest names is there, and that every per-layer metric's
`moves` metric is reported by every cell that reports the metric.

    python3 benchmark/check.py            # exit 0 and "ok", or the faults

It checks form, not truth: whether a cell runs is for the chip to say.
"""
from __future__ import annotations

import json
import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
TOP = {"command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"}
KEYS = {
    "configs": ({"name", "source", "file", "reduced", "why"}, set()),
    "workloads": ({"name", "config", "traffic", "chips", "why"}, set()),
    "end_to_end": ({"name", "unit", "better", "bound", "source"}, {"workloads"}),
    "per_layer": ({"name", "unit", "better", "source", "layer", "moves"}, {"workloads"}),
}
WIDTH = re.compile(r"(hidden|intermediate|latent|state|proj|head_dim|head_size|"
                   r"expansion|experts_per|_dim$|_rank$|^n_embd$|^n_inner$)")
MAX_RUN_SECONDS = 51
TRAFFIC_ENDINGS = (".json", ".jsonl", ".toml", ".txt", ".csv")


def line(text, what, faults, limit=200):
    if not isinstance(text, str) or not 1 <= len(text) <= limit \
            or "\n" in text or "\t" in text:
        faults.append(f"{what}: must be 1 to {limit} characters on one line, no tab")


def check(manifest: dict, root: str = ROOT) -> list:
    faults = []
    if set(manifest) != TOP:
        faults.append(f"top-level keys must be exactly {sorted(TOP)}; "
                      f"got {sorted(manifest)}")
        return faults

    def name(value, what):
        if not isinstance(value, str) or not NAME.match(value):
            faults.append(f"{what} {value!r}: a name is 1 to 64 of letters, digits, "
                          "'_', '.', '-', starting with a letter, digit or '_'")

    command, paths = manifest["command"], manifest["paths"]
    if not isinstance(command, list) or not 1 <= len(command) <= 32:
        faults.append("command: a list of 1 to 32 strings")
    for word in command:
        line(word, f"command word {word!r}", faults)
        if isinstance(word, str) and (word.startswith("/") or ".." in word.split("/")):
            faults.append(f"command word {word!r}: no absolute path, no '..'")
    if not isinstance(paths, list) or not 1 <= len(paths) <= 16:
        faults.append("paths: 1 to 16 directories")
    for p in paths:
        if not isinstance(p, str) or not PATH.match(p) or p.startswith("/") \
                or ".." in p.split("/"):
            faults.append(f"path {p!r}: a relative path of letters, digits, '_.-/'")
        elif not os.path.isdir(os.path.join(root, p)):
            faults.append(f"path {p!r}: no such directory")
    seconds = manifest["run_seconds"]
    if not isinstance(seconds, int) or not 1 <= seconds <= MAX_RUN_SECONDS:
        faults.append(f"run_seconds: a whole number from 1 to {MAX_RUN_SECONDS}")

    for group, (required, optional) in KEYS.items():
        entries = manifest[group]
        if not isinstance(entries, list) or not entries:
            faults.append(f"{group}: at least one entry")
            continue
        seen = set()
        for e in entries:
            extra = set(e) - required - optional
            missing = required - set(e)
            if extra or missing:
                faults.append(f"{group} {e.get('name')!r}: keys missing {sorted(missing)}, "
                              f"not allowed {sorted(extra)}")
            name(e.get("name"), f"{group} name")
            if e.get("name") in seen:
                faults.append(f"{group}: {e.get('name')!r} twice")
            seen.add(e.get("name"))

    def under_paths(f):
        return any(f == p or f.startswith(p.rstrip("/") + "/") for p in paths)

    configs, files = {}, set()
    for c in manifest["configs"]:
        configs[c.get("name")] = c
        line(c.get("source"), f"config {c.get('name')}: source", faults)
        line(c.get("why"), f"config {c.get('name')}: why", faults)
        f = c.get("file", "")
        if not PATH.match(f) or not under_paths(f):
            faults.append(f"config {c.get('name')}: file {f!r} is not under paths")
        elif not os.path.isfile(os.path.join(root, f)):
            faults.append(f"config {c.get('name')}: file {f!r} is missing")
        else:
            try:
                with open(os.path.join(root, f)) as fh:
                    if not isinstance(json.load(fh), dict):
                        faults.append(f"config file {f!r}: not a JSON object")
            except ValueError as err:
                faults.append(f"config file {f!r}: {err}")
        if f in files:
            faults.append(f"config file {f!r} serves two configurations")
        files.add(f)
        reduced = c.get("reduced", [])
        if not isinstance(reduced, list) or len(reduced) > 16:
            faults.append(f"config {c.get('name')}: reduced is a list of at most 16 keys")
        for key in reduced if isinstance(reduced, list) else []:
            name(key, f"config {c.get('name')}: reduced key")
            if isinstance(key, str) and WIDTH.search(key):
                faults.append(f"config {c.get('name')}: reduced names a width, {key!r}")

    cells, pairs, used = {}, set(), set()
    for w in manifest["workloads"]:
        cells[w.get("name")] = w
        name(w.get("config"), "workload config")
        name(w.get("traffic"), "workload traffic")
        line(w.get("why"), f"workload {w.get('name')}: why", faults)
        if w.get("config") not in configs:
            faults.append(f"workload {w.get('name')}: unknown config {w.get('config')!r}")
        used.add(w.get("config"))
        if w.get("chips") not in (1, 4):
            faults.append(f"workload {w.get('name')}: chips is 1 or 4")
        pair = (w.get("config"), w.get("traffic"))
        if pair in pairs:
            faults.append(f"workload {w.get('name')}: the pair {pair} appears twice")
        pairs.add(pair)
        mix = [p for p in paths for end in TRAFFIC_ENDINGS
               if os.path.isfile(os.path.join(root, p, "traffic", f"{w.get('traffic')}{end}"))]
        if not mix:
            faults.append(f"workload {w.get('name')}: no traffic file "
                          f"traffic/{w.get('traffic')}.json under paths")
    for c in configs:
        if c not in used:
            faults.append(f"config {c!r} is used by no cell")
    if len(cells) > 24 or len(configs) > 24:
        faults.append("at most 24 cells and 24 configurations")
    four = sum(1 for w in cells.values() if w.get("chips") == 4)
    if four > max(1, len(cells) // 4):
        faults.append(f"{four} cells ask for 4 chips; at most {max(1, len(cells) // 4)} may")

    def reported_in(metric):
        return set(metric.get("workloads", cells))

    metric_names, end_to_end = set(), {}
    for group in ("end_to_end", "per_layer"):
        for m in manifest[group]:
            if m.get("name") in metric_names:
                faults.append(f"metric {m.get('name')!r} twice")
            metric_names.add(m.get("name"))
            if not isinstance(m.get("unit"), str) or not UNIT.match(m["unit"]):
                faults.append(f"metric {m.get('name')}: unit {m.get('unit')!r} is not 1 to "
                              "16 of letters, digits, '_', '/', '%', '.', '-'")
            if m.get("better") not in ("lower", "higher"):
                faults.append(f"metric {m.get('name')}: better is 'lower' or 'higher'")
            if m.get("source") not in SOURCES:
                faults.append(f"metric {m.get('name')}: source is one of {sorted(SOURCES)}")
            for w in m.get("workloads", []):
                if w not in cells:
                    faults.append(f"metric {m.get('name')}: unknown workload {w!r}")
            if "workloads" in m and not m["workloads"]:
                faults.append(f"metric {m.get('name')}: an empty workloads list")
    for m in manifest["end_to_end"]:
        end_to_end[m.get("name")] = m
        if m.get("source") not in ("host_clock", "device_trace"):
            faults.append(f"end-to-end metric {m.get('name')}: source is host_clock "
                          "or device_trace")
        bound = m.get("bound")
        if not isinstance(bound, (int, float)) or not 0.01 <= bound <= 0.1:
            faults.append(f"end-to-end metric {m.get('name')}: bound from 0.01 to 0.1")
    if "setup_s" not in end_to_end or "workloads" in end_to_end.get("setup_s", {}):
        faults.append("end_to_end must hold setup_s, reported by every cell")
    if not 1 <= len(manifest["end_to_end"]) <= 16 or not 1 <= len(manifest["per_layer"]) <= 128:
        faults.append("1 to 16 end-to-end and 1 to 128 per-layer metrics")
    for m in manifest["per_layer"]:
        name(m.get("layer"), f"per_layer metric {m.get('name')}: layer")
        target = end_to_end.get(m.get("moves"))
        if target is None:
            faults.append(f"per_layer metric {m.get('name')}: moves {m.get('moves')!r}, "
                          "which is no end-to-end metric")
        elif not reported_in(m) <= reported_in(target):
            faults.append(f"per_layer metric {m.get('name')}: moves {m.get('moves')!r}, "
                          f"which is not reported in {sorted(reported_in(m) - reported_in(target))}")
        reader = m.get("name", "").split(".")[0]
        if not any(os.path.isfile(os.path.join(root, p, "layers", reader + ".py"))
                   for p in paths):
            faults.append(f"per_layer metric {m.get('name')}: no reader layers/{reader}.py")
    for w in cells:
        others = [m for m in manifest["end_to_end"]
                  if m.get("name") != "setup_s" and w in reported_in(m)]
        layers = [m for m in manifest["per_layer"] if w in reported_in(m)]
        if not others or not layers:
            faults.append(f"workload {w}: needs an end-to-end metric besides setup_s "
                          "and a per-layer metric")
    return faults


def main(argv) -> int:
    path = argv[1] if len(argv) > 1 else os.path.join(ROOT, "BENCHMARK.json")
    if os.path.getsize(path) > 64 * 1024:
        print(f"{path}: larger than 64 KiB")
        return 1
    with open(path) as f:
        manifest = json.load(f)
    faults = check(manifest, os.path.dirname(os.path.abspath(path)))
    for fault in faults:
        print(fault)
    print("ok" if not faults else f"{len(faults)} fault(s)")
    return 1 if faults else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
