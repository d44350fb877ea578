"""The benchmark's one command.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

BENCHMARK.json names the cell's configuration and traffic mix; everything
that belongs to one of them, or to one per-layer metric, is a file of its own
that this program finds by that name:

    configs/<config>.json    the model configuration (the manifest gives the path)
    traffic/<traffic>.json   the mix: its `driver` and that driver's parameters
    drivers/<driver>.py      run(config, traffic, seed, seconds, trace) -> result
    layers/<reader>.py       read(trace, spans, facts) -> value, or None;
                             metric `<reader>` or `<reader>.<tag>` is read by it

With --trace 0 the last line carries the cell's end-to-end metrics, with
--trace 1 its per-layer metrics and the breakdown. No cell, configuration,
mix or metric is named in this file. It runs on the TPU it is started on and
exits non-zero, printing no result, anywhere else.
"""
from __future__ import annotations

import argparse
import importlib.util
import json
import os
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
# libtpu logs to /tmp/tpu_logs unless told otherwise; keep it under the
# caller's TMPDIR, so that a run writes nothing outside what it was given
os.environ.setdefault("TPU_LOG_DIR", os.path.join(tempfile.gettempdir(), "tpu_logs"))

from benchmark import harness, trace_reduce  # noqa: E402  (starts the set-up clock)


def load_module(kind: str, name: str):
    path = os.path.join(harness.HERE, kind, name + ".py")
    if not os.path.isfile(path):
        raise FileNotFoundError(f"{kind}/{name}.py is not there")
    spec = importlib.util.spec_from_file_location(f"benchmark.{kind}.{name}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def metrics_of(manifest: dict, group: str, cell: str) -> list:
    """The group's metrics that this cell reports: all without a `workloads`
    key, and those that list the cell."""
    return [m for m in manifest[group]
            if "workloads" not in m or cell in m["workloads"]]


def find_cell(manifest: dict, name: str):
    cells = {w["name"]: w for w in manifest["workloads"]}
    if name not in cells:
        raise SystemExit(f"run.py: no workload {name!r} in BENCHMARK.json; "
                         f"it has {sorted(cells)}")
    cell = cells[name]
    config_entry = {c["name"]: c for c in manifest["configs"]}[cell["config"]]
    config = harness.load_json(os.path.join(ROOT, config_entry["file"]))
    traffic = harness.load_json(
        os.path.join(harness.HERE, "traffic", cell["traffic"] + ".json"))
    return cell, config, traffic


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)

    manifest = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell, config, traffic = find_cell(manifest, args.workload)

    from paddle_tpu.compile_cache.jax_cache import enable_jax_cache

    cache_dir = enable_jax_cache()
    device = harness.device_facts()
    harness.log(f"device: platform={device['platform']} kind={device['kind']!r} "
                f"count={device['count']}; compile cache {cache_dir}")
    if device["platform"] != "tpu":
        print(f"run.py: no TPU (jax reports {device['platform']!r}); the "
              "benchmark has no CPU mode", file=sys.stderr)
        return 3
    if device["count"] < cell["chips"]:
        print(f"run.py: the cell needs {cell['chips']} chips, jax sees "
              f"{device['count']}", file=sys.stderr)
        return 3
    harness.log(f"cell {cell['name']}: config {cell['config']}, traffic "
                f"{cell['traffic']} ({traffic['driver']}), seed {args.seed}, "
                f"{args.seconds:g} s, trace {args.trace}")

    driver = load_module("drivers", traffic["driver"])
    result = driver.run(config, traffic, args.seed, args.seconds, bool(args.trace))

    line = {"correct": bool(result["correct"]),
            "attempted": int(result["attempted"]),
            "failed": int(result["failed"])}
    facts, metrics = result["facts"], {}
    device["memory_peak_bytes"] = harness.memory_peak_bytes()
    if not args.trace:
        for m in metrics_of(manifest, "end_to_end", cell["name"]):
            metrics[m["name"]] = {"value": result["measured"][m["name"]],
                                  "unit": m["unit"]}
    else:
        capture = result["capture"]
        trace = trace_reduce.load(capture.path, capture.t_sync, capture.t0, capture.t1)
        harness.log(f"trace: {sum(len(d.ops) for d in trace.devices)} device "
                    f"operations; capture's clock shifted by "
                    f"{trace.clock_shift_s:.6f} s onto the host's")
        for m in metrics_of(manifest, "per_layer", cell["name"]):
            reader = load_module("layers", m["name"].split(".")[0])
            value = reader.read(trace, result["spans"], facts)
            if value is not None:
                metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
        device["busy_s"] = trace_reduce.busy_seconds(trace)
        device["window_s"] = trace.window_s
        # a span that carries a `kind` (the engine's prefill and decode steps)
        # is named with it, so that the gaps tell the two apart
        labelled = [(f"{name}:{a['kind']}" if a.get("kind") else name, t0, t1)
                    for name, t0, t1, a in result["spans"]]
        line["breakdown"] = {
            "device_ops": trace_reduce.top(trace_reduce.op_seconds(trace)),
            "idle_gaps": trace_reduce.top(trace_reduce.idle_gaps(trace, labelled)),
        }
    line["metrics"] = metrics
    line["device"] = device
    harness.log(f"set-up {result['measured']['setup_s']:.2f} s; persistent cache "
                f"{facts['cache_hits']} hits, {facts['cache_misses']} misses")
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
