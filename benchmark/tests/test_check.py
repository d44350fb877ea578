"""check.py: passes the committed manifest and refuses what PR 22's lacked."""
import copy
import os

import pytest

from benchmark import check, harness


@pytest.fixture()
def manifest():
    return harness.load_json(os.path.join(harness.ROOT, "BENCHMARK.json"))


def test_committed_manifest_passes(manifest):
    assert check.check(manifest) == []


def _set(path, value):
    def change(m):
        node = m
        for key in path[:-1]:
            node = node[key]
        node[path[-1]] = value
    return change


@pytest.mark.parametrize("change, said", [
    (_set(("per_layer", 0, "layer"), "attention flash"), "a name is"),
    (_set(("per_layer", 0, "layer"), "x" * 65), "a name is"),
    (_set(("workloads", 0, "name"), "-starts-with-dash"), "a name is"),
    (_set(("configs", 0, "name"), "gpt2,medium"), "a name is"),
    (_set(("end_to_end", 0, "unit"), "tokens per second"), "unit"),
    (_set(("end_to_end", 0, "unit"), "x" * 17), "unit"),
    (_set(("end_to_end", 0, "unit"), "µs"), "unit"),
    (_set(("per_layer", 0, "moves"), "serve_tokens_per_s"), "not reported in"),
    (_set(("per_layer", 0, "moves"), "no_such_metric"), "no end-to-end metric"),
    (_set(("configs", 0, "file"), "benchmark/configs/missing.json"), "missing"),
    (_set(("configs", 0, "file"), "paddle_tpu/models/gpt.py"), "not under paths"),
    (_set(("workloads", 0, "traffic"), "no-such-mix"), "no traffic file"),
    (_set(("per_layer", 0, "name"), "no_such_reader.train"), "no reader"),
    (_set(("per_layer", 0, "why"), "because"), "not allowed"),
    (_set(("end_to_end", 0, "bound"), 0.2), "bound"),
    (_set(("workloads", 0, "chips"), 2), "chips is 1 or 4"),
    (_set(("configs", 0, "reduced"), ["hidden_size"]), "names a width"),
    (_set(("run_seconds",), 52), "run_seconds"),
    (_set(("command",), ["python3", "/root/repo/benchmark/run.py"]), "no absolute path"),
])
def test_refuses(manifest, change, said):
    broken = copy.deepcopy(manifest)
    change(broken)
    faults = check.check(broken)
    assert faults and any(said in f for f in faults), faults
