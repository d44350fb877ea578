"""What PR 30 adds to the benchmark, on the CPU: the `closed_loop_lm` driver
on the stand-in configuration, the retention readers on captures encoded by
hand with the new regions, `flops_brumby` against hand counts, and the
manifest with the new configuration, cell and metrics."""
import json
import os

import pytest

from benchmark import check, flops_brumby, harness, run, scopes
from benchmark.drivers import closed_loop_lm
from benchmark.tests.test_scopes_and_spans import FakeTrace, encode_capture

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "serve-brumby14b-gen-saturated"
SMALL = dict(prompt={"median": 20, "sigma": 0.6, "min": 4, "max": 60},
             answer={"median": 8, "sigma": 0.5, "min": 2, "max": 16}, max_total=100,
             distinct_requests=32, ramp_seconds=0.5, ramp_completions=2, trace_seconds=1,
             check_prompts=[37, 50, 5, 16, 17, 12], check_answer=12, check_width=64)


def stand_in():
    return harness.load_json(os.path.join(HERE, "tiny-brumby.json"))


def mix(**changes):
    traffic = harness.load_json(f"{harness.HERE}/traffic/longgen-closed.json")
    traffic["retention_check"] = dict(traffic["retention_check"], ragged=5)
    return dict(traffic, **SMALL, **changes)


def published():
    return harness.load_json(f"{harness.HERE}/configs/brumby-14b-base.json")


# ------------------------------------------------------------------ driver
@pytest.mark.parametrize("trace", [False, True])
def test_closed_loop_lm_on_the_stand_in(trace):
    r = closed_loop_lm.run(stand_in(), mix(), 2147483700, 2.0, trace)
    assert r["correct"] and r["failed"] == 0 and r["attempted"] > 10
    f = r["facts"]
    assert f["lanes"] == 4 and 0 < f["pool_peak_share"] <= 100.0
    assert f["layers"] == 2 and f["state_bytes_per_lane"] == 2 * flops_brumby.state_bytes(stand_in())
    assert r["measured"]["serve_tokens_per_s"] > 0 and r["measured"]["setup_s"] > 0
    if trace:
        kinds = {a.get("kind") for name, _, _, a in r["spans"] if name == "serving.decode"}
        assert kinds == {"prefill", "decode"}
        chunked = [a for _, _, _, a in r["spans"] if a.get("kind") == "prefill"]
        assert all({"chunk", "chunks", "tokens"} <= set(a) for a in chunked)
        assert any(a["chunks"] > 1 for a in chunked)


def test_a_gpt_configuration_has_no_builder_here():
    with pytest.raises(ModuleNotFoundError):
        closed_loop_lm.run({"model_type": "gpt2"}, mix(), 1, 1.0, False)


# ------------------------------------------------------------------- flops
def test_flops_brumby_against_hand_counts():
    c = published()
    assert flops_brumby.sym(128) == 8256
    assert flops_brumby.state_bytes(c) == 8 * 8256 * 129 * 4 == 34_080_768      # 34.1 MB a layer
    assert flops_brumby.decode_state_bytes(c, 16) == 2 * 16 * 8 * 34_080_768     # 8.7 GB a step
    assert flops_brumby.retention_flops_per_token(c) == 8 * 2 * 8256 * 129 * 48  # 102 MFLOP a layer
    per_layer = 2 * 5120 * 5120 + 2 * 5120 * 1024 + 5120 * 8 + 3 * 5120 * 17408
    assert flops_brumby.layer_matmul_parameters(c) == per_layer == 330_342_400
    assert flops_brumby.head_parameters(c) == 151936 * 5120 == 777_912_320
    assert flops_brumby.prompt_flops_per_token(c) == 2 * 8 * per_layer + 8 * 2 * 8256 * 129 * 48
    assert flops_brumby.answer_flops_per_token(c) - flops_brumby.prompt_flops_per_token(c) \
        == 2 * 777_912_320
    # a full decode step's state at the bandwidth's peak: 10.65 ms
    assert flops_brumby.bytes_seconds(flops_brumby.decode_state_bytes(c, 16), "TPU v5 lite") \
        == pytest.approx(0.010653, rel=1e-3)


# ----------------------------------------------------------------- readers
DECODE = "jit(_decode_fn)/decode/while/body/closed_call/"
PREFILL = "jit(_prefill_fn)/prefill/while/body/closed_call/"
OPS = [  # microseconds: one prefill chunk, then two decode steps
    (100.0, 300.0, "%fusion.1 = f32[8] fusion(%a), kind=kOutput", PREFILL + "retn/chunk/dot_general:"),
    (400.0, 100.0, "%fusion.2 = bf16[8] fusion(%b), kind=kOutput", PREFILL + "mlp/dot_general:"),
    (1000.0, 50.0, "%retn_step.3 = f32[8] custom-call(%c)", DECODE + "retn/state/retn_step/pallas_call:"),
    (1050.0, 10.0, "%fusion.4 = f32[8] fusion(%d), kind=kLoop", DECODE + "retn/state/mul:"),
    (1060.0, 40.0, "%fusion.5 = bf16[8] fusion(%e), kind=kOutput", DECODE + "mlp/dot_general:"),
    (2000.0, 50.0, "%retn_step.3 = f32[8] custom-call(%c)", DECODE + "retn/state/retn_step/pallas_call:"),
    (2050.0, 50.0, "%copy.9 = f32[8] copy(%q)", None),
]
# the profiler lists a scanned layer stack's `while` beside the operations of
# its body, with no scope of its own: the second decode step's, here
WRAPPED = OPS + [(2000.0, 100.0, "%while.7 = (f32[8], s32[]) while(%t), condition=%c, body=%b", None)]
SPANS = [
    ("serving.decode", 50e-6, 600e-6, {"kind": "prefill", "rung": (1, 8), "lanes": 1,
                                        "chunk": 0, "chunks": 2, "tokens": 6}),
    ("serving.decode", 900e-6, 1200e-6, {"kind": "decode", "rung": 4, "lanes": 3}),
    ("serving.decode", 1900e-6, 2200e-6, {"kind": "decode", "rung": 4, "lanes": 4}),
    ("serving.decode", 2900e-6, 3300e-6, {"kind": "decode", "rung": 4, "lanes": 4}),   # past the window
]
FACTS = {"device_kind": "TPU v5 lite", "chips": 1, "lanes": 4, "layers": 2,
         "state_bytes_per_lane": 1000, "retention_flops_per_token": 5e6,
         "prompt_flops_per_token": 2e7, "answer_flops_per_token": 3e7}


@pytest.fixture()
def read(tmp_path, monkeypatch):
    def go(name, facts=FACTS, ops=OPS, spans=SPANS):
        path = tmp_path / f"c{len(os.listdir(tmp_path))}.xplane.pb"
        path.write_bytes(encode_capture(ops))
        monkeypatch.setattr(scopes, "capture_path", lambda: str(path))
        return run.load_module("layers", name).read(FakeTrace(ops, 0.0, 3000e-6), spans, facts)
    return go


def test_retention_share_readers(read):
    busy = 300 + 100 + 50 + 10 + 40 + 50 + 50
    assert read("retn_state_share") == pytest.approx(100 * 110 / busy)
    assert read("retn_chunk_share") == pytest.approx(100 * 300 / busy)
    assert read("unscoped_share") == pytest.approx(100 * 50 / busy)
    assert read("unscoped_share_flat") == pytest.approx(100 * 50 / busy)


def test_flat_unscoped_share_leaves_the_scan_wrappers_out(read):
    """`unscoped_share` adds a `while` to the operations inside it; the flat
    reader counts what no region names once."""
    busy = 300 + 100 + 50 + 10 + 40 + 50 + 50
    assert read("unscoped_share", ops=WRAPPED) == pytest.approx(100 * 150 / busy)
    assert read("unscoped_share_flat", ops=WRAPPED) == pytest.approx(100 * 50 / busy)
    # every operation in a region: 0, not None (the cell has to report it)
    scoped = [op for op in WRAPPED if op[3] is not None or "while" in op[2]]
    assert read("unscoped_share_flat", ops=scoped) == 0.0


def test_retention_rooflines_count_live_lanes_and_prompt_tokens(read):
    # two decode steps inside the window, 3 and 4 live lanes: 2 x 7 x 1000 bytes
    least = 2 * 7 * 1000 / 819e9
    assert read("retn_state_roofline") == pytest.approx(100 * least / 110e-6)
    # one chunk of 6 prompt tokens at 5 MFLOP a token
    assert read("retn_chunk_roofline") == pytest.approx(100 * (6 * 5e6 / 197e12) / 300e-6)
    # the whole window's tokens over the window at the peak
    needed = 6 * 2e7 + 7 * 3e7
    assert read("serve_mfu") == pytest.approx(100 * needed / (3000e-6 * 197e12))
    assert read("prefill_chunks_per_s") == pytest.approx(1 / 3000e-6)


def test_retention_readers_read_nothing_of_a_program_without_them(read, monkeypatch):
    gpt_spans = [(n, a, b, {k: v for k, v in args.items() if k in ("kind", "rung", "lanes")})
                 for n, a, b, args in SPANS]
    gpt_ops = [(s, d, text, tf.replace("retn/state", "attn/core").replace("retn/chunk", "attn/core")
                if tf else tf) for s, d, text, tf in OPS]
    gpt_facts = {k: FACTS[k] for k in ("device_kind", "chips", "lanes")}
    for name in ("retn_state_share", "retn_chunk_share", "retn_state_roofline",
                 "retn_chunk_roofline", "serve_mfu", "prefill_chunks_per_s"):
        assert read(name, gpt_facts, gpt_ops, gpt_spans) is None, name
    # a program without the vocabulary (the parent): the term itself is missing
    monkeypatch.setattr(scopes, "term", lambda name: None)
    for name in ("retn_state_share", "retn_chunk_share", "retn_state_roofline",
                 "retn_chunk_roofline", "unscoped_share_flat"):
        assert read(name) is None, name


# ---------------------------------------------------------------- manifest
def test_manifest_is_ok_and_the_cell_reports_what_it_must():
    manifest = harness.load_json(os.path.join(harness.ROOT, "BENCHMARK.json"))
    # check.py's width pattern holds `hidden`, so it calls the source's depth key
    # a width; the driver does not, and refuses a cut depth under any other key
    assert check.check(manifest) == [
        "config brumby-14b-base: reduced names a width, 'num_hidden_layers'"]
    cells = {w["name"]: w for w in manifest["workloads"]}
    assert cells[CELL] == {"name": CELL, "config": "brumby-14b-base",
                           "traffic": "longgen-closed", "chips": 1, "why": cells[CELL]["why"]}
    entry = {c["name"]: c for c in manifest["configs"]}["brumby-14b-base"]
    assert entry["reduced"] == ["num_hidden_layers"]
    end = {m["name"] for m in run.metrics_of(manifest, "end_to_end", CELL)}
    assert end == {"serve_tokens_per_s", "setup_s"}
    mine = {m["name"] for m in run.metrics_of(manifest, "per_layer", CELL)}
    assert mine == {
        "retn_state_share", "retn_state_roofline", "retn_chunk_share", "retn_chunk_roofline",
        "serve_mfu.brumby", "prefill_chunks_per_s", "decode_step_ms.brumby",
        "prefill_share.brumby", "batch_occupancy.brumby", "idle_share.brumby",
        "sched_host_ms.brumby", "sample_share.brumby", "unscoped_share_flat.brumby",
        "pool_peak_share.brumby", "cache_misses.brumby"}
    # the accepted cells report what they reported, and nothing of the new cell's
    for cell in ("train-gpt2m-1chip", "serve-gpt2s-chat-saturated", "serve-gpt2s-chat-steady"):
        names = {m["name"] for m in run.metrics_of(manifest, "per_layer", cell)}
        assert "cache_misses" in names and not names & mine


def test_configuration_keeps_every_published_width():
    c = published()
    catalog = {"attention_bias": False, "head_dim": 128, "hidden_act": "silu", "hidden_size": 5120,
               "intermediate_size": 17408, "max_position_embeddings": 32768,
               "max_window_layers": 40, "model_type": "brumby", "num_attention_heads": 40,
               "num_key_value_heads": 8, "rms_norm_eps": 1e-06, "rope_scaling": None,
               "rope_theta": 1000000, "sliding_window": None, "tie_word_embeddings": False,
               "use_sliding_window": False, "vocab_size": 151936}
    assert {k: c[k] for k in catalog} == catalog
    assert c["num_hidden_layers"] == 8 and c["published"] == {"num_hidden_layers": 40}
    assert c["reduced"] == ["num_hidden_layers"] and "five pipeline stages" in c["deployment"]
    traffic = harness.load_json(f"{harness.HERE}/traffic/longgen-closed.json")
    top = c["engine"]["seq_buckets"][-1]
    assert sum(p > 2 * top for p in traffic["check_prompts"]) >= 2
    assert traffic["check_answer"] >= 96 and len(traffic["check_prompts"]) == 6
    assert max(traffic["check_prompts"]) + traffic["check_answer"] <= traffic["check_width"]
