"""What PR 36 adds to the benchmark, on the CPU: the accepted
`closed_loop_pages` driver on the stand-in configuration of
`command-a-plus`, the window readers on captures encoded by hand with the new
kernel's name, `flops_cohere2_moe` against hand counts, the attention check's
controls, and the manifest with the new configuration, cell and metrics."""
import json
import os

import pytest

from benchmark import check, harness, run, scopes
from benchmark import flops_cohere2_moe as fl
from benchmark import models_cohere2_moe as lm
from benchmark.drivers import closed_loop_pages
from benchmark.tests.test_scopes_and_spans import FakeTrace, encode_capture

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "serve-cmdaplus-mixed-saturated"
SMALL = dict(prompt={"median": 24, "sigma": 1.0, "min": 4, "max": 110},
             answer={"median": 32, "sigma": 0.5, "min": 12, "max": 48}, max_total=158,
             distinct_requests=32, ramp_seconds=0.5, ramp_completions=2, trace_seconds=1,
             check_prompts=[5, 16, 17, 20, 70, 100], check_answer=12,
             check_widths=[32, 128], check_block=32)
TINY_CHECK = dict(chunks=3, ragged=10, deep=[32, 64], others=4, queries=8, steps=8,
                  step_group=4)


def stand_in():
    return harness.load_json(os.path.join(HERE, "tiny-cohere2-moe.json"))


def mix(**changes):
    traffic = harness.load_json(f"{harness.HERE}/traffic/mixedlen-closed.json")
    traffic["attention_check"] = dict(traffic["attention_check"], **TINY_CHECK)
    return dict(traffic, **SMALL, **changes)


def published():
    return harness.load_json(f"{harness.HERE}/configs/command-a-plus.json")


# ------------------------------------------------------------------ driver
@pytest.mark.parametrize("trace", [False, True])
def test_accepted_pages_driver_runs_the_stand_in(trace):
    r = closed_loop_pages.run(stand_in(), mix(), 2147483700, 2.0, trace)
    assert r["correct"] and r["failed"] == 0 and r["attempted"] > 5
    f = r["facts"]
    assert f["lanes"] == 8 and 0 < f["pool_peak_share"] <= 100.0     # both kinds of page
    assert (f["layers"], f["window_layers"], f["full_layers"], f["held_experts"]) == (4, 3, 1, 8)
    assert r["measured"]["serve_tokens_per_s"] > 0 and r["measured"]["setup_s"] > 0
    if trace:
        steps = [a for name, _, _, a in r["spans"] if name == "serving.decode"]
        assert {a["kind"] for a in steps} == {"prefill", "decode"}
        ran = [a for a in steps if a["lanes"]]
        assert all({"pairs", "experts_hit", "pages_live", "window_pages_live"} <= set(a)
                   for a in ran)
        assert all(a["window_pages_live"] <= a["pages_live"] for a in ran)
        assert any(a["window_pages_live"] < a["pages_live"] for a in ran)   # pages were released
        assert any(a["chunks"] > 1 for a in ran if a["kind"] == "prefill")


@pytest.fixture(scope="module")
def engine():
    _, engine = lm.build_engine(stand_in(), 11)
    yield engine
    engine.shutdown(drain=False)


def test_attention_check_is_sound_and_every_control_fails(engine):
    config, traffic = stand_in(), mix()
    limit = traffic["attention_check"]["tolerance"]
    assert lm.latent_error(engine, config, traffic, 5) < limit / 5
    for fault in lm.FAULTS:
        assert lm.latent_error(engine, config, traffic, 5, fault=fault) > 2 * limit, fault
    assert engine.kv_pool.in_use() == 0


def test_shared_experts_summed_instead_of_averaged_fail_the_logit_check(engine):
    import numpy as np

    config, traffic = stand_in(), mix()
    rng = np.random.default_rng(3)
    asked = [(rng.integers(0, 256, n).astype(np.int32), 12) for n in (5, 17, 70)]
    sent = [(p, n, engine.submit("t", p, max_new_tokens=n)) for p, n in asked]
    answered = lm.collect_check(sent, traffic)
    sound = lm.judge_check(engine.programs.params, config, traffic, answered)
    assert sound["complete"] and sound["worst_gap"] < 1e-3 and sound["exact"] == sound["tokens"]
    faulted = lm.judge_check(engine.programs.params, config, traffic, answered, average=False)
    assert faulted["exact"] < traffic["exact_floor"] * faulted["tokens"]


# ------------------------------------------------------------------- flops
def test_flops_against_hand_counts():
    c = published()
    attention = 2 * 4096 * 16384 + 2 * 4096 * 1024
    assert fl.attention_parameters(c) == attention == 142_606_336
    assert fl.expert_parameters(c) == 3 * 4096 * 4096 == 50_331_648
    assert fl.router_parameters(c) == 4096 * 128 and fl.held_pairs_per_token(c) == 1.0
    layer = attention + 4096 * 128 + 5 * 50_331_648          # 4 shared + 1 held pair a token
    assert fl.layer_parameters_per_token(c) == layer
    assert fl.prompt_flops_per_token(c) == 2 * 4 * layer == pytest.approx(3.16e9, rel=5e-3)
    assert fl.answer_flops_per_token(c) == 2 * 4 * layer + 2 * 32768 * 4096
    assert fl.expert_bytes(c) == 100_663_296 == fl.expert_flops_per_pair(c)
    assert fl.layer_counts(c) == (3, 1) and fl.kv_row_bytes(c) == 4096
    assert fl.attention_flops_per_row(c) == 4 * 128 * 128
    # a row: 4096 bytes at 819 GB/s is 5.0 ns, its 65,536 operations 0.33 ns: bytes bound it
    assert fl.least_seconds(65_536, 4096, "TPU v5 lite") == pytest.approx(4096 / 819e9)
    # 64 lanes, 20 of them past the window: global 900 pages; window 20 x 17 + 44 x 3
    assert fl.decode_attention_rows(900, 472, 64, 256, 3, 1) \
        == (900 - 64) * 256 + 64 + 3 * (472 - 64) * 256


# ----------------------------------------------------------------- readers
DECODE = "jit(_decode_fn)/decode/"
KERNEL = "attn/core/jit(gqa_paged_attention)/gqa_paged_attn/pallas_call:"
OPS = [  # microseconds: one prefill chunk, then two decode steps
    (100.0, 300.0, "%fusion.1 = bf16[8] fusion(%a), kind=kOutput", "jit(_prefill_fn)/prefill/while/body/attn/core/dot_general:"),
    (1000.0, 40.0, "%gqa_paged_attn.4 = bf16[8] custom-call(%e)", DECODE + KERNEL),
    (1040.0, 20.0, "%gqa_paged_attn.5 = bf16[8] custom-call(%e)", DECODE + KERNEL),
    (1060.0, 40.0, "%fusion.6 = bf16[8] fusion(%h), kind=kOutput", DECODE + "moe/shared/dot_general:"),
    (2000.0, 60.0, "%gqa_paged_attn.4 = bf16[8] custom-call(%e)", DECODE + KERNEL),
]
SPANS = [
    ("serving.decode", 50e-6, 600e-6, {"kind": "prefill", "rung": (1, 8), "lanes": 1, "chunk": 1,
                                        "chunks": 2, "tokens": 6, "pages_live": 3,
                                        "window_pages_live": 3}),
    ("serving.decode", 900e-6, 1200e-6, {"kind": "decode", "rung": (4, 20), "lanes": 3,
                                          "pages_live": 12, "pages_table": 80,
                                          "window_pages_live": 7}),
    ("serving.decode", 1900e-6, 2200e-6, {"kind": "decode", "rung": (4, 20), "lanes": 4,
                                           "pages_live": 20, "pages_table": 80,
                                           "window_pages_live": 10}),
    ("serving.decode", 2900e-6, 3300e-6, {"kind": "decode", "rung": (4, 20), "lanes": 4,
                                           "pages_live": 20, "pages_table": 80,
                                           "window_pages_live": 10}),   # past the window
]
FACTS = {"device_kind": "TPU v5 lite", "chips": 1, "lanes": 4, "layers": 4, "window_layers": 3,
         "full_layers": 1, "page_size": 8, "kv_row_bytes": 128, "attn_flops_per_row": 512}


@pytest.fixture()
def read(tmp_path, monkeypatch):
    def go(name, facts=FACTS, ops=OPS, spans=SPANS):
        path = tmp_path / f"c{len(os.listdir(tmp_path))}.xplane.pb"
        path.write_bytes(encode_capture(ops))
        monkeypatch.setattr(scopes, "capture_path", lambda: str(path))
        return run.load_module("layers", name).read(FakeTrace(ops, 0.0, 3000e-6), spans, facts)
    return go


def test_window_readers(read):
    busy = sum(d for _, d, _, _ in OPS)
    assert read("win_attn_share") == pytest.approx(100 * 120 / busy)
    rows = ((12 - 3) * 8 + 3 + 3 * (7 - 3) * 8) + ((20 - 4) * 8 + 4 + 3 * (10 - 4) * 8)
    assert read("win_attn_roofline") == pytest.approx(100 * (rows * 128 / 819e9) / 120e-6)
    held = ((3 * 7 + 12) / (4 * 12) + (3 * 10 + 20) / (4 * 20)) / 2
    assert read("win_pages_held_share") == pytest.approx(100 * held)


def test_window_readers_read_nothing_of_a_program_without_them(read, monkeypatch):
    bare = [(n, a, b, {k: v for k, v in args.items() if k != "window_pages_live"})
            for n, a, b, args in SPANS]
    for name in ("win_attn_roofline", "win_pages_held_share"):
        assert read(name, spans=bare) is None, name
    # the parent: no such kernel in the vocabulary
    monkeypatch.setattr(scopes, "term", lambda name: None)
    for name in ("win_attn_share", "win_attn_roofline"):
        assert read(name) is None, name


# ---------------------------------------------------------------- manifest
def test_manifest_has_the_cell_and_the_accepted_cells_are_as_they_were():
    manifest = harness.load_json(os.path.join(harness.ROOT, "BENCHMARK.json"))
    # check.py's width pattern holds `hidden`, so it calls the source's depth
    # key a width, for this configuration as for Brumby's and A.X-K1's
    assert check.check(manifest) == [
        f"config {c}: reduced names a width, 'num_hidden_layers'"
        for c in ("brumby-14b-base", "ax-k1", "command-a-plus")]
    assert manifest["workloads"][-1] == {
        "name": CELL, "config": "command-a-plus", "traffic": "mixedlen-closed", "chips": 1,
        "why": manifest["workloads"][-1]["why"]}
    assert manifest["configs"][-1]["reduced"] == ["num_hidden_layers", "layer_types",
                                                  "num_experts", "vocab_size"]
    end = {m["name"] for m in run.metrics_of(manifest, "end_to_end", CELL)}
    assert end == {"serve_tokens_per_s", "setup_s"}
    mine = {m["name"] for m in run.metrics_of(manifest, "per_layer", CELL)}
    assert mine == {f"{name}.cmdaplus" for name in (
        "serve_mfu", "decode_step_ms", "prefill_share", "batch_occupancy", "idle_share",
        "sched_host_ms", "sample_share", "unscoped_share_flat", "pool_peak_share",
        "cache_misses", "prefill_chunks_per_s", "moe_expert_share", "moe_expert_roofline",
        "moe_pairs_per_expert")} | {"win_attn_share", "win_attn_roofline",
                                    "win_pages_held_share"}
    for m in run.metrics_of(manifest, "per_layer", CELL):
        assert os.path.isfile(os.path.join(harness.HERE, "layers", m["name"].split(".")[0] + ".py"))
    for cell in ("train-gpt2m-1chip", "serve-gpt2s-chat-saturated", "serve-gpt2s-chat-steady",
                 "serve-brumby14b-gen-saturated", "serve-axk1-docqa-saturated"):
        assert not {m["name"] for m in run.metrics_of(manifest, "per_layer", cell)} & mine


def test_configuration_keeps_every_published_width():
    c = published()
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        rows = [json.loads(line) for line in f]
    (row,) = [r for r in rows if r["name"] == "command-a-plus-05-2026"]
    catalog = row["config"]
    cut = {"num_hidden_layers": 4, "layer_types": catalog["layer_types"][:4],
           "num_experts": 16, "vocab_size": 32768}
    assert {k: c[k] for k in catalog} == dict(catalog, **cut)
    assert c["source"] == row["source_url"] and c["reduced"] == list(cut)
    assert c["layer_types"] == ["sliding_attention"] * 3 + ["full_attention"]
    assert c["published"]["num_experts"] == 128 and c["published"]["vocab_size"] == 262144
    assert c["expert_share"] == [0, 8] and "8 chips share each layer" in c["deployment"]
    assert c["num_experts"] * c["expert_share"][1] == 128
    assert c["vocab_size"] * 8 == 262144 == 8 * c["tokenizer_vocab"]
    assert set(c["assumed"]) >= {"shared_experts", "intermediate_size", "prefix_dense",
                                 "window_edge", "initialisation"}
    e = c["engine"]
    assert e["window_pool_pages"] == e["max_slots"] * 17 + 2048 // e["page_size"]
    traffic = harness.load_json(f"{harness.HERE}/traffic/mixedlen-closed.json")
    assert traffic["prompt"] == {"median": 2048, "sigma": 1.3, "min": 128, "max": 32768}
    assert traffic["answer"] == {"median": 256, "sigma": 0.7, "min": 32, "max": 1024}
    assert traffic["max_total"] == e["max_seq"] == 33792
    assert traffic["clients_per_lane"] * e["max_slots"] == 128
    assert traffic["distinct_requests"] == 512
    assert traffic["check_prompts"] == [300, 4096, 4097, 4300, 9000, 20000]
    assert max(traffic["check_prompts"]) + traffic["check_answer"] <= max(traffic["check_widths"])
    assert all(w % traffic["check_block"] == 0 for w in traffic["check_widths"])
