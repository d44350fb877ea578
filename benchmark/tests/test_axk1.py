"""What PR 34 adds to the benchmark, on the CPU: the `closed_loop_pages`
driver on the stand-in configuration, the latent and expert readers on
captures encoded by hand with the new regions, `flops_axk1` against hand
counts, and the manifest with the new configuration, cell and metrics."""
import os

import pytest

from benchmark import check, flops_axk1, harness, run, scopes
from benchmark.drivers import closed_loop_pages
from benchmark.tests.test_scopes_and_spans import FakeTrace, encode_capture

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "serve-axk1-docqa-saturated"
SMALL = dict(prompt={"median": 24, "sigma": 0.8, "min": 4, "max": 120},
             answer={"median": 24, "sigma": 0.5, "min": 8, "max": 48}, max_total=200,
             distinct_requests=32, ramp_seconds=0.5, ramp_completions=2, trace_seconds=1,
             check_prompts=[68, 100, 5, 32, 33, 150], check_answer=12, check_widths=[64, 192])


def stand_in():
    return harness.load_json(os.path.join(HERE, "tiny-axk1.json"))


def mix(**changes):
    traffic = harness.load_json(f"{harness.HERE}/traffic/docqa-closed.json")
    traffic["latent_check"] = dict(traffic["latent_check"], ragged=5)
    return dict(traffic, **SMALL, **changes)


def published():
    return harness.load_json(f"{harness.HERE}/configs/ax-k1.json")


# ------------------------------------------------------------------ driver
@pytest.mark.parametrize("trace", [False, True])
def test_closed_loop_pages_on_the_stand_in(trace):
    r = closed_loop_pages.run(stand_in(), mix(), 2147483700, 2.0, trace)
    assert r["correct"] and r["failed"] == 0 and r["attempted"] > 5
    f = r["facts"]
    assert f["lanes"] == 4 and 0 < f["pool_peak_share"] <= 100.0     # pages over pages
    assert f["layers"] == 3 and f["sparse_layers"] == 2 and f["held_experts"] == 4
    assert r["measured"]["serve_tokens_per_s"] > 0 and r["measured"]["setup_s"] > 0
    if trace:
        steps = [a for name, _, _, a in r["spans"] if name == "serving.decode"]
        assert {a["kind"] for a in steps} == {"prefill", "decode"}
        assert all({"pairs", "experts_hit"} <= set(a) for a in steps)
        chunked = [a for a in steps if a["kind"] == "prefill"]
        assert all({"chunk", "chunks", "tokens"} <= set(a) for a in chunked)
        assert any(a["chunks"] > 1 for a in chunked)
        assert all({"pages_live", "pages_table"} <= set(a) for a in steps if a["kind"] == "decode")


# ------------------------------------------------------------------- flops
def test_flops_axk1_against_hand_counts():
    c = published()
    attention = 7168 * 1536 + 1536 * 64 * 192 + 7168 * 576 + 512 * 64 * 256 + 8192 * 7168
    assert flops_axk1.attention_parameters(c) == attention == 101_122_048
    assert flops_axk1.expert_parameters(c) == 3 * 7168 * 2048 == 44_040_192
    assert flops_axk1.dense_ffn_parameters(c) == 3 * 7168 * 18432
    sparse = attention + 7168 * 192 + 1.5 * 44_040_192                # half a held expert a token
    assert flops_axk1.sparse_layer_parameters_per_token(c) == sparse
    assert flops_axk1.prompt_flops_per_token(c) \
        == 2 * (attention + 3 * 7168 * 18432 + 6 * sparse) == pytest.approx(3.02e9, rel=5e-3)
    assert flops_axk1.expert_bytes(c) == 88_080_384 and flops_axk1.expert_flops_per_pair(c) == 88_080_384
    # a row: 1152 bytes at 819 GB/s is 1.41 ns, its 139,264 operations 0.71 ns: bytes bound it
    assert flops_axk1.least_seconds(139_264, 1152, "TPU v5 lite") == pytest.approx(1152 / 819e9)


# ----------------------------------------------------------------- readers
DECODE = "jit(_decode_fn)/decode/while/body/closed_call/"
PREFILL = "jit(_prefill_fn)/prefill/while/body/closed_call/"
KERNEL = "attn/core/jit(latent_paged_attention)/latent_paged_attn/pallas_call:"
OPS = [  # microseconds: one prefill chunk, then two decode steps
    (100.0, 200.0, "%fusion.1 = bf16[8] fusion(%a), kind=kOutput", PREFILL + "while/body/attn/core/dot_general:"),
    (300.0, 60.0, "%fusion.2 = bf16[8] fusion(%b), kind=kOutput", PREFILL + "while/body/attn/expand/dot_general:"),
    (360.0, 40.0, "%fusion.3 = s32[8] fusion(%c), kind=kLoop", PREFILL + "moe/experts/sort:"),
    (400.0, 100.0, "%ragged-dot-none = bf16[8] custom-call(%d)", "ragged-dot-none"),
    (1000.0, 50.0, "%latent_paged_attn.4 = bf16[8] custom-call(%e)", DECODE + KERNEL),
    (1050.0, 10.0, "%fusion.5 = s32[8] fusion(%f), kind=kLoop", DECODE + "moe/experts/sort:"),
    (1060.0, 5.0, "%ragged-dot-metadata = s32[8] custom-call(%g)", "ragged-dot-metadata"),
    (1065.0, 35.0, "%ragged-dot-none = bf16[8] custom-call(%d)", "ragged-dot-none"),
    (1100.0, 20.0, "%fusion.6 = bf16[8] fusion(%h), kind=kOutput", DECODE + "moe/shared/dot_general:"),
    (2000.0, 50.0, "%latent_paged_attn.4 = bf16[8] custom-call(%e)", DECODE + KERNEL),
    (2050.0, 30.0, "%ragged-dot-none = bf16[8] custom-call(%d)", "ragged-dot-none"),
]
SPANS = [
    ("serving.decode", 50e-6, 600e-6, {"kind": "prefill", "rung": (1, 8), "lanes": 1, "chunk": 1,
                                        "chunks": 2, "tokens": 6, "pairs": 5, "experts_hit": 4}),
    ("serving.decode", 900e-6, 1200e-6, {"kind": "decode", "rung": (4, 8), "lanes": 3,
                                          "pages_live": 7, "pages_table": 32, "pairs": 9,
                                          "experts_hit": 6}),
    ("serving.decode", 1900e-6, 2200e-6, {"kind": "decode", "rung": (4, 8), "lanes": 4,
                                           "pages_live": 9, "pages_table": 32, "pairs": 7,
                                           "experts_hit": 5}),
    ("serving.decode", 2900e-6, 3300e-6, {"kind": "decode", "rung": (4, 8), "lanes": 4,
                                           "pages_live": 9, "pages_table": 32, "pairs": 7,
                                           "experts_hit": 5}),   # past the window
]
FACTS = {"device_kind": "TPU v5 lite", "chips": 1, "lanes": 4, "layers": 3, "sparse_layers": 2,
         "held_experts": 4, "page_size": 8, "latent_row_bytes": 80, "latent_flops_per_row": 576,
         "expert_bytes": 12288, "expert_flops_per_pair": 12288,
         "prompt_flops_per_token": 2e7, "answer_flops_per_token": 3e7}


@pytest.fixture()
def read(tmp_path, monkeypatch):
    def go(name, facts=FACTS, ops=OPS, spans=SPANS):
        path = tmp_path / f"c{len(os.listdir(tmp_path))}.xplane.pb"
        path.write_bytes(encode_capture(ops))
        monkeypatch.setattr(scopes, "capture_path", lambda: str(path))
        return run.load_module("layers", name).read(FakeTrace(ops, 0.0, 3000e-6), spans, facts)
    return go


def test_latent_attention_readers(read):
    decode_busy = (50 + 10 + 5 + 35 + 20) + (50 + 30)
    assert read("latent_attn_share") == pytest.approx(100 * 100 / decode_busy)
    # live rows: at least (pages - lanes) x page + lanes, a layer: (4 x 8 + 3) and (5 x 8 + 4), x 3
    rows = 3 * ((7 - 3) * 8 + 3) + 3 * ((9 - 4) * 8 + 4)
    assert read("latent_attn_roofline") == pytest.approx(100 * (rows * 80 / 819e9) / 100e-6)
    prefill_busy = 200 + 60 + 40 + 100
    assert read("attn_expand_share") == pytest.approx(100 * 60 / prefill_busy)


def test_expert_readers_count_the_grouped_product_that_carries_no_scope(read):
    busy = sum(d for _, d, _, _ in OPS)
    experts = (40 + 100) + (10 + 5 + 35) + 30
    assert read("moe_expert_share") == pytest.approx(100 * experts / busy)
    # decode is bound by the hit experts' bytes, the chunk too at this size
    least = (6 + 5 + 4) * 12288 / 819e9
    assert read("moe_expert_roofline") == pytest.approx(100 * least / (experts * 1e-6))
    assert read("moe_pairs_per_expert") == pytest.approx((9 + 7) / 2 / (2 * 4))
    # the accepted reader calls the grouped product unscoped: it has no scope
    assert read("unscoped_share_flat") == pytest.approx(100 * (100 + 5 + 35 + 30) / busy)


def test_latent_readers_read_nothing_of_a_program_without_them(read, monkeypatch):
    bare = [(n, a, b, {k: v for k, v in args.items() if k in ("kind", "rung", "lanes")})
            for n, a, b, args in SPANS]
    names = ("latent_attn_share", "latent_attn_roofline", "attn_expand_share",
             "moe_expert_share", "moe_expert_roofline", "moe_pairs_per_expert")
    assert read("moe_expert_roofline", spans=bare) is None
    assert read("moe_pairs_per_expert", spans=bare) is None
    # a program without the vocabulary (the parent): the term itself is missing
    monkeypatch.setattr(scopes, "term", lambda name: None)
    for name in names[:5]:
        assert read(name) is None, name
    assert read("moe_pairs_per_expert", {"device_kind": "TPU v5 lite"}, spans=bare) is None


# ---------------------------------------------------------------- manifest
def test_manifest_has_the_cell_and_the_accepted_cells_are_as_they_were():
    manifest = harness.load_json(os.path.join(harness.ROOT, "BENCHMARK.json"))
    # check.py's width pattern holds `hidden`, so it calls the source's depth
    # key a width, for this configuration as for Brumby's (PERF.md section 7)
    assert check.check(manifest) == [
        "config brumby-14b-base: reduced names a width, 'num_hidden_layers'",
        "config ax-k1: reduced names a width, 'num_hidden_layers'"]
    assert [w["name"] for w in manifest["workloads"]][-1] == CELL
    assert manifest["workloads"][-1] == {
        "name": CELL, "config": "ax-k1", "traffic": "docqa-closed", "chips": 1,
        "why": manifest["workloads"][-1]["why"]}
    assert manifest["configs"][-1]["reduced"] == ["num_hidden_layers", "n_routed_experts",
                                                  "vocab_size"]
    end = {m["name"] for m in run.metrics_of(manifest, "end_to_end", CELL)}
    assert end == {"serve_tokens_per_s", "setup_s"}
    mine = {m["name"] for m in run.metrics_of(manifest, "per_layer", CELL)}
    assert mine == {
        "serve_mfu.axk1", "decode_step_ms.axk1", "prefill_share.axk1", "batch_occupancy.axk1",
        "idle_share.axk1", "sched_host_ms.axk1", "sample_share.axk1", "unscoped_share_flat.axk1",
        "pool_peak_share.axk1", "cache_misses.axk1", "prefill_chunks_per_s.axk1",
        "latent_attn_share", "latent_attn_roofline", "moe_expert_share", "moe_expert_roofline",
        "moe_pairs_per_expert", "attn_expand_share"}
    for m in run.metrics_of(manifest, "per_layer", CELL):
        assert os.path.isfile(os.path.join(harness.HERE, "layers", m["name"].split(".")[0] + ".py"))
    for cell in ("train-gpt2m-1chip", "serve-gpt2s-chat-saturated", "serve-gpt2s-chat-steady",
                 "serve-brumby14b-gen-saturated"):
        assert not {m["name"] for m in run.metrics_of(manifest, "per_layer", cell)} & mine


def test_configuration_keeps_every_published_width():
    c = published()
    catalog = {   # the catalog row's `config`, key for key
        "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 1, "hidden_act": "silu",
        "hidden_size": 7168, "intermediate_size": 18432, "kv_lora_rank": 512,
        "max_position_embeddings": 131072, "model_type": "axk1", "moe_intermediate_size": 2048,
        "moe_layer_freq": 1, "n_group": 8, "n_routed_experts": 192, "n_shared_experts": 1,
        "norm_topk_prob": True, "num_attention_heads": 64, "num_experts_per_tok": 8,
        "num_hidden_layers": 61, "num_key_value_heads": 64, "q_lora_rank": 1536,
        "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
        "rope_theta": 10000,
        "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 32, "mscale": 1,
                         "mscale_all_dim": 1, "original_max_position_embeddings": 4096,
                         "type": "yarn"},
        "routed_scaling_factor": 2.5, "scoring_func": "sigmoid", "seq_aux": True,
        "tie_word_embeddings": False, "topk_group": 4, "topk_method": "none",
        "v_head_dim": 128, "vocab_size": 163840}
    cut = {"num_hidden_layers": 7, "n_routed_experts": 12, "vocab_size": 20480}
    assert {k: c[k] for k in catalog} == dict(catalog, **cut)
    assert c["published"] == {k: catalog[k] for k in cut} and c["reduced"] == list(cut)
    assert c["expert_share"] == [0, 16] and "16 chips share each layer" in c["deployment"]
    assert c["n_routed_experts"] * c["expert_share"][1] == 192
    assert c["vocab_size"] * 8 == 163840 == 8 * c["tokenizer_vocab"]
    traffic = harness.load_json(f"{harness.HERE}/traffic/docqa-closed.json")
    assert traffic["prompt"] == {"median": 3072, "sigma": 0.8, "min": 512, "max": 16384}
    assert traffic["answer"] == {"median": 512, "sigma": 0.6, "min": 64, "max": 2048}
    assert traffic["max_total"] == c["engine"]["max_seq"] == 18432
    assert traffic["clients_per_lane"] * c["engine"]["max_slots"] == 128
    assert traffic["check_prompts"] == [4100, 6000, 640, 2048, 2049, 9000]
    assert max(traffic["check_prompts"]) + traffic["check_answer"] <= max(traffic["check_widths"])
