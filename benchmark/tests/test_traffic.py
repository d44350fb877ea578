"""The traffic generators: the same requests for the same seed, other ones
for another, and for every seed the same set of sizes and gaps."""
import numpy as np
import pytest

from benchmark import harness, serve_common

MIXES = ["chat-closed", "chat-open"]
N = 216          # as many as one 40 s window of chat-open holds


def mix(name):
    return harness.load_json(f"{harness.HERE}/traffic/{name}.json")


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_requests_other_seed_other_order(name):
    t = mix(name)
    n = N
    a, b, c = (serve_common.RequestStream(t, n, 50257, s) for s in (7, 7, 2147483700))
    for i in (0, 1, n - 1, n, 3 * n + 5):
        pa, aa = a(i)
        pb, ab = b(i)
        assert aa == ab and np.array_equal(pa, pb)
    assert any(len(a(i)[0]) != len(c(i)[0]) for i in range(n))
    # every seed offers the same multiset of (prompt, answer) lengths
    sizes = lambda s: sorted((len(s(i)[0]), s(i)[1]) for i in range(n))
    assert sizes(a) == sizes(c)


@pytest.mark.parametrize("name", MIXES)
def test_lengths_keep_inside_the_mix_and_the_model(name):
    t = mix(name)
    prompts, answers = serve_common.length_pool(t, N)
    assert prompts.min() >= t["prompt"]["min"] and prompts.max() <= t["prompt"]["max"]
    assert answers.min() >= t["answer"]["min"] and answers.max() <= t["answer"]["max"]
    assert (prompts + answers).max() <= t["max_total"] <= 1024
    assert abs(np.median(prompts) - t["prompt"]["median"]) < 0.25 * t["prompt"]["median"]
    small, cut = serve_common.length_pool(t, N, max_total=t["check_max_total"],
                                          answer_max=t["check_answer_max"])
    assert (small + cut).max() <= t["check_max_total"] and cut.max() <= t["check_answer_max"]


def test_arrivals_repeat_for_a_seed_and_every_window_holds_the_same():
    t = dict(mix("chat-open"), rate_per_s=20.0)
    n = 20 * 5                         # a 5 s window
    a, b, c = (serve_common.arrival_times(t, n, s, 23.0) for s in (1, 1, 2))
    assert np.array_equal(a, b) and not np.array_equal(a[:50], c[:50])
    assert np.all(np.diff(a) > 0) and a[-1] < 23.0
    # one pass lasts exactly n / rate seconds, so any stretch of that length
    # holds n arrivals, give or take the one on its edge
    for start in (0.0, 3.3, 17.9):
        assert abs(int(((a >= start) & (a < start + 5.0)).sum()) - n) <= 1
    gaps = lambda x: sorted(np.round(np.diff(np.concatenate([[0.0], x]))[:n], 9))
    long_a, long_c = (serve_common.arrival_times(t, n, s, 1e3) for s in (1, 2))
    assert gaps(long_a) == gaps(long_c)
