"""The warm-start contract on the one compile cache, JAX's persistent one.

A restarted trainer or serving replica builds its objects anew; what it
must not pay again is the XLA compile. Each case below builds one of this
repo's compile sites and runs it, drops every in-memory executable
(``jax.clear_caches()``), builds a FRESH object and runs again: the second
build records no cache miss and at least one hit, and returns the first
build's outputs bit for bit (it ran the same executable). The cache is
pointed at ``tmp_path`` with the thresholds ``enable_jax_cache`` sets, and
hits and misses are counted by the benchmark's own ``CacheCounter``.
"""
import os
import pathlib
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.compilation_cache import compilation_cache as jcc

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import paddle_tpu as paddle  # noqa: E402
import paddle_tpu.nn as nn  # noqa: E402
from benchmark.harness import CacheCounter  # noqa: E402
from paddle_tpu import serving  # noqa: E402
from paddle_tpu.core import kernel_cache  # noqa: E402
from paddle_tpu.profiler.pipeline import ServingStats  # noqa: E402

_CACHE_OPTIONS = {"jax_persistent_cache_min_compile_time_secs": 0.0,
                  "jax_persistent_cache_min_entry_size_bytes": -1}


def _restart(counter):
    """What a new process starts without: every in-memory executable, and
    a count of what it found in the cache."""
    jax.clear_caches()
    kernel_cache.clear()
    counter.hits = counter.misses = 0


@pytest.fixture
def cache(tmp_path):
    """JAX's persistent cache in an empty directory of this test's own,
    every executable kept however quick its compile; a counter of its hits
    and misses. The session's cache comes back afterwards."""
    options = dict(_CACHE_OPTIONS,
                   jax_compilation_cache_dir=str(tmp_path / "jax_cache"))
    before = {name: getattr(jax.config, name) for name in options}
    for name, value in options.items():
        jax.config.update(name, value)
    jcc.reset_cache()
    counter = CacheCounter()
    _restart(counter)
    try:
        yield counter
    finally:
        jax.monitoring.unregister_event_listener(counter._on_event)
        for name, value in before.items():
            jax.config.update(name, value)
        jcc.reset_cache()


def _entries(cache_dir):
    return sorted(p for p in pathlib.Path(cache_dir).iterdir()
                  if p.name.endswith("-cache"))


# ------------------------------------------------------------------ sites
def _tiny_gpt(**overrides):
    from paddle_tpu.models.gpt import GPTForCausalLM, gpt_tiny

    paddle.seed(0)
    config = dict(num_hidden_layers=1, hidden_size=32, num_attention_heads=2,
                  max_position_embeddings=32)
    config.update(overrides)
    model = GPTForCausalLM(gpt_tiny(**config))
    model.eval()
    return model


def _decode_engine(model, **kw):
    kw.setdefault("max_slots", 2)
    kw.setdefault("max_seq", 16)
    kw.setdefault("seq_buckets", [8])
    kw.setdefault("prefill_max_batch", 1)
    kw.setdefault("stats", ServingStats())
    return serving.DecodeEngine(model, **kw)


def _serve_tokens(model, **kw):
    eng = _decode_engine(model, **kw).warmup()
    try:
        prompt = np.arange(3, 9, dtype=np.int32)
        tokens = np.asarray(eng.generate("a", prompt, max_new_tokens=5))
        assert eng.compiles_after_warmup == 0
        assert eng.programs.traces == len(eng.programs.rungs)
        return [tokens]
    finally:
        eng.shutdown(drain=True)


def _to_static(tmp_path):
    w = paddle.Tensor(np.full((8, 8), 2.0, np.float32), stop_gradient=True)
    f = paddle.jit.to_static(lambda x: paddle.matmul(x, w) + 1)
    return [f(paddle.ones([4, 8])).numpy()]


def _guarded_family(tmp_path):
    from paddle_tpu.jit.functionalize import functionalize

    @functionalize
    def g(x):
        if paddle.sum(x) > 0:
            return x * 2
        return x * 3

    outs = [g(paddle.ones([4])).numpy(), g(paddle.full([4], -1.0)).numpy()]
    assert g.stats["compiled_steps"] == 2  # one program a specialization
    return outs


def _train_step(tmp_path):
    """A restarted trainer's first useful step: its loss and the
    parameters it leaves."""
    from paddle_tpu.jit.api import TrainStep

    paddle.seed(0)
    model = nn.Linear(8, 4)
    opt = paddle.optimizer.SGD(learning_rate=0.1,
                               parameters=model.parameters())
    crit = nn.MSELoss()
    step = TrainStep(model=model, optimizer=opt,
                     loss_fn=lambda x, y: crit(model(x), y))
    x = paddle.Tensor(np.ones((2, 8), np.float32), stop_gradient=True)
    y = paddle.Tensor(np.zeros((2, 4), np.float32), stop_gradient=True)
    loss = step(x, y).numpy()
    return [loss] + [p.numpy() for p in model.parameters()]


def _eager_kernel(tmp_path):
    a = paddle.Tensor(np.full((8, 8), 0.5, np.float32), stop_gradient=True)
    out = paddle.matmul(a, a).numpy()
    assert any(not e.has_vjp for e in kernel_cache._cache.values())
    return [out]


def _eager_kernel_vjp(tmp_path):
    x = paddle.Tensor(np.full((4, 4), 3.0, np.float32), stop_gradient=False)
    out = paddle.matmul(x, x)
    out.backward()
    assert any(e.has_vjp for e in kernel_cache._cache.values())
    return [out.numpy(), x.grad.numpy()]


def _export_mlp(tmp_path):
    from paddle_tpu.static import InputSpec

    paddle.seed(0)
    net = nn.Sequential(nn.Linear(8, 16), nn.ReLU(), nn.Linear(16, 4))
    net.eval()
    prefix = str(tmp_path / "mlp" / "model")
    os.makedirs(os.path.dirname(prefix), exist_ok=True)
    paddle.jit.save(net, prefix, input_spec=[InputSpec([None, 8], "float32")])
    return prefix


def _bucket_ladder(tmp_path):
    from paddle_tpu.inference import Config, Predictor

    p = Predictor(Config(_export_mlp(tmp_path)))
    p.set_batch_ladder([1, 2, 4])
    p.warmup_ladder()
    assert p.compile_count == 3  # one trace a rung, on either build
    x = np.random.RandomState(0).randn(3, 8).astype(np.float32)
    outs = p.run_many([x])
    assert p.compile_count == 3
    return outs


def _serving_engine(tmp_path):
    eng = serving.ServingEngine(_export_mlp(tmp_path), buckets=[1, 2, 4],
                                stats=ServingStats()).warmup()
    try:
        rs = np.random.RandomState(0)
        outs = [eng.run(tenant, rs.randn(n, 8).astype(np.float32))[0]
                for tenant, n in (("a", 1), ("b", 3), ("a", 4))]
        assert eng.compiles_after_warmup == 0
        return outs
    finally:
        eng.shutdown(drain=True)


def _decode_paged(tmp_path):
    return _serve_tokens(_tiny_gpt(), kv_mode="paged", page_size=16)


def _decode_speculating(tmp_path):
    return _serve_tokens(_tiny_gpt(), kv_mode="paged", page_size=16,
                         speculate_k=2, spec_draft_layers=1)


def _decode_slots(tmp_path):
    return _serve_tokens(_tiny_gpt(), kv_mode="slots")


def _decode_state_lanes(tmp_path):
    from paddle_tpu.models import BrumbyForCausalLM, brumby_tiny

    paddle.seed(5)
    model = BrumbyForCausalLM(brumby_tiny())
    model.eval()
    return _serve_tokens(model, seq_buckets=[8], max_seq=32)


_SITES = {
    "to_static": _to_static,
    "guarded_family": _guarded_family,
    "train_step": _train_step,
    "eager_kernel": _eager_kernel,
    "eager_kernel_vjp": _eager_kernel_vjp,
    "bucket_ladder": _bucket_ladder,
    "serving_engine": _serving_engine,
    "decode_paged": _decode_paged,
    "decode_speculating": _decode_speculating,
    "decode_slots": _decode_slots,
    "decode_state_lanes": _decode_state_lanes,
}


@pytest.mark.parametrize("site", sorted(_SITES))
def test_second_build_runs_the_first_builds_executables(site, cache, tmp_path):
    build_and_run = _SITES[site]
    first = build_and_run(tmp_path)
    assert cache.misses > 0  # the empty directory held nothing
    _restart(cache)
    second = build_and_run(tmp_path)
    assert cache.misses == 0
    assert cache.hits > 0
    assert len(first) == len(second)
    for a, b in zip(first, second):
        np.testing.assert_array_equal(a, b)


# ----------------------------------------------------------- a bad cache
@pytest.mark.parametrize("site", ["decode_paged", "train_step"])
def test_truncated_entries_fall_back_to_a_compile(site, cache, tmp_path):
    """A replica or a trainer survives a rotted cache directory: what
    cannot be read is compiled again, and the result is the same."""
    build_and_run = _SITES[site]
    first = build_and_run(tmp_path)
    victims = _entries(jax.config.jax_compilation_cache_dir)
    assert victims
    for path in victims:
        with open(path, "r+b") as f:
            f.truncate(16)
    _restart(cache)
    with pytest.warns(UserWarning, match="Error reading persistent"):
        second = build_and_run(tmp_path)
    assert cache.hits == 0
    for a, b in zip(first, second):
        np.testing.assert_array_equal(a, b)


def test_unwritable_cache_directory_degrades_to_compiling(
        cache, tmp_path, monkeypatch):
    """A read-only cache directory costs the compile and nothing else:
    what it already holds is still served, what it cannot take is
    compiled and the call returns. (The tests run as root, to whom chmod
    is no barrier: the write itself is made to fail as a read-only file
    system fails it.)"""
    first = _to_static(tmp_path)
    held = len(_entries(jax.config.jax_compilation_cache_dir))
    assert held > 0

    def denied(self, data):
        raise PermissionError(30, "Read-only file system", str(self))

    # jax writes an entry through etils' path class where etils is installed
    from etils import epath

    monkeypatch.setattr(epath.Path, "write_bytes", denied)
    _restart(cache)
    second = _to_static(tmp_path)
    assert cache.misses == 0 and cache.hits > 0
    np.testing.assert_array_equal(first[0], second[0])
    with pytest.warns(UserWarning, match="Error writing persistent"):
        new = _guarded_family(tmp_path)
    assert cache.misses > 0
    assert len(_entries(jax.config.jax_compilation_cache_dir)) == held
    np.testing.assert_array_equal(new[0], np.full([4], 2.0, np.float32))


# --------------------------------------------------------------- the key
def test_layer_norm_epsilon_is_in_the_key(cache, tmp_path):
    """Epsilon is a constant baked into the traced programs, so two
    engines whose models differ in nothing else must not share an
    executable: the key JAX derives from the program tells them apart."""
    a = _decode_engine(_tiny_gpt()).warmup()
    _restart(cache)
    b = _decode_engine(_tiny_gpt(layer_norm_epsilon=1e-3)).warmup()
    try:
        assert cache.misses > 0
        x = jnp.asarray(np.random.RandomState(0).randn(1, 32), jnp.float32)
        logits = [np.asarray(e.programs._logits_head(e.programs.params, x))
                  for e in (a, b)]
        assert logits[0].shape == logits[1].shape
        assert np.abs(logits[0] - logits[1]).max() > 1e-4
    finally:
        a.shutdown(drain=True)
        b.shutdown(drain=True)


# ------------------------------------------------- one cache, by deletion
def test_no_compile_cache_flag_is_defined():
    from paddle_tpu.base import flags

    assert [name for name in flags.get_flags()
            if name.startswith("compile_cache")] == []


def test_compile_cache_package_holds_jax_cache_alone():
    import pkgutil

    from paddle_tpu.compile_cache import jax_cache

    package = sys.modules[jax_cache.__package__]
    assert [m.name for m in pkgutil.iter_modules(package.__path__)] == [
        "jax_cache"]
    assert [n for n in vars(package) if not n.startswith("__")] == [
        "jax_cache"]
