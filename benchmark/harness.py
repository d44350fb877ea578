"""What every driver of the benchmark shares: the process clock, the model
built from a configuration file, compile-cache counters, host spans, the
profiler capture and the small arithmetic (percentiles) of the yardstick.

Nothing here knows a cell, a configuration, a traffic mix or a metric by name.
"""
from __future__ import annotations

import contextlib
import glob
import json
import os
import shutil
import time

from benchmark.trace_reduce import SYNC_NAME

# setup_s runs from here: run.py imports this module before jax or the program.
PROCESS_START = time.perf_counter()

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HERE = os.path.join(ROOT, "benchmark")
# A fixed place inside the checkout (listed in .gitignore); emptied by each
# traced run, so one trace is on disk at a time.
TRACE_DIR = os.path.join(ROOT, ".bench_trace")


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def percentile(values, q: float) -> float:
    """The q-th percentile (0-100) by linear interpolation between order
    statistics: the value at rank q/100 * (n - 1) of the sorted sample."""
    xs = sorted(float(v) for v in values)
    if not xs:
        raise ValueError("percentile of an empty sample")
    rank = q / 100.0 * (len(xs) - 1)
    lo = int(rank)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (rank - lo)


# ------------------------------------------------------------------- model
# The configuration files carry the source's own key names (a Hugging Face
# GPT-2 config.json); this is the one place they meet the program's.
_GPT_KEYS = {
    "vocab_size": "vocab_size",
    "n_embd": "hidden_size",
    "n_layer": "num_hidden_layers",
    "n_head": "num_attention_heads",
    "n_positions": "max_position_embeddings",
    "layer_norm_epsilon": "layer_norm_epsilon",
    "initializer_range": "initializer_range",
    "resid_pdrop": "hidden_dropout_prob",
    "attn_pdrop": "attention_dropout_prob",
}


def gpt_config(config: dict, **overrides):
    from paddle_tpu.models import gpt as gpt_models

    if config.get("model_type") != "gpt2":
        raise ValueError(f"configuration {config.get('name')!r}: only "
                         "model_type 'gpt2' has a builder here")
    if config.get("embd_pdrop", 0.0) != config.get("resid_pdrop", 0.0):
        raise ValueError("the program has one dropout rate for embeddings "
                         "and residuals: embd_pdrop must equal resid_pdrop")
    kwargs = {ours: config[theirs] for theirs, ours in _GPT_KEYS.items()}
    if config.get("n_inner"):
        kwargs["intermediate_size"] = config["n_inner"]
    kwargs.update(overrides)
    return gpt_models.GPTConfig(**kwargs)


def build_model(config: dict, seed: int, **overrides):
    """The program's own model class with the program's own initializers,
    seeded from --seed. Weights are float32 until a driver casts them."""
    import paddle_tpu as paddle
    from paddle_tpu.models import GPTForCausalLM

    paddle.seed(int(seed))
    return GPTForCausalLM(gpt_config(config, **overrides))


def matmul_parameters(config: dict) -> int:
    """Parameters that a token is multiplied by: the blocks' four matrices
    and the (tied) output head. Embedding look-ups and biases are no
    matrix multiplications."""
    h = config["n_embd"]
    inner = config.get("n_inner") or 4 * h
    return config["n_layer"] * (4 * h * h + 2 * h * inner) + config["vocab_size"] * h


# ------------------------------------------------------------ observation
class CacheCounter:
    """Counts JAX's persistent-compilation-cache hits and misses."""

    def __init__(self):
        import jax.monitoring

        self.hits = self.misses = 0
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1


class Spans:
    """Host spans on time.perf_counter, kept in memory: (name, start, end,
    args). Off, `span` costs one attribute test."""

    def __init__(self, enabled: bool):
        self.enabled = bool(enabled)
        self.items = []

    @contextlib.contextmanager
    def span(self, name: str, **args):
        if not self.enabled:
            yield
            return
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self.items.append((name, t0, time.perf_counter(), args))

    def extend_from_tracer(self, tracer, names):
        """Copy the program's own spans (observability.tracing, microseconds
        on the same clock) whose name is in `names`."""
        for e in tracer.to_chrome_trace()["traceEvents"]:
            if e.get("ph") == "X" and e.get("name") in names:
                t0 = e["ts"] / 1e6
                self.items.append((e["name"], t0, t0 + e["dur"] / 1e6,
                                   dict(e.get("args") or {})))


class TraceCapture:
    """One jax.profiler capture into TRACE_DIR. `t_sync` is the host clock at
    an annotation the trace also holds, so that trace_reduce can put device
    times on the host's clock; `t0`/`t1` bound the part of the capture in
    which the profiler was certainly on."""

    def __init__(self):
        self.path = None
        self.t_sync = self.t0 = self.t1 = None

    def start(self):
        import jax

        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        os.makedirs(TRACE_DIR, exist_ok=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0   # the host's Python frames are not read
        jax.profiler.start_trace(TRACE_DIR, profiler_options=options)
        with jax.profiler.TraceAnnotation(SYNC_NAME):
            self.t_sync = time.perf_counter()
        self.t0 = time.perf_counter()

    def stop(self):
        import jax

        self.t1 = time.perf_counter()
        jax.profiler.stop_trace()
        found = glob.glob(os.path.join(TRACE_DIR, "plugins", "profile", "*",
                                       "*.xplane.pb"))
        if not found:
            raise RuntimeError(f"the profiler wrote no .xplane.pb under {TRACE_DIR}")
        self.path = found[0]


def device_facts() -> dict:
    import jax

    devices = jax.devices()
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}


_HELD_BYTES = 0


def note_memory() -> None:
    """Sample what each chip holds now: the buffers in use plus what the
    runtime has reserved for its loaded programs' temporaries. The runtime
    keeps the two apart, and its peak_bytes_in_use counts only the first: with
    the 64-lane engine loaded it reads 3.4 GB in use beside 6.5 GB reserved,
    and a further allocation fails once the two and it pass the limit
    (PERF.md, PR 24). A driver calls this while its programs are loaded."""
    import jax

    global _HELD_BYTES
    for d in jax.local_devices():
        m = d.memory_stats() or {}
        _HELD_BYTES = max(_HELD_BYTES, m.get("bytes_in_use", 0) + m.get("bytes_reserved", 0))


def memory_peak_bytes() -> int:
    """The peak on the fullest chip, from the runtime's own counters: its
    peak of buffers in use, or the most that note_memory saw held, whichever
    is more. Also logs the parts."""
    import jax

    stats = [d.memory_stats() or {} for d in jax.local_devices()]
    in_use = max(m.get("peak_bytes_in_use", 0) for m in stats)
    log(f"memory: peak of buffers in use {in_use}, peak reserved for programs "
        f"{max(m.get('peak_bytes_reserved', 0) for m in stats)}, most held at "
        f"once when sampled {_HELD_BYTES}")
    return int(max(in_use, _HELD_BYTES))


def verdict(checks: dict, detail: str = "") -> bool:
    """`correct`: every named check holds. One that does not is logged."""
    for what, ok in checks.items():
        if not ok:
            log(f"INCORRECT: not true that {what}{detail}")
    return all(checks.values())


def log(message: str) -> None:
    print(f"[bench] {message}", flush=True)
