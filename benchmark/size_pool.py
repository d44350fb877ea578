"""Compile the serving engine's largest programs for a described TPU v5e,
in a sandbox with no chip, and print the compiler's memory analysis.

    JAX_PLATFORMS=cpu python benchmark/size_pool.py benchmark/configs/gpt2-small.json 32 64 128

For each lane count: the top decode rung (lanes x the widest block table) and
the top prefill rung (prefill_max_batch x the longest seq bucket), with the
weights and both pool arrays as arguments, donated as on the chip. A lane
count fits when both compile; what the compiler refuses, it refuses here as
it would on the chip ("Used 16.52G of 15.75G hbm"). Nothing runs, so this
says nothing about time, and a lane count that compiles is not yet one worth
deploying: at 128 lanes the compiler fits gpt2-small's top decode rung by a
path that took 790 ms a step on the chip against 81 ms at 64 (PERF.md,
PR 24). `configs/<name>.json` records the choice under `engine.max_slots`.
"""
from __future__ import annotations

import json
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def top_rungs(programs) -> dict:
    """The engine's largest decode and prefill programs, by rung key."""
    return {"decode": ("decode", programs.decode_rungs[-1], programs.table_rungs[-1]),
            "prefill": ("prefill", programs.prefill_batch_rungs[-1],
                        programs.seq_ladder[-1])}


def rung_memory(programs, key, place):
    """The compiler's memory analysis of one rung's program, compiled with
    the weights and both pool arrays as arguments, the pool donated as on the
    chip; `place` maps the arguments to shapes on the described device. The
    engine has no public handle on a rung's program before it is warmed, so
    this reaches for its private ones (PERF.md, Open questions)."""
    import jax
    import numpy as np

    fn = programs._decode_fn if key[0] == "decode" else programs._prefill_fn
    args = place((programs.params, programs.pool.k, programs.pool.v,
                  *(np.asarray(a) for a in programs._zero_args(key))))
    return jax.jit(fn, donate_argnums=(1, 2)).lower(*args).compile().memory_analysis()


def analyse(config: dict, lanes: int) -> dict:
    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from benchmark import serve_common
    from paddle_tpu import serving

    model = serve_common.serving_model(config, seed=0)
    args = dict(config["engine"], max_slots=lanes)
    engine = serving.DecodeEngine(model, **args)
    programs = engine.programs
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])

    def described(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=chip), tree)

    out = {"lanes": lanes, "pool_shape": list(programs.pool.k.shape),
           "pool_nominal_bytes": int(programs.pool.device_bytes())}
    for kind, key in top_rungs(programs).items():
        try:
            m = rung_memory(programs, key, described)
            out[kind] = {"rung": list(key[1:]),
                         "argument_bytes": int(m.argument_size_in_bytes),
                         "output_bytes": int(m.output_size_in_bytes),
                         "alias_bytes": int(m.alias_size_in_bytes),
                         "temp_bytes": int(m.temp_size_in_bytes),
                         "code_bytes": int(m.generated_code_size_in_bytes)}
        except Exception as e:  # noqa: BLE001 - the compiler's refusal is the answer
            out[kind] = {"rung": list(key[1:]), "refused": str(e)[:600]}
    engine.shutdown(drain=False)
    return out


def main(argv):
    with open(argv[1]) as f:
        config = json.load(f)
    for lanes in map(int, argv[2:]):
        print(json.dumps(analyse(config, lanes)), flush=True)


if __name__ == "__main__":
    main(sys.argv)
