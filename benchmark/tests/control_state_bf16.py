"""By hand, on the chip: the cell's two limits read with the state KEPT IN
BFLOAT16, at the cell's own size. The nearest precision below the one the
configuration states has to come out as not correct by one of the cell's
limits; on the CPU stand-in it does (tests/test_brumby_serving.py), and
this reads it where the limits were set:

    python3 -m benchmark.tests.control_state_bf16 --seed 3000000301

It runs the cell's driver as `run.py` does, with a shorter window, on an
engine whose programs round every lane they touch to bfloat16 after each
update (the prefill chunk's carry-out, the decode step's new state) and
whose check program does the same: what a bfloat16 pool would hold, in the
float32 array the kernel is compiled for. It prints the driver's own log
(the worst logit gap, the retention path's error, the verdict) and exits 0
when the verdict is NOT correct, 1 when the fault passed.
"""
import argparse
import os
import sys

from benchmark import harness


def keep_state_in_bfloat16():
    import jax.numpy as jnp
    from jax import lax

    from paddle_tpu.serving.decode import RetentionPrograms

    chunk, step = RetentionPrograms._state_chunk, RetentionPrograms._state_step

    def rounded(state, li, slot_ids):
        """The lanes `slot_ids` of layer `li`, one at a time, in place."""
        def one(i, state):
            at = (li, slot_ids[i]) + (jnp.zeros((), jnp.int32),) * (state.ndim - 2)
            lane = lax.dynamic_slice(state, at, (1, 1) + state.shape[2:])
            # not `astype` there and back: XLA may drop that pair
            # (xla_allow_excess_precision), and on the chip it does
            return lax.dynamic_update_slice(
                state, lax.reduce_precision(lane, exponent_bits=8, mantissa_bits=7), at)
        return lax.fori_loop(0, slot_ids.shape[0], one, state)

    def state_chunk(self, state, li, slot, *rest):
        y, state = chunk(self, state, li, slot, *rest)
        return y, rounded(state, li, slot[None])

    def state_step(self, state, li, slot_ids, *rest):
        y, state = step(self, state, li, slot_ids, *rest)
        return y, rounded(state, li, slot_ids)

    RetentionPrograms._state_chunk, RetentionPrograms._state_step = state_chunk, state_step


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, default=5.0)
    parser.add_argument("--workload", default="serve-brumby14b-gen-saturated")
    args = parser.parse_args(argv)

    from benchmark import run
    from paddle_tpu.compile_cache.jax_cache import enable_jax_cache

    manifest = harness.load_json(os.path.join(harness.ROOT, "BENCHMARK.json"))
    _, config, traffic = run.find_cell(manifest, args.workload)
    enable_jax_cache()
    keep_state_in_bfloat16()
    driver = run.load_module("drivers", traffic["driver"])
    result = driver.run(config, traffic, args.seed, args.seconds, False)
    harness.log(f"control, state kept in bfloat16: correct {result['correct']}")
    return 1 if result["correct"] else 0


if __name__ == "__main__":
    sys.exit(main())
