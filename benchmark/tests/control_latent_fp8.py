"""By hand, on the chip: the cell's two limits read with the latent rows
KEPT IN 8-BIT FLOATS (e4m3: 4 exponent bits, 3 of mantissa), at the cell's own
size. The nearest precision below the one the configuration states (a
bfloat16 pool) has to come out as not correct by one of the cell's limits; on
the CPU stand-in it does, and this reads it where the limits were set:

    python3 -m benchmark.tests.control_latent_fp8 --seed 3000000301

It runs the cell's driver as `run.py` does, with a shorter window, on an
engine whose programs round every latent row to e4m3 before it is written to
the pool (the prefill chunk's rows, the decode step's) and whose check program
does the same: what an 8-bit pool would hold, in the bfloat16 array the kernel
is compiled for. It prints the driver's own log (the worst logit gap, the
attention path's error, the verdict) and exits 0 when the verdict is NOT
correct, 1 when the fault passed.
"""
import argparse
import os
import sys

from benchmark import harness


def keep_rows_in_e4m3():
    from jax import lax

    from paddle_tpu.serving import kv_cache as kvc

    def rounded(write):
        # not `astype` there and back: XLA may drop that pair
        def wrapped(cache, layer, pages, where_or_rows, *rows):
            *head, last = (where_or_rows,) + rows
            return write(cache, layer, pages, *head,
                         lax.reduce_precision(last, exponent_bits=4, mantissa_bits=3))
        return wrapped

    kvc.append_token_paged = rounded(kvc.append_token_paged)   # (cache, layer, pages, offsets, rows)
    kvc.write_chunk_pages = rounded(kvc.write_chunk_pages)     # (cache, layer, pages, rows)


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, default=5.0)
    parser.add_argument("--workload", default="serve-axk1-docqa-saturated")
    args = parser.parse_args(argv)

    from benchmark import run
    from paddle_tpu.compile_cache.jax_cache import enable_jax_cache

    manifest = harness.load_json(os.path.join(harness.ROOT, "BENCHMARK.json"))
    _, config, traffic = run.find_cell(manifest, args.workload)
    enable_jax_cache()
    keep_rows_in_e4m3()
    driver = run.load_module("drivers", traffic["driver"])
    result = driver.run(config, traffic, args.seed, args.seconds, False)
    harness.log(f"control, latent rows kept in e4m3: correct {result['correct']}")
    return 1 if result["correct"] else 0


if __name__ == "__main__":
    sys.exit(main())
