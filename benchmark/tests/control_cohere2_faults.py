"""By hand, on the chip: the `serve-cmdaplus-mixed-saturated` cell's limits
read with each named way of computing something else, at the cell's own size,
where the limits were set. Each has to come out as NOT correct by one of the
cell's limits, and the sound program as correct:

    python3 -m benchmark.tests.control_cohere2_faults --seed 3600000301

It builds the cell's engine as the driver does (no load), and reads

- the attention check (`models_cohere2_moe.latent_error`, the engine's own
  pools and kernels) sound, then with the window's edge off by one, a window
  layer left unrotated, a global layer rotated, a released page read, and
  with the K and V rows kept in 8-bit floats (e4m3), the nearest precision
  below the bfloat16 the configuration states;
- the logit check on the cell's six check prompts, sent through the engine
  alone, judged by the reference as it is and by the reference with the four
  shared experts SUMMED instead of averaged.

Exits 0 when the sound readings pass and every control fails, 1 otherwise.
"""
import argparse
import os
import sys

from benchmark import harness


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--workload", default="serve-cmdaplus-mixed-saturated")
    parser.add_argument("--skip-logits", action="store_true")
    args = parser.parse_args(argv)

    from benchmark import models_cohere2_moe as lm
    from benchmark import run
    from paddle_tpu.compile_cache.jax_cache import enable_jax_cache

    manifest = harness.load_json(os.path.join(harness.ROOT, "BENCHMARK.json"))
    _, config, traffic = run.find_cell(manifest, args.workload)
    enable_jax_cache()
    _, engine = lm.build_engine(config, args.seed)
    limit = traffic["attention_check"]["tolerance"]
    ok = True
    try:
        sound = lm.latent_error(engine, config, traffic, args.seed)
        harness.log(f"control: sound attention check {sound:.3e} against {limit}: "
                    f"{'correct' if sound <= limit else 'INCORRECT'}")
        ok &= sound <= limit
        for fault in lm.FAULTS + (lm.LOWER_PRECISION,):
            got = lm.latent_error(engine, config, traffic, args.seed, fault=fault)
            harness.log(f"control: attention check with fault {fault}: {got:.3e} against "
                        f"{limit}: {'INCORRECT, as it has to be' if got > limit else 'PASSED'}")
            ok &= got > limit
        answered = [] if args.skip_logits else lm.collect_check(
            lm.send_check(engine, config, traffic, args.seed), traffic)
        weights = engine.programs.params
    finally:
        engine.shutdown(drain=False)
    if answered:
        for array in engine.kv_pool.arrays():
            array.delete()
        for average in (True, False):
            check = lm.judge_check(weights, config, traffic, answered, average=average)
            passed = (check["complete"] and check["worst_gap"] <= traffic["logit_tolerance"]
                      and check["exact"] >= traffic["exact_floor"] * check["tokens"])
            harness.log(f"control: logit check, shared experts "
                        f"{'averaged' if average else 'SUMMED'}: {check}: "
                        f"{'correct' if passed else 'INCORRECT'}")
            ok &= passed == average
    harness.log(f"control: {'every control failed and the sound program passed' if ok else 'A CONTROL PASSED OR THE SOUND PROGRAM FAILED'}")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
