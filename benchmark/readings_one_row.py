"""``readings.py train`` for a training cell whose batch is ONE row.

That script plants its fault by leaving half of the batch's rows out of
the reference's mean, which at one row is no row at all. Here the fault is
half of the SEQUENCE: the reference on ``batch[:, :seq // 2]``, put in the
program's place. Beside each reading goes what ``check.judge`` makes of it
under the cell's committed limits: the program has to come out correct,
the control (the reference in the next precision down) and the fault not;
the exit code is 1 if any of them comes out otherwise.

    python3 benchmark/readings_one_row.py --workload c --seeds 1,2,3 \\
        --control-seeds 1 --out chiprun_out/readings_one_row.jsonl

The benchmark's own runs never run this.
"""
import argparse
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                      os.path.join(ROOT, ".jax_cache"))


def read(name: str, seeds, control_seeds: int, out=None) -> int:
    from benchmark import manifest, traffic, check, run as R
    from benchmark.drivers import train_steps
    from benchmark.readings import emit
    cell = manifest.workload(name)
    cfg = manifest.config_of(cell)
    mix = traffic.load_mix(cell["traffic"])
    if mix["batch"] != 1:
        raise SystemExit(f"{name} has {mix['batch']} rows a batch: "
                         "readings.py train cuts those")
    limits = check.load_limits(name)
    vocab = cfg["model"]["vocab_size"]
    as_expected = True

    def judged(who, seed, readings, ref, expect):
        nonlocal as_expected
        numbers = check.train_numbers(readings, ref)
        correct, _ = check.judge(numbers, limits)
        as_expected = as_expected and correct == expect
        emit(out, workload=name, seed=seed, who=who, correct=correct,
             **numbers)

    for k, seed in enumerate(seeds):
        trainer, setup = train_steps.setup(cfg, mix, seed, R.log)
        train_steps.release(trainer)
        del trainer
        batches = [train_steps.feed(mix, vocab, seed, s)
                   for s in range(train_steps.CHECK_STEPS)]
        ref = check.reference_train_readings(cfg, seed, batches)
        judged("program", seed, setup["readings"], ref, True)
        if k < control_seeds:
            judged("control", seed, check.reference_train_readings(
                cfg, seed, batches, precision="lower"), ref, False)
            judged("fault_half_sequence", seed,
                   check.reference_train_readings(
                       cfg, seed, [b[:, :mix["seq"] // 2] for b in batches]),
                   ref, False)
    return 0 if as_expected else 1


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", type=int, default=1)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    from benchmark import manifest, run as R
    try:
        R.device_info(1, manifest.peaks())
    except R.NoChip as e:
        R.log(f"refusing to measure: {e}")
        return 2
    return read(args.workload, [int(s) for s in args.seeds.split(",")],
                args.control_seeds, args.out)


if __name__ == "__main__":
    sys.exit(main())
