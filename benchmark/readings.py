"""Read, on the chip and at the cells' own sizes, the numbers that the
limits in ``benchmark/limits/`` are set from: what sound runs of the
program give over many seeds (the lower reading), what the control gives
(the reference in the next precision down, put in the program's place)
and, for training, what a planted fault gives. One process reads many
seeds, because set-up is most of a run.

    python3 benchmark/readings.py serve --workloads a,b --seeds 1,2,3 \
        --seconds 15 --control-seeds 3 --out chiprun_out/readings_serve.jsonl
    python3 benchmark/readings.py train --workloads c --seeds 1,2,3 \
        --control-seeds 3 --out chiprun_out/readings_train.jsonl

The benchmark's own runs never run this.
"""
import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                      os.path.join(ROOT, ".jax_cache"))


def emit(out, **row):
    print(json.dumps(row), flush=True)
    if out:
        with open(out, "a") as f:
            f.write(json.dumps(row) + "\n")


def serve(args):
    import numpy as np
    from benchmark import manifest, traffic, check, systems, run as R
    from benchmark.drivers import _serving as S
    names = args.workloads.split(",")
    cells = [manifest.workload(n) for n in names]
    cfg = manifest.config_of(cells[0])
    if any(c["config"] != cells[0]["config"] for c in cells):
        raise SystemExit("one process reads one configuration")
    reference = check.reference_of(cfg)
    mixes = [traffic.load_mix(c["traffic"]) for c in cells]
    seeds = [int(s) for s in args.seeds.split(",")]
    eng, _ = S.setup(cfg, mixes[0], seeds[0], R.log)
    kept = []
    for k, seed in enumerate(seeds):
        if k:
            t0 = time.perf_counter()
            systems.reseed_engine(eng, cfg, seed)
            R.log(f"seed {seed}: weights swapped in "
                  f"{time.perf_counter() - t0:.1f} s")
        for name, mix in zip(names, mixes):
            driver = manifest.module("drivers", mix["driver"])
            hooks = R.Hooks(False, mix, args.seconds)
            res = driver.run(eng, mix, cfg["model"]["vocab_size"], seed,
                             args.seconds, hooks)
            while eng.has_work:        # leave nothing for the next window
                eng.step()
            sample = S.check_sample(
                res["measured"], seed, int(mix.get("check_requests", 5)),
                int(mix.get("check_max_tokens", 8192)))
            kept.append((name, seed, check.served_sequences(sample),
                         res["attempted"], res["failed"],
                         hooks.compiles_close - hooks.compiles_open))
            R.log(f"{name} seed {seed}: attempted {res['attempted']} "
                  f"failed {res['failed']} sample {len(sample)}")
    S.release(eng)
    del eng
    controls = set(seeds[:args.control_seeds])
    for name, seed, (seqs, pos, toks), attempted, failed, comp in kept:
        t0 = time.perf_counter()
        ref = reference.sequence_logits(cfg, seed, seqs, pos)
        gaps = np.concatenate([check.gaps_below_best(l, t)
                               for l, t in zip(ref, toks)])
        row = {"workload": name, "seed": seed, "who": "program",
               "attempted": attempted, "failed": failed,
               "compiles_in_window": comp,
               "served_logit_gap_max": float(gaps.max()),
               "served_logit_gap_mean": float(gaps.mean()),
               "off_best": int((gaps > 0).sum()), "checked": int(gaps.size),
               "reference_s": time.perf_counter() - t0}
        emit(args.out, **row)
        if seed in controls:
            t0 = time.perf_counter()
            low = reference.sequence_logits(cfg, seed, seqs, pos,
                                            precision="lower")
            cg = np.concatenate([
                check.gaps_below_best(r, np.asarray(l).argmax(-1))
                for r, l in zip(ref, low)])
            emit(args.out, workload=name, seed=seed, who="control",
                 served_logit_gap_max=float(cg.max()),
                 served_logit_gap_mean=float(cg.mean()),
                 off_best=int((cg > 0).sum()), checked=int(cg.size),
                 reference_s=time.perf_counter() - t0)
    return 0


def train(args):
    from benchmark import manifest, traffic, check, run as R
    from benchmark.drivers import train_steps
    name = args.workloads
    cell = manifest.workload(name)
    cfg = manifest.config_of(cell)
    mix = traffic.load_mix(cell["traffic"])
    vocab = cfg["model"]["vocab_size"]
    seeds = [int(s) for s in args.seeds.split(",")]
    controls = set(seeds[:args.control_seeds])
    for seed in seeds:
        t0 = time.perf_counter()
        trainer, setup = train_steps.setup(cfg, mix, seed, R.log)
        train_steps.release(trainer)
        del trainer
        t1 = time.perf_counter()
        batches = [train_steps.feed(mix, vocab, seed, s)
                   for s in range(train_steps.CHECK_STEPS)]
        ref = check.reference_train_readings(cfg, seed, batches)
        t2 = time.perf_counter()
        emit(args.out, workload=name, seed=seed, who="program",
             program_s=t1 - t0, reference_s=t2 - t1,
             **check.train_numbers(setup["readings"], ref))
        if seed in controls:
            low = check.reference_train_readings(cfg, seed, batches,
                                                 precision="lower")
            emit(args.out, workload=name, seed=seed, who="control",
                 **check.train_numbers(low, ref))
            half = check.reference_train_readings(
                cfg, seed, batches, rows=range(mix["batch"] // 2))
            emit(args.out, workload=name, seed=seed, who="fault_half_batch",
                 **check.train_numbers(half, ref))
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("kind", choices=("serve", "train"))
    ap.add_argument("--workloads", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=15.0)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    from benchmark import manifest, run as R
    try:
        R.device_info(1, manifest.peaks())
    except R.NoChip as e:
        R.log(f"refusing to measure: {e}")
        return 2
    return serve(args) if args.kind == "serve" else train(args)


if __name__ == "__main__":
    sys.exit(main())
