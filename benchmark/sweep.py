"""Find the knee of an open-loop cell once: one process, one engine,
several fixed rates, each for a short window after its own ramp.

    python3 benchmark/sweep.py --workload mistral7b_chat_r80 --seed 7 \
        --rates 3,4,5,6,7,8 --seconds 25 [--out chiprun_out/sweep.jsonl]

Prints one JSON object per rate: requests due, finished and failed, the
queue the engine was left with when the window closed, TTFT and ITL as the
driver saw them, output tokens per second completed inside the window and
how late the generator ran. The knee is the highest rate at which the
queue does not grow through the window; the cell's ``rate_rps`` is four
fifths of it (PERF.md keeps the table).
"""
import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                      os.path.join(ROOT, ".jax_cache"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=25.0)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    from benchmark import manifest, traffic, run as R
    from benchmark.drivers import open_loop, _serving as S
    cell = manifest.workload(args.workload)
    cfg = manifest.config_of(cell)
    mix = traffic.load_mix(cell["traffic"])
    if mix["driver"] != "open_loop":
        raise SystemExit("only an open-loop cell has a knee")
    try:
        R.device_info(cell["chips"],
                      manifest.peaks())
    except R.NoChip as e:
        R.log(f"refusing to measure: {e}")
        return 2
    eng, _ = S.setup(cfg, mix, args.seed, R.log)
    rows = []
    for k, rate in enumerate(float(r) for r in args.rates.split(",")):
        m = dict(mix, rate_rps=rate)
        hooks = R.Hooks(False, m, args.seconds)
        backlog = {}
        close = hooks.window_close

        def window_close(e, close=close, backlog=backlog):
            backlog["queued"] = len(e._queue)
            backlog["running"] = sum(r is not None for r in e._slots)
            close(e)
        hooks.window_close = window_close
        res = open_loop.run(eng, m, cfg["model"]["vocab_size"],
                            args.seed + k, args.seconds, hooks)
        meas = res["measured"]
        ttft = [r.t_tokens[0] - r.due for r in meas if r.t_tokens]
        gaps = [g for r in meas for _, g in S.token_gaps(r)]
        row = {"rate_rps": rate, "due": len(meas),
               "failed": res["failed"], "queued_at_close": backlog["queued"],
               "running_at_close": backlog["running"],
               "ttft_p50_ms": 1e3 * S.percentile(ttft, 0.5),
               "ttft_p95_ms": 1e3 * S.percentile(ttft, 0.95),
               "itl_p50_ms": 1e3 * S.percentile(gaps, 0.5),
               "itl_p95_ms": 1e3 * S.percentile(gaps, 0.95),
               "serve_tokens_per_s":
                   res["end_to_end"]["serve_tokens_per_s"],
               "gen_late_p95_ms": res["clock"].get("gen_late_p95_ms"),
               "compiles_in_window":
                   hooks.compiles_close - hooks.compiles_open,
               "drain_s": hooks.clock() - hooks.t_close}
        rows.append(row)
        print(json.dumps(row), flush=True)
    if args.out:
        with open(args.out, "w") as f:
            for row in rows:
                f.write(json.dumps(row) + "\n")
    eng.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
