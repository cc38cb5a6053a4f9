#!/usr/bin/env python
"""Summarize a serving telemetry trace (ISSUE 12).

Reads the Chrome-trace/Perfetto JSON written by
``paddle_tpu.utils.telemetry.Tracer.export`` and prints the post-mortem
a red gate run (or a bench artifact) needs without opening the UI:

- per-phase latency breakdown: count / total / mean / p50 / p99 of
  every span name (queued, prefill, splice_wait, decode, ..., and the
  program's own engine.* / train_step* spans), and its SELF time: a
  span's duration minus the part of it the spans it caused (``parent``
  = its ``id``) cover, so that engine.step's self time is what no
  named phase accounts for;
- per-replica occupancy: span-busy seconds per replica track over the
  trace wall clock (an approximation — overlapping spans of different
  requests double-count busy time, so >100% means real concurrency);
- dispatch mix per replica (ragged/decode/prefill/spec counts);
- top preempted / migrated requests, with req ids and tenant
  attributes off the request-begin records;
- terminal-state counts and the event tally (retries, injected
  faults, breaker strikes, kv churn);
- compile-span table (ISSUE 14): per program family, compile count +
  total/max compile wall and the XLA flops / bytes-accessed numbers
  when CompileWatch's analyze mode recorded them, plus the
  unexpected-recompile verdict;
- counter-track summaries: min/mean/max/last of every ``ph:"C"``
  resource timeline (running slots, free blocks, queue depth, ...)
  per replica track;
- SLO section: ``slo_violation`` events plus the burn-rate / headroom
  gauges riding the exported metrics snapshot;
- dispatch amortization (ISSUE 16): tokens per dispatch grouped by
  (kind, fused-window depth k) off the dispatch events' ``k`` /
  ``decode_toks`` args, plus sampled device-execute totals per
  program family (the ragged_ms* families are the k>1 windows);
- worker lifecycle (ISSUE 19): process-fleet supervision off the
  fleet track — worker exits grouped by reason, respawn count and
  wall-clock, heartbeat misses, and migrations.

Pure host tool: no jax, no paddle_tpu import — runs anywhere the JSON
does.

    python tools/trace_report.py trace.json
    python tools/trace_report.py trace.json --json   # machine-readable
    python tools/trace_report.py trace.json --top 10
"""
from __future__ import annotations

import argparse
import json
import sys
from collections import Counter, defaultdict


def _pct(xs, p):
    if not xs:
        return None
    xs = sorted(xs)
    i = (len(xs) - 1) * p
    lo, hi = int(i), min(int(i) + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (i - lo)


def _pid_name(pid):
    # keep in sync with telemetry.FLEET_PID (no import: pure host tool)
    return "fleet" if pid == 1000 else f"replica{pid}"


def _self_times(spans) -> dict:
    """{span name: summed self seconds}: each span's duration minus the
    union of its children's intervals, a child being a span whose
    ``parent`` is this span's ``id`` on the same track and which lies
    inside it (a request phase closed inside an engine step began
    before the step: it hangs under it but takes nothing from it).
    Spans without an ``id`` (older traces) count whole; the 1e-3 us
    lets through what rebasing to microseconds rounds."""
    kids = defaultdict(list)
    for s in spans:
        if s.get("parent") is not None:
            kids[(s["pid"], s["parent"])].append(s)
    out: dict = defaultdict(float)
    for s in spans:
        if s["name"] == "compile":
            continue
        t0, dur = s["ts"], s.get("dur", 0.0)
        inside = sorted(
            (k["ts"], k["ts"] + k.get("dur", 0.0))
            for k in kids.get((s["pid"], s.get("id")), ())
            if s.get("id") is not None and k["ts"] >= t0 - 1e-3
            and k["ts"] + k.get("dur", 0.0) <= t0 + dur + 1e-3)
        covered, end = 0.0, t0
        for a, b in inside:
            if b > end:
                covered += b - max(a, end)
                end = b
        out[s["name"]] += (dur - covered) / 1e6
    return out


def analyze(doc: dict, top: int = 5) -> dict:
    evts = doc.get("traceEvents", [])
    spans = [e for e in evts if e.get("ph") == "X"]
    insts = [e for e in evts if e.get("ph") == "i"]
    counters = [e for e in evts if e.get("ph") == "C"]
    begins = {e.get("id"): e for e in evts if e.get("ph") == "b"}
    ends = {e.get("id"): e for e in evts if e.get("ph") == "e"}

    # -- per-phase latency breakdown ------------------------------------
    # compile spans get their own table below — they are program
    # lifecycle, not request phases
    by_phase: dict = defaultdict(list)
    for s in spans:
        if s["name"] == "compile":
            continue
        by_phase[s["name"]].append(s.get("dur", 0.0) / 1e6)
    self_s = _self_times(spans)
    phases = {}
    for name, durs in sorted(by_phase.items()):
        phases[name] = {
            "count": len(durs),
            "total_s": round(sum(durs), 4),
            "self_s": round(self_s.get(name, sum(durs)), 4),
            "mean_s": round(sum(durs) / len(durs), 5),
            "p50_s": round(_pct(durs, 0.50), 5),
            "p99_s": round(_pct(durs, 0.99), 5),
        }

    # -- per-replica occupancy + dispatch mix ---------------------------
    ts_all = [e["ts"] for e in evts if e.get("ph") in ("X", "i", "b", "e")]
    wall_s = ((max(ts_all) - min(ts_all)) / 1e6) if ts_all else 0.0
    busy: Counter = Counter()
    for s in spans:
        # waiting phases are not device work: a queue-backed-up idle
        # replica must not read as saturated. Compile spans are
        # warmup/one-off cost with their own table — a grid-warmed
        # trace must not read as a saturated replica either.
        # The program's own phase spans (no request id: tid 0) lie
        # over the request spans they serve and would count twice.
        if s["name"] in ("queued", "splice_wait", "compile") \
                or not s.get("tid"):
            continue
        busy[s["pid"]] += s.get("dur", 0.0) / 1e6
    dispatch_mix: dict = defaultdict(Counter)
    for e in insts:
        if e["name"] == "dispatch":
            dispatch_mix[e["pid"]][e.get("args", {}).get("kind", "?")] \
                += 1
    replicas = {}
    for pid in sorted(set(busy) | set(dispatch_mix)):
        replicas[_pid_name(pid)] = {
            "busy_s": round(busy.get(pid, 0.0), 4),
            "occupancy": (round(busy.get(pid, 0.0) / wall_s, 4)
                          if wall_s else None),
            "dispatches": dict(dispatch_mix.get(pid, {})),
        }

    # -- per-request robustness: preempt / migrate counts ---------------
    preempts: Counter = Counter()
    migrations: Counter = Counter()
    for e in insts:
        tid = e.get("tid")
        if e["name"] == "preempt" and tid:
            preempts[tid] += 1
        elif e["name"] == "migrate" and tid:
            migrations[tid] += 1

    def _req_label(tid):
        b = begins.get(str(tid)) or begins.get(tid)
        if b is None:
            return {"trace": tid}
        a = b.get("args", {})
        out = {"trace": tid, "req_id": a.get("req_id")}
        if "tenant" in a:
            out["tenant"] = a["tenant"]
        return out

    top_preempted = [dict(_req_label(t), preemptions=n)
                     for t, n in preempts.most_common(top)]
    top_migrated = [dict(_req_label(t), migrations=n)
                    for t, n in migrations.most_common(top)]

    # -- terminal states + event tally ----------------------------------
    states: Counter = Counter()
    for e in ends.values():
        states[e.get("args", {}).get("state", "?")] += 1
    events: Counter = Counter(e["name"] for e in insts)

    # -- compile-span table (ISSUE 14) ----------------------------------
    # one row per program family: how often it compiled, the wall it
    # cost, and the XLA cost/memory analysis when the watch recorded
    # it (analyze mode). unexpected counts compiles observed AFTER
    # seal_programs — the runtime FC2xx; any non-zero row is the
    # retrace the gate legs assert against.
    fam_rows: dict = defaultdict(lambda: {
        "count": 0, "total_wall_s": 0.0, "max_wall_s": 0.0,
        "unexpected": 0})
    for s in spans:
        if s["name"] != "compile":
            continue
        a = s.get("args", {})
        row = fam_rows[a.get("family", "?")]
        w = s.get("dur", 0.0) / 1e6
        row["count"] += 1
        row["total_wall_s"] += w
        row["max_wall_s"] = max(row["max_wall_s"], w)
        if a.get("sealed"):
            row["unexpected"] += 1
        for k in ("flops", "bytes_accessed", "temp_bytes",
                  "output_bytes", "argument_bytes"):
            if k in a:
                row[k] = a[k]
    compiles = {}
    for fam, row in sorted(fam_rows.items()):
        row["total_wall_s"] = round(row["total_wall_s"], 4)
        row["max_wall_s"] = round(row["max_wall_s"], 4)
        compiles[fam] = row
    unexpected_recompiles = (
        events.get("unexpected_recompile", 0)
        or sum(r["unexpected"] for r in compiles.values()))

    # -- counter-track summaries (ISSUE 14) -----------------------------
    # per (replica track, counter name): sample count + min/mean/max
    # and the final value — the text view of the Perfetto timelines
    track_vals: dict = defaultdict(list)
    for c in counters:
        v = c.get("args", {}).get("value")
        if v is not None:
            track_vals[(c["pid"], c["name"])].append(float(v))
    tracks: dict = {}
    for (pid, name), vals in sorted(track_vals.items()):
        tracks.setdefault(_pid_name(pid), {})[name] = {
            "n": len(vals),
            "min": round(min(vals), 4),
            "mean": round(sum(vals) / len(vals), 4),
            "max": round(max(vals), 4),
            "last": round(vals[-1], 4),
        }

    # -- dispatch amortization (ISSUE 16) -------------------------------
    # ragged dispatch events carry k (fused-window depth) and
    # decode_toks (decode tokens the window delivers); grouping by
    # (kind, k) shows the tokens-per-dispatch amortization the
    # multi-step refactor buys, and the per-family execute totals from
    # the sampled attribution events split the device wall by program
    # family (the ragged_ms* families are the k>1 windows)
    amort_rows: dict = defaultdict(
        lambda: {"dispatches": 0, "decode_toks": 0})
    for e in insts:
        if e["name"] != "dispatch":
            continue
        a = e.get("args", {})
        row = amort_rows[(a.get("kind", "?"), int(a.get("k", 1)))]
        row["dispatches"] += 1
        row["decode_toks"] += int(a.get("decode_toks", 0))
    amort: dict = {}
    for (kind, kk), row in sorted(amort_rows.items()):
        amort[f"{kind} k={kk}"] = {
            "dispatches": row["dispatches"],
            "decode_toks": row["decode_toks"],
            "toks_per_dispatch": round(
                row["decode_toks"] / row["dispatches"], 2),
        }
    exec_by_family: dict = defaultdict(
        lambda: {"samples": 0, "execute_s": 0.0})
    for e in insts:
        if e["name"] == "profile_sample":
            a = e.get("args", {})
            r = exec_by_family[a.get("family", "?")]
            r["samples"] += 1
            r["execute_s"] += float(a.get("execute_s", 0.0))
    execute = {fam: {"samples": r["samples"],
                     "execute_s": round(r["execute_s"], 4)}
               for fam, r in sorted(exec_by_family.items())}
    amortization = ({"dispatch": amort, "execute_by_family": execute}
                    if amort or execute else None)

    # -- SLO section (ISSUE 14) -----------------------------------------
    # violation events carry (policy, headroom at detection); the
    # exported metrics snapshot carries the latest burn-rate /
    # headroom gauges under the slo* namespaces
    slo_events = [dict(e.get("args", {}))
                  for e in insts if e["name"] == "slo_violation"]
    slo_gauges = {
        k: v for k, v in sorted(
            (doc.get("metrics", {}).get("gauges") or {}).items())
        if k.startswith("slo") or ".slo." in k}
    slo = ({"violations": slo_events, "gauges": slo_gauges}
           if (slo_events or slo_gauges) else None)

    # -- worker lifecycle (ISSUE 19) ------------------------------------
    # process-fleet supervision events off the fleet track: worker
    # exits grouped by reason (process_exit / heartbeat / ...),
    # respawn count + wall-clock each respawn paid (spawn + warmup
    # replay + re-seal), heartbeat misses, and migrations — the
    # crash-isolation story of a run at a glance
    w_exits = [dict(e.get("args", {}))
               for e in insts if e["name"] == "worker_exit"]
    w_spawns = [dict(e.get("args", {}))
                for e in insts if e["name"] == "worker_respawn"]
    hb_misses = sum(1 for e in insts if e["name"] == "heartbeat_miss")
    workers = None
    if w_exits or w_spawns or hb_misses:
        walls = [float(r.get("wall_s", 0.0)) for r in w_spawns]
        workers = {
            "exits": len(w_exits),
            "exits_by_reason": dict(Counter(
                x.get("reason", "?") for x in w_exits)),
            "respawns": len(w_spawns),
            "respawn_failed": sum(
                1 for e in insts
                if e["name"] == "worker_respawn_failed"),
            "respawn_wall_s": {
                "max": round(max(walls), 3),
                "total": round(sum(walls), 3),
            } if walls else None,
            "heartbeat_misses": hb_misses,
            "migrations": sum(
                1 for e in insts if e["name"] == "migrate"),
        }

    return {
        "wall_s": round(wall_s, 4),
        "records": len(evts),
        "dropped_records": doc.get("otherData", {}).get(
            "dropped_records", 0),
        "requests": {"begun": len(begins), "ended": len(ends),
                     "states": dict(states)},
        "phases": phases,
        "replicas": replicas,
        "top_preempted": top_preempted,
        "top_migrated": top_migrated,
        "events": dict(events),
        "compiles": compiles,
        "unexpected_recompiles": unexpected_recompiles,
        "tracks": tracks,
        "amortization": amortization,
        "slo": slo,
        "workers": workers,
    }


def format_report(rep: dict) -> str:
    lines = [f"trace: {rep['records']} records over {rep['wall_s']}s "
             f"wall ({rep['dropped_records']} dropped from the ring)"]
    rq = rep["requests"]
    lines.append(f"requests: {rq['begun']} begun, {rq['ended']} ended "
                 f"{rq['states']}")
    lines.append("per-phase latency:")
    for name, p in rep["phases"].items():
        lines.append(
            f"  {name:12s} n={p['count']:<5d} total={p['total_s']:<9g} "
            f"self={p['self_s']:<9g} mean={p['mean_s']:<9g} "
            f"p50={p['p50_s']:<9g} p99={p['p99_s']:g}")
    lines.append("per-replica occupancy:")
    for name, r in rep["replicas"].items():
        occ = (f"{r['occupancy'] * 100:.1f}%"
               if r["occupancy"] is not None else "n/a")
        lines.append(f"  {name:10s} busy={r['busy_s']}s ({occ}) "
                     f"dispatches={r['dispatches']}")
    if rep["top_preempted"]:
        lines.append(f"top preempted: {rep['top_preempted']}")
    if rep["top_migrated"]:
        lines.append(f"top migrated: {rep['top_migrated']}")
    if rep.get("compiles"):
        verdict = rep.get("unexpected_recompiles", 0)
        lines.append(f"compiles (unexpected={verdict}):")
        for fam, r in rep["compiles"].items():
            extra = "".join(
                f" {k}={r[k]:g}" for k in ("flops", "bytes_accessed")
                if k in r)
            flag = (f" UNEXPECTED={r['unexpected']}"
                    if r["unexpected"] else "")
            lines.append(
                f"  {fam:18s} n={r['count']:<4d} "
                f"total={r['total_wall_s']:<9g} "
                f"max={r['max_wall_s']:g}{extra}{flag}")
        # XLA memory_analysis per family (CompileWatch analyze=True):
        # argument/peak-temp/output bytes of the last compile observed
        mem_fams = {fam: r for fam, r in rep["compiles"].items()
                    if any(k in r for k in (
                        "argument_bytes", "temp_bytes", "output_bytes"))}
        if mem_fams:
            lines.append("memory by family (XLA memory_analysis):")
            for fam, r in mem_fams.items():
                parts = "".join(
                    f" {label}={r[k]:g}B"
                    for k, label in (("argument_bytes", "args"),
                                     ("temp_bytes", "peak-temp"),
                                     ("output_bytes", "out"))
                    if k in r)
                lines.append(f"  {fam:18s}{parts}")
    if rep.get("tracks"):
        lines.append("counter tracks:")
        for rname, tr in rep["tracks"].items():
            for name, t in tr.items():
                lines.append(
                    f"  {rname}/{name:18s} n={t['n']:<5d} "
                    f"min={t['min']:<8g} mean={t['mean']:<8g} "
                    f"max={t['max']:<8g} last={t['last']:g}")
    if rep.get("amortization"):
        am = rep["amortization"]
        if am["dispatch"]:
            lines.append("dispatch amortization:")
            for key, r in am["dispatch"].items():
                lines.append(
                    f"  {key:22s} dispatches={r['dispatches']:<5d} "
                    f"decode_toks={r['decode_toks']:<7d} "
                    f"toks/dispatch={r['toks_per_dispatch']:g}")
        if am["execute_by_family"]:
            lines.append("device execute by family (sampled):")
            for fam, r in am["execute_by_family"].items():
                lines.append(
                    f"  {fam:18s} samples={r['samples']:<5d} "
                    f"execute={r['execute_s']:g}s")
    if rep.get("slo"):
        slo = rep["slo"]
        lines.append(f"slo: {len(slo['violations'])} violation "
                     f"event(s)")
        for v in slo["violations"]:
            lines.append(f"  VIOLATION {v}")
        for k, v in slo["gauges"].items():
            lines.append(f"  {k} = {v:g}")
    if rep.get("workers"):
        w = rep["workers"]
        wall = w["respawn_wall_s"]
        wall_txt = (f" wall max={wall['max']:g}s total={wall['total']:g}s"
                    if wall else "")
        failed = (f" ({w['respawn_failed']} failed)"
                  if w["respawn_failed"] else "")
        lines.append(
            f"worker lifecycle: {w['exits']} exit(s) "
            f"{w['exits_by_reason']}, {w['respawns']} "
            f"respawn(s){failed}{wall_txt}, "
            f"{w['heartbeat_misses']} heartbeat miss(es), "
            f"{w['migrations']} migration(s)")
    lines.append(f"events: {rep['events']}")
    return "\n".join(lines)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("trace", help="path to a Tracer.export JSON file")
    ap.add_argument("--json", action="store_true",
                    help="print the machine-readable summary dict")
    ap.add_argument("--top", type=int, default=5,
                    help="top-N preempted/migrated requests to list")
    args = ap.parse_args()
    with open(args.trace) as f:
        doc = json.load(f)
    rep = analyze(doc, top=args.top)
    try:
        print(json.dumps(rep) if args.json else format_report(rep))
    except BrokenPipeError:      # head/less closed the pipe — fine
        pass
    return 0


if __name__ == "__main__":
    sys.exit(main())
