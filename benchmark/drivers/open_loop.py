"""Open loop: requests fall due on a seeded Poisson schedule at the mix's
fixed rate, whatever the engine is doing, and every latency is taken from
the time a request was DUE. A ramp of the same traffic runs before the
window opens, so that the window starts on a loaded engine."""
import numpy as np

from . import _serving as S
from .. import traffic


def schedule(mix: dict, vocab: int, seed: int, seconds: float):
    """[Rec] of the ramp and then of the window, due times from the loop's
    start. The window's requests are a set of their own. The order of
    gaps and sizes is drawn once, from the mix's own ``order_seed``, and a
    run's seed ROTATES it (and draws the token ids): every seed has the
    same requests after the same gaps in the same cyclic order, starting
    at another place. A free shuffle moved the 95th percentile of the time
    to first token by 16% between seeds, because where the long prompts
    cluster decides the tail (PERF.md)."""
    recs = []
    order = int(mix.get("order_seed", 0))
    for stream, start, span_s in (("ramp", 0.0, mix["ramp_s"]),
                                  ("", mix["ramp_s"], seconds)):
        n = int(round(mix["rate_rps"] * span_s))
        if n < 1:
            continue
        due = traffic.poisson_due_times(n / span_s, n, order, stream)
        gaps = np.diff(due, prepend=0.0)
        gaps[0] *= 2.0          # the whole first gap: the span is cyclic
        plen, olen = traffic.request_sizes(mix, n, order, stream)
        # "order": "fixed" keeps the drawn order for every seed (the seed
        # then draws only the token ids); the default turns it by seed
        turn = 0 if mix.get("order") == "fixed" else int(
            traffic.rng_for(seed, "turn" + stream).integers(n))
        gaps, plen, olen = (np.roll(a, -turn) for a in (gaps, plen, olen))
        due = start + np.cumsum(gaps) - gaps[0] / 2.0
        recs += [S.Rec(len(recs) + i, float(due[i]),
                       traffic.prompt_tokens(vocab, plen[i], seed,
                                             len(recs) + i), olen[i])
                 for i in range(n)]
    return recs


def run(eng, mix: dict, vocab: int, seed: int, seconds: float, hooks):
    recs = schedule(mix, vocab, seed, seconds)
    loop = S.ServeLoop(eng, hooks.clock)
    t_loop = loop.clock()
    w0, w1 = t_loop + mix["ramp_s"], t_loop + mix["ramp_s"] + seconds
    nxt, opened, closed = 0, False, False
    while True:
        now = loop.clock()
        if not opened and now >= w0:
            hooks.window_open(eng)
            opened = True
        if not closed and now >= w1:
            hooks.window_close(eng)
            closed = True
        while nxt < len(recs) and t_loop + recs[nxt].due <= now:
            loop.submit(recs[nxt])
            nxt += 1
        hooks.tick(now - w0)
        if eng.has_work:
            loop.step()
        elif nxt < len(recs):
            loop.idle(min(t_loop + recs[nxt].due, w1) - loop.clock())
        elif not closed:
            loop.idle(w1 - loop.clock())
        else:
            break
        if closed and nxt >= len(recs) and loop.clock() > w1 + S.DRAIN_S:
            break
    measured = [r for r in recs if w0 <= t_loop + r.due < w1]
    for r in measured:
        r.due += t_loop         # absolute from here on
    ttft = [r.t_tokens[0] - r.due for r in measured if r.t_tokens]
    gaps = [g for r in measured for _, g in S.token_gaps(r)]
    late = [r.sent - r.due for r in measured if r.sent is not None]
    n_failed = sum(S.failed(r) for r in measured)
    out = {
        "attempted": len(measured), "failed": n_failed,
        "window": (w0, w1), "recs": recs, "measured": measured,
        "loop": loop,
        "end_to_end": {}, "clock": {}}
    if ttft:
        out["end_to_end"]["ttft_p95_ms"] = 1e3 * S.percentile(ttft, 0.95)
    if gaps:
        out["end_to_end"]["itl_p95_ms"] = 1e3 * S.percentile(gaps, 0.95)
    out["end_to_end"]["serve_tokens_per_s"] = sum(
        w0 <= t < w1 for r in recs for t in r.t_tokens) / seconds
    if late:
        out["clock"]["gen_late_p95_ms"] = 1e3 * S.percentile(late, 0.95)
    return out

setup, release, check_numbers = S.setup, S.release, S.check_numbers
