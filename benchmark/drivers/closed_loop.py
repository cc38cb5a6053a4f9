"""Closed loop: a fixed number of clients, each sending its next request
when its last one completes. The window opens after a ramp in which the
clients fall out of step with each other, and closes on the clock: what
is in flight then is left unfinished, as a batch caller's last wave is."""
from . import _serving as S
from .. import traffic


def run(eng, mix: dict, vocab: int, seed: int, seconds: float, hooks):
    n = int(mix["population"])
    plen, olen = traffic.request_sizes(mix, n, seed)
    loop = S.ServeLoop(eng, hooks.clock)
    recs, nxt = [], 0

    def send(client):
        nonlocal nxt
        i = nxt % n             # a run that outlasts the population wraps
        rec = S.Rec(nxt, loop.clock(),
                    traffic.prompt_tokens(vocab, plen[i], seed, nxt),
                    olen[i], client)
        nxt += 1
        recs.append(rec)
        loop.submit(rec)

    t_loop = loop.clock()
    w0, w1 = t_loop + mix["ramp_s"], t_loop + mix["ramp_s"] + seconds
    for c in range(int(mix["clients"])):
        send(c)
    opened = False
    while True:
        now = loop.clock()
        if not opened and now >= w0:
            hooks.window_open(eng)
            opened = True
        if now >= w1:
            hooks.window_close(eng)
            break
        hooks.tick(now - w0)
        for rec in loop.step():
            send(rec.client)
    # what ended inside the window, and what ended with no token at all
    ended = [r for r in loop.done
             if not r.t_tokens or w0 <= r.t_tokens[-1] < w1]
    gaps = [g for r in recs for t, g in S.token_gaps(r) if w0 <= t < w1]
    out = {
        "attempted": len(ended), "failed": sum(S.failed(r) for r in ended),
        "window": (w0, w1), "recs": recs, "measured": ended, "loop": loop,
        "end_to_end": {}, "clock": {}}
    if gaps:
        out["end_to_end"]["itl_p95_ms"] = 1e3 * S.percentile(gaps, 0.95)
    out["end_to_end"]["serve_tokens_per_s"] = sum(
        w0 <= t < w1 for r in recs for t in r.t_tokens) / seconds
    return out

setup, release, check_numbers = S.setup, S.release, S.check_numbers
