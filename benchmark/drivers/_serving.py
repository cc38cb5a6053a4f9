"""What the open and the closed loop share: one engine, one thread, the
driver's own clock on every token.

The loop submits what is due, calls ``eng.step()``, and then looks at
every live request's ``out_tokens``: tokens that were not there before
the step are stamped with the time the step returned, which is when a
client of this loop could first see them. Latencies are the driver's,
never the engine's own ``ttft``/``itl`` statistics.
"""
import time

import numpy as np
from jax.profiler import TraceAnnotation

from .. import systems, traffic
from ..trace_reduce import HOST_PREFIX

DRAIN_S = 60.0


def setup(cfg: dict, mix: dict, seed: int, log):
    """Build the engine and warm every program greedy traffic can reach.
    Returns (engine, {"weights_s", "programs": [[T, W, seconds]]})."""
    eng, t_weights = systems.build_engine(cfg, seed)
    log(f"weights made and quantised in {t_weights:.2f} s; attention: "
        f"{eng.attention_impls.get('ragged')}")
    took = systems.warm_ragged(eng, systems.ragged_program_set(eng), log)
    eng.seal_programs()
    return eng, {"weights_s": t_weights,
                 "programs": [[t, w, s] for t, w, s in took]}


def release(eng):
    """Free the engine's device state before the reference runs."""
    import gc
    import jax
    eng.close()
    eng.dec.weights = None
    eng.dec.cache.k = eng.dec.cache.v = None
    jax.clear_caches()
    gc.collect()


def span(name):
    return TraceAnnotation(HOST_PREFIX + name)


class Rec:
    """One request as the driver saw it."""
    __slots__ = ("index", "rid", "req", "due", "sent", "prompt", "want",
                 "seen", "t_tokens", "state", "tokens", "client")

    def __init__(self, index, due, prompt, want, client=None):
        self.index, self.due, self.prompt, self.want = index, due, prompt, \
            int(want)
        self.rid = self.req = self.sent = None
        self.seen, self.t_tokens, self.state, self.tokens = 0, [], "live", \
            None
        self.client = client


class ServeLoop:
    def __init__(self, eng, clock=time.perf_counter):
        self.eng, self.clock = eng, clock
        self.live, self.done = {}, []
        self.pool_samples = []
        self.steps = 0

    def submit(self, rec: Rec):
        with span("add_request"):
            rec.rid = self.eng.add_request(rec.prompt,
                                           systems.greedy(rec.want))
        rec.sent = self.clock()
        # the engine has no public view of a live request's tokens: keep
        # its record and read out_tokens as a streaming client would
        rec.req = self.eng._find_request(rec.rid)
        self.live[rec.rid] = rec

    def step(self):
        """One engine step, then stamp what it delivered. Returns the
        records that reached a terminal state in it."""
        with span("step"):
            self.eng.step()
        now = self.clock()
        self.steps += 1
        cache = self.eng.dec.cache
        self.pool_samples.append(
            (now, 1.0 - cache.available_blocks / cache.num_blocks))
        ended = []
        with span("poll"):
            for rid, rec in list(self.live.items()):
                req = rec.req
                n = len(req.out_tokens)
                if n > rec.seen:
                    rec.t_tokens += [now] * (n - rec.seen)
                    rec.seen = n
                if req.state in ("done", "aborted", "failed"):
                    rec.state = req.state
                    rec.tokens = np.asarray(req.out_tokens, np.int32)
                    rec.req = None
                    del self.live[rid]
                    self.done.append(rec)
                    ended.append(rec)
        return ended

    def idle(self, seconds: float):
        with span("no_request_due"):
            time.sleep(max(0.0, seconds))


def failed(rec: Rec) -> bool:
    """A request that never finished, ended in a fault state, or came
    back with another number of tokens than it asked for."""
    return rec.state != "done" or rec.tokens is None \
        or len(rec.tokens) != rec.want


def percentile(values, q: float) -> float:
    """The q-quantile by linear interpolation (numpy's default)."""
    return float(np.percentile(np.asarray(values, np.float64), 100 * q))


def token_gaps(rec: Rec):
    """Gaps between consecutive tokens of one request as the loop saw
    them arrive (tokens of one chunk arrive together: one long gap and
    zeros), each with the arrival time of its later token."""
    t = rec.t_tokens
    return [(t[i], t[i] - t[i - 1]) for i in range(1, len(t))]


def work_counts(recs, t0: float, t1: float):
    """Tokens the model processed in [t0, t1) and the (query, key) pairs
    they met, from the driver's stamps: a request's prompt counts when
    its first token arrives, each later token when the one after it
    arrives (token i is the input of the step that yields token i+1)."""
    tokens = pairs = decode_pairs = decode_tokens = 0
    for rec in recs:
        n = len(rec.prompt)
        for i, t in enumerate(rec.t_tokens):
            if not (t0 <= t < t1):
                continue
            if i == 0:
                tokens += n
                pairs += n * (n + 1) // 2
            else:
                tokens += 1
                pairs += n + i
                decode_tokens += 1
                decode_pairs += n + i
    return {"tokens": tokens, "pairs": pairs,
            "decode_tokens": decode_tokens, "decode_pairs": decode_pairs}


def check_sample(recs, seed: int, n_random: int, max_tokens: int):
    """The finished requests whose served tokens the reference judges:
    the longest, then seeded draws until ``n_random`` more are in or the
    sequences together pass ``max_tokens``."""
    ok = [r for r in recs if not failed(r)]
    if not ok:
        return []
    ok.sort(key=lambda r: r.index)
    longest = max(ok, key=lambda r: (len(r.prompt) + r.want, r.index))
    picked, total = [longest], len(longest.prompt) + longest.want
    order = traffic.rng_for(seed, "check").permutation(len(ok))
    for j in order:
        r = ok[int(j)]
        if r is longest or len(picked) > n_random:
            continue
        if total + len(r.prompt) + r.want > max_tokens:
            continue
        picked.append(r)
        total += len(r.prompt) + r.want
    return picked


def check_numbers(cfg: dict, mix: dict, seed: int, res: dict, setup: dict):
    """Once the engine is freed: the reference over a seeded sample of
    what the window served."""
    from .. import check
    sample = check_sample(res["measured"], seed,
                          int(mix.get("check_requests", 5)),
                          int(mix.get("check_max_tokens", 8192)))
    res["sample"] = sample
    return check.serve_numbers(cfg, seed, sample)
