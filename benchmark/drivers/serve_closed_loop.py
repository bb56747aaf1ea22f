"""Driver `serve_closed_loop`: GenerationServer over a GenerationEngine,
driven by a fixed number of clients that each send their next request when
the last one is done.

Built from chip_smoke.py's serving leg (`_serve_engine`, `_serve_wave`,
`Scorer`, `_compare_greedy`), which passed on the chip in PR 21. The
traffic file gives the request sizes: a table of (prompt length, new
tokens, greedy or sampled) drawn once from the table's OWN seed and taken
in the table's order, so every run seed serves the same sizes in the same
order; --seed draws the token ids and the weights. (With sizes shuffled by
the seed, which requests end inside a window of a few dozen decode steps
differed from seed to seed, and with it the number of prefills.)

The window opens when the loop is steady: every executable warmed by one
request per bucket, then a ramp in which every client finishes one short
request, so that finishes are out of step. Every request that is given a
token inside the window is a sample, for the tokens it is given there: the
benchmark reads each handle's token count and last-token time when the
window opens and when it closes, so a request that began before the window
or is cut by its end counts for its part (a decode step of 470 ms, as PR 25
found, finishes one or two requests in 30 s, and a tail over those alone
would be no tail). Requests in flight at the close are then cancelled.

Correctness is decided against the family's plain reference
(families/<family>.py: reference/gpt.py for `gpt`, a float32 forward with no
cache): for some greedy requests of the window, each served token at a
sample of positions must be the reference's argmax given the served
prefix, or lie within `near_tie` of its top logit (random weights give
near-uniform logits, and bf16 arithmetic legitimately decides such a tie).
"""
from __future__ import annotations

import itertools
import math
import os
import threading
import time

import numpy as np

import families
import stats


def size_table(tr):
    """The traffic's request sizes, the same for every run seed."""
    rng = np.random.default_rng(int(tr["table_seed"]))
    n = int(tr["table_size"])
    lo, hi = tr["prompt_len"]
    plen = np.exp(rng.uniform(math.log(lo), math.log(hi), n))
    plen = np.clip(np.rint(plen), lo, hi).astype(int)
    nlo, nhi = tr["max_new_tokens"]
    new = rng.integers(nlo, nhi + 1, n)
    greedy = np.arange(n) % int(tr["greedy_every"]) == 0
    return [(int(p), int(m), bool(g)) for p, m, g in zip(plen, new, greedy)]


class Requests:
    """The run's requests, in the order the clients take them: the table
    in its own order, again for every further pass over it."""

    def __init__(self, tr, vocab, seed):
        self.tr, self.vocab = tr, vocab
        self.table = size_table(tr)
        self.rng = np.random.default_rng(seed)
        self.seed = seed
        self.count = itertools.count()
        # token ids for a few passes, drawn in bulk during set-up
        self.order = list(range(len(self.table))) * int(tr.get("passes", 4))
        total = sum(self.table[i][0] for i in self.order)
        self.tokens = self.rng.integers(1, vocab, total, dtype=np.int32)
        self.starts = np.concatenate(
            [[0], np.cumsum([self.table[i][0] for i in self.order])])

    def options(self, k, new, greedy):
        opts = {"max_new_tokens": int(new),
                "seed": (self.seed * 7919 + k) % (2 ** 31 - 1)}
        if not greedy:
            opts.update(temperature=float(self.tr["temperature"]),
                        top_k=int(self.tr["top_k"]))
        return opts

    def next(self):
        k = next(self.count) % len(self.order)
        plen, new, greedy = self.table[self.order[k]]
        prompt = self.tokens[self.starts[k]:self.starts[k] + plen].tolist()
        return prompt, greedy, self.options(k, new, greedy)

    def ramp(self, i):
        """Client i's first request: this traffic's prompt, a short
        answer, never a sample."""
        plen, _, greedy = self.table[i % len(self.table)]
        lo, hi = self.tr["ramp_new_tokens"]
        new = int(np.random.default_rng(
            [int(self.tr["table_seed"]), 1, i]).integers(lo, hi + 1))
        prompt = np.random.default_rng([self.seed, 1, i]).integers(
            1, self.vocab, plen).tolist()
        return prompt, greedy, self.options(10 ** 6 + i, new, greedy)


def setup(run):
    from paddle_tpu.serving import GenerationEngine, GenerationServer

    tr, wl, say = run["traffic"], run["wl"], run["say"]
    fam = families.of(run["cfg"])
    forbidden = sorted(k for k in os.environ if k.startswith("PADDLE_TPU_"))
    if forbidden:
        raise SystemExit(f"serve_closed_loop: unset {forbidden}: the cell "
                         "runs the package's defaults")
    t0 = time.perf_counter()
    cfg, model = fam.build(run["cfg"], run["seed"])
    vocab = fam.vocab_size(run["cfg"])
    model.eval()
    eng_cfg = wl["engine"]
    clients = int(tr["clients"])
    if clients != int(eng_cfg["max_batch_size"]):
        raise SystemExit("serve_closed_loop: clients must equal the "
                         "engine's slots (the loop keeps the batch full "
                         "without a queue)")
    longest = tr["prompt_len"][1] + tr["max_new_tokens"][1]
    per_slot = int(eng_cfg["blocks_per_slot"])
    if per_slot * int(eng_cfg["block_size"]) < longest:
        raise SystemExit(f"blocks_per_slot {per_slot} cannot hold the "
                         f"longest request ({longest} tokens)")
    eng = GenerationEngine(
        model, max_batch_size=clients, buckets=tuple(eng_cfg["buckets"]),
        max_seq_len=int(eng_cfg["max_seq_len"]),
        block_size=int(eng_cfg["block_size"]),
        num_blocks=1 + clients * per_slot,
        rng_seed=run["seed"] % (2 ** 31 - 1))
    server = GenerationServer(engine=eng, max_queue_size=2 * clients)
    want = "pallas" if run["on_tpu"] else "xla"
    say(f"engine: paged kernel {eng.paged_kernel!r} "
        f"({eng.stats()['paged_kernel_reason']}); {clients} slots, pool "
        f"{eng.pool.num_blocks} blocks of {eng.block_size}, buckets "
        f"{eng.buckets}, max_seq_len {eng.max_seq_len}")
    if eng.paged_kernel != want:
        raise SystemExit(f"serve_closed_loop: paged kernel resolved to "
                         f"{eng.paged_kernel!r}, expected {want!r}")
    reqs = Requests(tr, vocab, run["seed"])
    t1 = time.perf_counter()

    # warm every executable the traffic uses: one request per bucket (both
    # sampling modes ride one executable; the modes are arrays), then decode
    warm = []
    for j, b in enumerate(eng.buckets):
        n = min(b, tr["prompt_len"][1])
        prompt = np.random.default_rng([run["seed"], 2, j]).integers(
            1, vocab, n).tolist()
        warm.append(server.submit(prompt, **reqs.options(
            2 * 10 ** 6 + j, 4, greedy=bool(j % 2))))
    for h in warm:
        h.result(timeout=1500)
        if h.status != "done":
            raise SystemExit(f"warm-up request failed: {h.status} {h.error}")
    t2 = time.perf_counter()

    # the reference's executable, warmed on a dummy so the check after the
    # window only runs it
    score = fam.reference_scorer(
        run["cfg"], cfg, model, int(tr["check"]["padded_len"]),
        int(tr["check"]["positions"]))
    t3 = time.perf_counter()

    state = {"server": server, "eng": eng, "vocab": vocab, "reqs": reqs,
             "score": score, "records": [],
             "refused": [], "stop": threading.Event(), "threads": [],
             "clients": clients, "live": [None] * clients}
    ramp_done = [threading.Event() for _ in range(clients)]
    for i in range(clients):
        th = threading.Thread(target=_client, name=f"bench-client-{i}",
                              args=(run, state, i, ramp_done[i]),
                              daemon=True)
        th.start()
        state["threads"].append(th)
    for ev in ramp_done:
        if not ev.wait(600):
            raise SystemExit("serve_closed_loop: the ramp did not finish")
    t4 = time.perf_counter()
    say(f"set-up parts: import+start {t0 - run['t_start']:.2f} s, model + "
        f"engine + requests {t1 - t0:.2f} s, warm-up of "
        f"{len(eng.buckets)} prefill buckets + decode {t2 - t1:.2f} s, "
        f"reference executable {t3 - t2:.2f} s, ramp {t4 - t3:.2f} s")
    return state


def _client(run, state, i, ramp_done):
    """One closed-loop client: submit, wait, record, again."""
    server, reqs, stop = state["server"], state["reqs"], state["stop"]
    annotate = run["tracer"].annotate
    ramp = True
    while not stop.is_set():
        prompt, greedy, opts = reqs.ramp(i) if ramp else reqs.next()
        try:
            h = server.submit(prompt, **opts)
        except RuntimeError as e:  # QueueFullError, or shutting down
            if stop.is_set():
                return
            state["refused"].append((time.monotonic(), repr(e)))
            ramp_done.set()
            time.sleep(0.01)
            continue
        rec = (prompt, greedy, opts, h)
        if not ramp:
            state["live"][i] = rec
        with annotate("bench.client_wait"):
            while not h.finished.wait(0.5):
                if stop.is_set():
                    return
        if ramp:
            ramp = False
            ramp_done.set()
        else:
            state["records"].append(rec)


def _mark(h):
    """(tokens so far, time of the last one, ended?) of a request, read
    while the scheduler thread may be appending: taken again if the two
    readings of the time disagree."""
    while True:
        ts, n, done = h.last_tok_ts, len(h.tokens), h.done
        if h.last_tok_ts == ts:
            return n, ts, done


def window(run, state, seconds):
    tracer = run["tracer"]
    t_open = time.monotonic()
    at_open = {id(r[3]): _mark(r[3]) for r in list(state["live"]) if r}
    while True:
        left = seconds - (time.monotonic() - t_open)
        if left <= 0:
            break
        if tracer.due(seconds - left):
            tracer.start()
        time.sleep(min(0.02, left))
    t_close = time.monotonic()
    state["stop"].set()
    live = [r for r in list(state["live"]) if r]
    at_close = {id(r[3]): _mark(r[3]) for r in live}
    seen = {id(r[3]): r for r in list(state["records"])}
    seen.update((id(r[3]), r) for r in live)

    parts, failed = [], 0
    for key, rec in seen.items():
        h = rec[3]
        # a request replaced by its client's next one ended before the close
        n1, ts1, ended = at_close.get(key) or (len(h.tokens), h.last_tok_ts,
                                               True)
        if ended and h.status != "done":
            failed += ts1 is None or ts1 >= t_open
            continue
        if ts1 is None or ts1 < t_open:
            continue  # no token in the window
        n0, ts0, _ = at_open.get(key, (0, None, False))
        first_in = ts0 is None  # its first token fell inside the window
        if first_in:
            n0, ts0 = 1, h.first_tok_ts  # gaps are counted from token 1
        parts.append({"rec": rec, "tokens": n1 - (0 if first_in else n0),
                      "gaps": n1 - n0, "span_s": ts1 - ts0, "ended": ended,
                      "ttft_s": h.ttft_s if first_in else None})
    failed += sum(1 for t, _ in list(state["refused"])
                  if t_open <= t <= t_close)
    sched = state["server"].scheduler
    min_gaps = int(run["traffic"]["tpot_min_gaps"])
    return {"window_s": t_close - t_open, "parts": parts,
            "attempted": len(parts) + failed, "failed": failed,
            "finished": sum(p["ended"] for p in parts),
            "in_flight_at_close": sched.active() + sched.prefilling()
            + sched.queued(),
            "tokens": sum(p["tokens"] for p in parts),
            "ttft_s": [p["ttft_s"] for p in parts
                       if p["ttft_s"] is not None],
            "tpot_s": [p["span_s"] / p["gaps"] for p in parts
                       if p["gaps"] >= min_gaps]}


def check(run, state, samples):
    import jax.numpy as jnp

    say, tr = run["say"], run["traffic"]
    server, eng, vocab = state["server"], state["eng"], state["vocab"]
    # cancel what is in flight, stop the clients, then audit the pool
    server.shutdown(drain=False, timeout=120)
    for th in state["threads"]:
        th.join(60)
    alive = [th.name for th in state["threads"] if th.is_alive()]
    ok = not alive
    if alive:
        say(f"client threads still alive: {alive}")
    try:
        audit = eng.pool.audit()
    except AssertionError as e:
        audit, ok = f"VIOLATED: {e}", False
    c = run["counters"]
    say(f"pool audit {audit}; pool_exhausted in window "
        f"{c.get('serving.pool_exhausted')}, prefix hits "
        f"{c.get('serving.prefix_hits')}, prefills "
        f"{c.get('serving.prefills')}, decode steps "
        f"{c.get('serving.decode_steps')}, kv_blocks_hwm "
        f"{run['counters_abs'].get('serving.kv_blocks_hwm')} of "
        f"{eng.pool.num_blocks}, kernel fallbacks "
        f"{c.get('serving.kernel.fallbacks')}")

    parts = samples["parts"]
    def wrong(p):
        _, _, opts, h = p["rec"]
        n, want = len(h.tokens), opts["max_new_tokens"]
        return (n != want if p["ended"] else n > want) \
            or not all(0 <= t < vocab for t in h.tokens)

    bad = [(p["rec"][3].status, len(p["rec"][3].tokens)) for p in parts
           if wrong(p)]
    if bad or samples["failed"] or not parts:
        say(f"requests: {len(parts)} given tokens in the window, "
            f"{samples['failed']} failed, {len(bad)} with wrong length or "
            f"ids {bad[:3]}")
        ok = False
    if run["compiles_in_window"]:
        say(f"{run['compiles_in_window']} compiles inside the window")
        ok = False
    if c.get("serving.kernel.fallbacks"):
        say("the paged kernel fell back inside the window")
        ok = False

    # served tokens against the plain reference
    L, K = int(tr["check"]["padded_len"]), int(tr["check"]["positions"])
    tie = float(tr["check"]["near_tie"])
    # greedy requests of the window, the finished ones first; one cut
    # short by the window's end is checked on the tokens it was given
    greedy = sorted((p for p in parts if p["rec"][1]
                     and len(p["rec"][3].tokens) >= K),
                    key=lambda p: not p["ended"])
    greedy = [p["rec"] for p in greedy[:int(tr["check"]["requests"])]]
    worst, off, n_pos = 0.0, 0, 0
    for prompt, _, _, h in greedy:
        toks = [int(t) for t in h.tokens]
        ids = np.zeros(L, np.int32)
        ids[:len(prompt) + len(toks)] = prompt + toks
        idx = np.unique(np.linspace(0, len(toks) - 1, K).astype(int))
        at = np.full(K, len(prompt) - 1, np.int32)
        at[:len(idx)] += idx
        lg = np.asarray(state["score"](jnp.asarray(ids), jnp.asarray(at)),
                        np.float32)
        for k, i in enumerate(idx):
            gap = float(lg[k].max() - lg[k, toks[i]])
            worst = max(worst, gap)
            off += int(np.argmax(lg[k])) != toks[i]
            n_pos += 1
    ref_ok = bool(greedy) and worst <= tie
    say(f"reference check: {len(greedy)} greedy requests, {n_pos} "
        f"positions, {off} off the reference's argmax, worst logit gap "
        f"below the top {worst:.5f} (near-tie limit {tie}) "
        f"({'ok' if ref_ok else 'NOT ok'})")

    def ms(values, q):
        p = stats.percentile(values, q)
        return "none" if p is None else f"{1e3 * p:.3f}"

    say(f"{len(parts)} requests given {samples['tokens']} tokens in "
        f"{samples['window_s']:.3f} s ({samples['finished']} finished in "
        f"it, {samples['in_flight_at_close']} in flight at close, "
        f"cancelled): {samples['tokens'] / samples['window_s']:.2f} output "
        f"tokens/s; tpot ms over {len(samples['tpot_s'])} requests p50 "
        f"{ms(samples['tpot_s'], 50)} p95 {ms(samples['tpot_s'], 95)}; ttft "
        f"ms over {len(samples['ttft_s'])} first tokens p50 "
        f"{ms(samples['ttft_s'], 50)} p95 {ms(samples['ttft_s'], 95)}")
    run["compared"] = {
        "worst_logit_gap": [worst, tie],
        "greedy_requests_compared_at_least": [len(greedy), 1],
        "requests_failed": [int(samples["failed"]), 0],
        "requests_wrong_length_or_ids": [len(bad), 0],
        "client_threads_alive": [len(alive), 0],
        "pool_audit_violated": [int(isinstance(audit, str)), 0],
        "compiles_in_window": [run["compiles_in_window"], 0],
        "kernel_fallbacks": [c.get("serving.kernel.fallbacks") or 0, 0]}
    return ok and ref_ok
