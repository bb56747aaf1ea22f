"""99th percentile of single gaps between consecutive tokens of one request
(`GenerationRequest.tok_ts`), over the requests given a token in the window
and the gaps that end in the last `window_s` before the newest stamp: what
a client sees when somebody else's prefill lands between two of its
tokens."""
import stats

META = {"name": "serve.itl_p99_ms", "layer": "serve entry", "unit": "ms",
        "better": "lower", "source": "host_clock", "moves": "tpot_p95_ms",
        "drivers": ["serve_closed_loop"]}


def read(run):
    stamps = [getattr(p["rec"][3], "tok_ts", None)
              for p in run["samples"]["parts"]]
    stamps = [list(s) for s in stamps if s]
    if not stamps:
        return None
    since = max(s[-1] for s in stamps) - run["window_s"]
    gaps = [b - a for s in stamps for a, b in zip(s, s[1:]) if b > since]
    p = stats.percentile(gaps, 99)
    return None if p is None else 1e3 * p
