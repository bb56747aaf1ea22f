"""Median time from submit to first token (GenerationRequest.ttft_s) over
the requests whose first token fell inside the window. A median and a
per-layer metric until the decode step is fast enough for a window to admit
the hundreds of requests a 95th percentile needs (PERF.md section 7)."""
import stats

META = {"name": "serve.ttft_p50_ms", "layer": "serve entry", "unit": "ms",
        "better": "lower", "source": "host_clock",
        "moves": "serve_tokens_per_s", "drivers": ["serve_closed_loop"]}


def read(run):
    p = stats.percentile(run["samples"]["ttft_s"], 50)
    return None if p is None else 1e3 * p
