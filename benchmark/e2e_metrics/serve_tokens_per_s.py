"""Output tokens given to requests inside the window (every request, for
its part of the window; counted by the benchmark from the handles) over
the window's seconds."""
META = {"name": "serve_tokens_per_s", "unit": "tokens/s", "better": "higher",
        "source": "host_clock", "drivers": ["serve_closed_loop"]}


def read(run):
    s = run["samples"]
    return s["tokens"] / s["window_s"] if s["tokens"] else None
