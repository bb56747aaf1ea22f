"""Mean time from submit to the start of admission over the requests
admitted in the window: delta of serving.queue_wait_ns / delta of
serving.admitted."""
META = {"name": "serve.queue_wait_ms", "layer": "serve entry", "unit": "ms",
        "better": "lower", "source": "program_counter",
        "moves": "serve_tokens_per_s", "drivers": ["serve_closed_loop"]}


def read(run):
    c = run["counters"]
    n = c.get("serving.admitted")
    return c["serving.queue_wait_ns"] / n / 1e6 if n else None
