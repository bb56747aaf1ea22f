"""Share of the window the server thread spent waiting for work: delta of
serving.loop_idle_ns (the `serving.loop_idle` span around `_work.wait`) /
window seconds."""
META = {"name": "serve.loop_idle_share", "layer": "serve entry", "unit": "%",
        "better": "lower", "source": "program_counter",
        "moves": "serve_tokens_per_s", "drivers": ["serve_closed_loop"]}


def read(run):
    c = run["counters"]
    if "serving.sched_steps" not in c:  # a program without the span
        return None
    return 100.0 * c.get("serving.loop_idle_ns", 0) / 1e9 / run["window_s"]
