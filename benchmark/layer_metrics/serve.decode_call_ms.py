"""Mean host time of one decode call (dispatch + wait for its tokens) over
the window, from inside the engine: delta of serving.decode_step_ns / delta
of serving.decode_step_n (the `serving.decode_step` span). No prefill is in
it, unlike serve.step_ms."""
META = {"name": "serve.decode_call_ms", "layer": "serve entry", "unit": "ms",
        "better": "lower", "source": "program_counter",
        "moves": "tpot_p95_ms", "drivers": ["serve_closed_loop"]}


def read(run):
    c = run["counters"]
    n = c.get("serving.decode_step_n")
    return c["serving.decode_step_ns"] / n / 1e6 if n else None
