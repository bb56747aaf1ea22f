"""Mean host time of one prefill call (dispatch + wait for the first token)
over the window: delta of serving.prefill_ns / delta of serving.prefill_n
(the `serving.prefill` span)."""
META = {"name": "serve.prefill_call_ms", "layer": "serve entry", "unit": "ms",
        "better": "lower", "source": "program_counter",
        "moves": "serve_tokens_per_s", "drivers": ["serve_closed_loop"]}


def read(run):
    c = run["counters"]
    n = c.get("serving.prefill_n")
    return c["serving.prefill_ns"] / n / 1e6 if n else None
