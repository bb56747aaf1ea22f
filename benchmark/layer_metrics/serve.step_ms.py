"""Window seconds over the decode iterations in it (delta of
serving.decode_steps), so it holds the prefills interleaved between them."""
META = {"name": "serve.step_ms", "layer": "serve entry", "unit": "ms",
        "better": "lower", "source": "program_counter",
        "moves": "tpot_p95_ms", "drivers": ["serve_closed_loop"]}


def read(run):
    n = run["counters"].get("serving.decode_steps")
    return 1e3 * run["window_s"] / n if n else None
