"""Share of the decode batch's lanes that held a live request: delta of
active_slot_steps / (delta of decode_steps x max_batch_size)."""
META = {"name": "serve.batch_occupancy", "layer": "serve entry", "unit": "%",
        "better": "higher", "source": "program_counter",
        "moves": "serve_tokens_per_s", "drivers": ["serve_closed_loop"]}


def read(run):
    c = run["counters"]
    n = c.get("serving.decode_steps")
    if not n:
        return None
    slots = int(run["wl"]["engine"]["max_batch_size"])
    return 100.0 * c["serving.active_slot_steps"] / (n * slots)
