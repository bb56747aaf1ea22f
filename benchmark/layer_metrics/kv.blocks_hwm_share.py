"""High-water mark of KV blocks in use (serving.kv_blocks_hwm, since the
engine was built) as a share of the pool."""
META = {"name": "kv.blocks_hwm_share", "layer": "block pool", "unit": "%",
        "better": "lower", "source": "program_counter",
        "moves": "serve_tokens_per_s", "drivers": ["serve_closed_loop"]}


def read(run):
    hwm = run["counters_abs"].get("serving.kv_blocks_hwm")
    e = run["wl"]["engine"]
    pool = 1 + int(e["max_batch_size"]) * int(e["blocks_per_slot"])
    return 100.0 * hwm / pool if hwm else None
