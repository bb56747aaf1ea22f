"""Peak bytes in use on the fullest chip, from memory_stats() after the
window and the check, in GB (1e9 bytes)."""
META = {"name": "device.hbm_peak_gb.serve", "layer": "device", "unit": "GB",
        "better": "lower", "source": "program_counter", "moves": "serve_tokens_per_s",
        "drivers": ["serve_closed_loop"]}


def read(run):
    b = run["memory_peak_bytes"]
    return b / 1e9 if b else None
