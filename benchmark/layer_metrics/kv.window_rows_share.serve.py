"""What a window layer reads of what a full layer reads, in a mean decode
step of the window: 100 x delta of serving.kv_window_rows_read (the sum over
active slots of min(length, window), for ONE window layer) / delta of
serving.kv_tokens_read (the sum of the lengths: one full layer's rows). It
explains the step's bytes as moe.experts_hit_share.serve does: 100 means no
slot is past the window and a ring saves nothing, lower means the window
bites. None where the program keeps no such counter or it did not move (a
family without window layers, or a program before them)."""
META = {"name": "kv.window_rows_share.serve", "layer": "block pool",
        "unit": "%", "better": "lower", "source": "program_counter",
        "moves": "tpot_p95_ms", "drivers": ["serve_closed_loop"]}


def read(run):
    c = run["counters"]
    win, full = c.get("serving.kv_window_rows_read"), c.get(
        "serving.kv_tokens_read")
    if not win or not full:
        return None
    return 100.0 * win / full
