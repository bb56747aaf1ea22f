"""The share of a layer's routed experts that a mean decode step of the
window gave at least one row: delta of serving.moe_experts_hit / (delta of
serving.moe_layer_steps x the experts a layer holds). It explains the step's
bytes: an expert nobody chose is not read, so lower is fewer weights read.
None where the program keeps no such counters (a family without expert
layers, or a program before them)."""
META = {"name": "moe.experts_hit_share.serve", "layer": "model", "unit": "%",
        "better": "lower", "source": "program_counter",
        "moves": "tpot_p95_ms", "drivers": ["serve_closed_loop"]}


def read(run):
    c = run["counters"]
    layer_steps = c.get("serving.moe_layer_steps")
    held = run["cfg"].get("n_routed_experts")
    if not layer_steps or not held:
        return None
    return 100.0 * c.get("serving.moe_experts_hit", 0) / (layer_steps * held)
