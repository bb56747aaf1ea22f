"""The share of the window's linear-layer steps whose state update ran
through the Pallas kernel `gated_delta_step` (each slot's state read once
and written once in place) and not the plain XLA route: 100 x delta of
serving.linear_kernel_layer_steps / delta of serving.linear_layer_steps.
None where the program keeps no such counters or counts no step of a state
layer (a family without linear layers, or a program before them)."""
META = {"name": "linear.state_kernel_share.serve", "layer": "kernels",
        "unit": "%", "better": "higher", "source": "program_counter",
        "moves": "tpot_p95_ms", "drivers": ["serve_closed_loop"]}


def read(run):
    c = run["counters"]
    layer_steps = c.get("serving.linear_layer_steps")
    if not layer_steps or "serving.linear_kernel_layer_steps" not in c:
        return None
    return 100.0 * c["serving.linear_kernel_layer_steps"] / layer_steps
