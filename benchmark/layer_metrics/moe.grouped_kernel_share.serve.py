"""The share of the window's expert layer-steps whose grouped matmuls ran
through the Pallas kernel `grouped_matmul` (each hit expert's weight tiles
copied once) and not XLA's `ragged-dot`: 100 x delta of
serving.moe_kernel_layer_steps / delta of serving.moe_layer_steps. 100 where
the engine's plan gives the decode step's shapes to the kernel, 0 where it
keeps `lax.ragged_dot` (by shape, or a fallback, which `kernel.fallbacks`
also counts). None where the program keeps no such counters (a family without
expert layers, or a program before the kernel)."""
META = {"name": "moe.grouped_kernel_share.serve", "layer": "kernels",
        "unit": "%", "better": "higher", "source": "program_counter",
        "moves": "tpot_p95_ms", "drivers": ["serve_closed_loop"]}


def read(run):
    c = run["counters"]
    layer_steps = c.get("serving.moe_layer_steps")
    if not layer_steps or "serving.moe_kernel_layer_steps" not in c:
        return None
    return 100.0 * c["serving.moe_kernel_layer_steps"] / layer_steps
