"""Paged-attention calls traced in the window that fell back from the
engine's resolved kernel: delta of serving.kernel.fallbacks."""
META = {"name": "kernel.fallbacks.serve", "layer": "kernels",
        "unit": "count", "better": "lower", "source": "program_counter",
        "moves": "tpot_p95_ms", "drivers": ["serve_closed_loop"]}


def read(run):
    return run["counters"].get("serving.kernel.fallbacks")
