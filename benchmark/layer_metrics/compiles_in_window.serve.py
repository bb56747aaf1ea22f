"""Executables JAX built or loaded inside the window (profiler.CompileWatch,
JAX's own compile events). Should be 0."""
META = {"name": "compiles_in_window.serve", "layer": "jit cache",
        "unit": "count", "better": "lower", "source": "program_counter",
        "moves": "serve_tokens_per_s", "drivers": ["serve_closed_loop"]}


def read(run):
    return run["compiles_in_window"]
