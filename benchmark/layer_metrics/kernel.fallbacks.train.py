"""Attention calls traced in the window that did not resolve to the Pallas
flash kernel: delta of kernel.flash.fallbacks + kernel.flash.xla."""
META = {"name": "kernel.fallbacks.train", "layer": "kernels",
        "unit": "count", "better": "lower", "source": "program_counter",
        "moves": "train_tokens_per_s", "drivers": ["train_fixed_shape"]}


def read(run):
    c = run["counters"]
    if "kernel.flash.fallbacks" not in c:
        return None
    n = c["kernel.flash.fallbacks"]
    # off the chip XLA attention is the only route, not a fallback
    return n + c.get("kernel.flash.xla", 0) if run["on_tpu"] else n
