"""Tokens trained in the window over the window's seconds, per chip: all
the steps dispatched in it, timed to the sync that ends it."""
META = {"name": "train_tokens_per_s", "unit": "tokens/s", "better": "higher",
        "source": "host_clock", "drivers": ["train_fixed_shape"]}


def read(run):
    s = run["samples"]
    return s["tokens"] / s["window_s"] / int(run["wl"]["chips"])
