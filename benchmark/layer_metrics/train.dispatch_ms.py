"""Host time from calling the train step to its return, before any sync:
median over the window's steps."""
META = {"name": "train.dispatch_ms", "layer": "train entry", "unit": "ms",
        "better": "lower", "source": "host_clock",
        "moves": "train_tokens_per_s", "drivers": ["train_fixed_shape"]}


def read(run):
    d = sorted(run["samples"].get("dispatch_s", ()))
    return 1e3 * d[len(d) // 2] if d else None
