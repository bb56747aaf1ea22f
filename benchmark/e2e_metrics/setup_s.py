"""Process start to window open: import, weights, compile or cache load,
warm-up and, where the driver has one, the ramp."""
META = {"name": "setup_s", "unit": "s", "better": "lower",
        "source": "host_clock",
        "drivers": ["train_fixed_shape", "serve_closed_loop"]}


def read(run):
    return run["setup_s"]
