"""Share of the traced stretch in which no operation ran on the device:
1 - union of the device-op intervals / traced span (trace_reduce.py),
averaged over the chips the cell uses."""
META = {"name": "device.idle_share.train", "layer": "device", "unit": "%",
        "better": "lower", "source": "device_trace", "moves": "train_tokens_per_s",
        "drivers": ["train_fixed_shape"]}


def read(run):
    t = run["trace"]
    if not t or not t["window_s"]:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
