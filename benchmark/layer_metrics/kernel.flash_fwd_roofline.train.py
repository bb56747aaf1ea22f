"""Flash-attention forward's share of its roofline: the least time one
layer's call can take (the family's kernel_work(run, "flash_fwd"), the
larger of the two bounds) over the mean device time of a `flash_fwd` event
inside complete `train_step` events (scope_reduce.py)."""
import families
import kernel_counts as kc
import scope_reduce

META = {"name": "kernel.flash_fwd_roofline.train", "layer": "kernels",
        "unit": "%", "better": "higher", "source": "device_trace",
        "moves": "train_tokens_per_s", "drivers": ["train_fixed_shape"]}


def read(run):
    got = scope_reduce.per_event(run, "kernels", "flash_fwd")
    work = families.of(run["cfg"]).kernel_work(run, "flash_fwd")
    if got is None or work is None:
        return None
    seconds, n = got
    least, bound = kc.least_seconds(
        *work, run["peaks"]["devices"][run["device_kind"]])
    run["say"](f"flash_fwd: least {1e3 * least:.4f} ms a layer (bound: "
               f"{bound}), measured {1e3 * seconds / n:.4f} ms")
    return 100.0 * least / (seconds / n)
