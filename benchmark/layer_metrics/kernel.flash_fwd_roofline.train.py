"""Flash-attention forward's share of its roofline: the least time one
layer's call can take (kernel_counts.flash_fwd_flops / flash_fwd_bytes, the
larger of the two bounds) over the mean device time of a `flash_fwd` event
inside complete `train_step` events (scope_reduce.py)."""
import kernel_counts as kc
import model as bench_model
import scope_reduce

META = {"name": "kernel.flash_fwd_roofline.train", "layer": "kernels",
        "unit": "%", "better": "higher", "source": "device_trace",
        "moves": "train_tokens_per_s", "drivers": ["train_fixed_shape"]}


def read(run):
    got = scope_reduce.per_event(run, "kernels", "flash_fwd")
    if got is None:
        return None
    seconds, n = got
    sizes = bench_model.sizes(run["cfg"])
    B = int(run["traffic"]["batch"]) // int(run["wl"]["chips"])
    least, bound = kc.least_seconds(
        kc.flash_fwd_flops(sizes, B), kc.flash_fwd_bytes(sizes, B),
        run["peaks"]["devices"][run["device_kind"]])
    run["say"](f"flash_fwd: least {1e3 * least:.4f} ms a layer (bound: "
               f"{bound}), measured {1e3 * seconds / n:.4f} ms")
    return 100.0 * least / (seconds / n)
