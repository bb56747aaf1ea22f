"""Flash-attention backward's share of its roofline: the least time one
layer's backward can take (the family's kernel_work(run, "flash_bwd"))
over the mean device time of one `flash_bwd_dkv` plus one `flash_bwd_dq`
event inside complete `train_step` events (scope_reduce.py)."""
import families
import kernel_counts as kc
import scope_reduce

META = {"name": "kernel.flash_bwd_roofline.train", "layer": "kernels",
        "unit": "%", "better": "higher", "source": "device_trace",
        "moves": "train_tokens_per_s", "drivers": ["train_fixed_shape"]}


def read(run):
    got = scope_reduce.per_event(run, "kernels", "flash_bwd_dkv",
                                 "flash_bwd_dq")
    work = families.of(run["cfg"]).kernel_work(run, "flash_bwd")
    if got is None or work is None:
        return None
    seconds, n = got
    per_layer = seconds / (n / 2)  # one event of each a layer
    least, bound = kc.least_seconds(
        *work, run["peaks"]["devices"][run["device_kind"]])
    run["say"](f"flash_bwd: least {1e3 * least:.4f} ms a layer (bound: "
               f"{bound}), measured {1e3 * per_layer:.4f} ms (dkv + dq)")
    return 100.0 * least / per_layer
