"""The gated-delta kernel's share of its roofline: the time the HBM needs to
read and write once the state of every active slot of a mean decode step of
the window in one linear layer (the family's kernel_work(run,
"gated_delta_step"): for `qwen3_next` delta of serving.active_slot_steps /
delta of decode_steps x 2 x the state's 2 MiB; its FLOPs bound less) over
the mean device time of a `gated_delta_step` event inside complete
`serving_decode` events (scope_reduce.py). None where the trace has no such
kernel (a family without linear layers, or a program before the kernel)."""
import families
import kernel_counts as kc
import scope_reduce

META = {"name": "kernel.gated_delta_roofline.serve", "layer": "kernels",
        "unit": "%", "better": "higher", "source": "device_trace",
        "moves": "tpot_p95_ms", "drivers": ["serve_closed_loop"]}


def read(run):
    got = scope_reduce.per_event(run, "kernels", "gated_delta_step")
    if got is None:
        return None
    work = families.of(run["cfg"]).kernel_work(run, "gated_delta_step")
    if work is None:
        return None
    seconds, n = got
    least, bound = kc.least_seconds(
        *work, run["peaks"]["devices"][run["device_kind"]])
    run["say"](f"gated_delta_step: least {1e3 * least:.4f} ms a layer "
               f"(bound: {bound}), measured {1e3 * seconds / n:.4f} ms")
    return 100.0 * least / (seconds / n)
