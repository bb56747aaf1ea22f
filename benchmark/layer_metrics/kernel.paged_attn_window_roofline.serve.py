"""The window layers' paged-attention kernel's share of its roofline: the
time the HBM needs for the KV rows a mean decode step of the window reads in
ONE window layer (the family's kernel_work(run, "paged_attention_window"):
for `cohere2_moe` delta of serving.kv_window_rows_read / delta of
decode_steps x 4,096 B a row, at most `sliding_window` rows a slot; its FLOPs
bound less) over the mean device time of a `paged_attention_window` event
inside complete `serving_decode` events (scope_reduce.py), exactly as
kernel.paged_attn_roofline.serve reads the full layers' call. None where the
trace has no such kernel (a family without window layers, or a program
before them)."""
import families
import kernel_counts as kc
import scope_reduce

META = {"name": "kernel.paged_attn_window_roofline.serve",
        "layer": "kernels", "unit": "%", "better": "higher",
        "source": "device_trace", "moves": "tpot_p95_ms",
        "drivers": ["serve_closed_loop"]}


def read(run):
    got = scope_reduce.per_event(run, "kernels", "paged_attention_window")
    work = families.of(run["cfg"]).kernel_work(run, "paged_attention_window")
    if got is None or work is None:
        return None
    seconds, n = got
    least, bound = kc.least_seconds(
        *work, run["peaks"]["devices"][run["device_kind"]])
    run["say"](f"paged_attention_window: least {1e3 * least:.4f} ms a layer "
               f"(bound: {bound}), measured {1e3 * seconds / n:.4f} ms")
    return 100.0 * least / (seconds / n)
