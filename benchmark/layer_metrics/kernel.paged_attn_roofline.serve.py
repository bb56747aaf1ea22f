"""The paged-attention kernel's share of its roofline: the time the HBM
needs for the KV rows a mean decode step of the window reads in one layer
(the family's kernel_work(run, "paged_attention"): for `gpt` delta of
serving.kv_tokens_read / delta of decode_steps x kernel_counts.kv_row_bytes;
its FLOPs bound less) over the mean device time
of a `paged_attention` event inside complete `serving_decode` events
(scope_reduce.py)."""
import families
import kernel_counts as kc
import scope_reduce

META = {"name": "kernel.paged_attn_roofline.serve", "layer": "kernels",
        "unit": "%", "better": "higher", "source": "device_trace",
        "moves": "tpot_p95_ms", "drivers": ["serve_closed_loop"]}


def read(run):
    got = scope_reduce.per_event(run, "kernels", "paged_attention")
    work = families.of(run["cfg"]).kernel_work(run, "paged_attention")
    if got is None or work is None:
        return None
    seconds, n = got
    least, bound = kc.least_seconds(
        *work, run["peaks"]["devices"][run["device_kind"]])
    run["say"](f"paged_attention: least {1e3 * least:.4f} ms a layer "
               f"(bound: {bound}), measured {1e3 * seconds / n:.4f} ms")
    return 100.0 * least / (seconds / n)
