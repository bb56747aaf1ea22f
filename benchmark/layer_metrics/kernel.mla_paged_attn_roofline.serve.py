"""The latent (MLA) paged-attention kernel's share of its roofline: the time
the HBM needs for the latent rows a mean decode step of the window reads in
one layer (the family's kernel_work(run, "mla_paged_attention"): for `xing4`
delta of serving.kv_tokens_read / delta of decode_steps x 1,152 B, the row
read once for all heads; its FLOPs bound less) over the mean device time of
a `mla_paged_attention` event inside complete `serving_decode` events
(scope_reduce.py). None where the trace has no such kernel (another family,
or a program without it)."""
import families
import kernel_counts as kc
import scope_reduce

META = {"name": "kernel.mla_paged_attn_roofline.serve", "layer": "kernels",
        "unit": "%", "better": "higher", "source": "device_trace",
        "moves": "tpot_p95_ms", "drivers": ["serve_closed_loop"]}


def read(run):
    got = scope_reduce.per_event(run, "kernels", "mla_paged_attention")
    work = families.of(run["cfg"]).kernel_work(run, "mla_paged_attention")
    if got is None or work is None:
        return None
    seconds, n = got
    least, bound = kc.least_seconds(
        *work, run["peaks"]["devices"][run["device_kind"]])
    run["say"](f"mla_paged_attention: least {1e3 * least:.4f} ms a layer "
               f"(bound: {bound}), measured {1e3 * seconds / n:.4f} ms")
    return 100.0 * least / (seconds / n)
