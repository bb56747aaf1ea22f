"""The decode step's share of its roofline: the least time a mean step of
the window can take (the family's decode_step_work: the larger of its FLOPs
over the bf16 peak and its bytes — for `gpt` the weights once, the KV rows
read — over the HBM bandwidth) over the mean device time of a complete
`serving_decode` module event (scope_reduce.py)."""
import families
import kernel_counts as kc
import scope_reduce

META = {"name": "serve.decode_step_roofline", "layer": "device", "unit": "%",
        "better": "higher", "source": "device_trace",
        "moves": "tpot_p95_ms", "drivers": ["serve_closed_loop"]}


def read(run):
    got = scope_reduce.per_event(run, "modules", "serving_decode")
    means = kc.decode_step_means(run["counters"])
    work = families.of(run["cfg"]).decode_step_work(run)
    if got is None or means is None or work is None:
        return None
    seconds, n = got
    slots, rows = means  # the window's own, whatever the family counts
    least, bound = kc.least_seconds(
        *work, run["peaks"]["devices"][run["device_kind"]])
    run["say"](f"decode step: {slots:.2f} slots, {rows:.0f} KV rows; least "
               f"{1e3 * least:.4f} ms (bound: {bound}), measured "
               f"{1e3 * seconds / n:.4f} ms")
    return 100.0 * least / (seconds / n)
