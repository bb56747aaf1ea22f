"""Model FLOPs of a mean decode step of the window
(the family's decode_step_work: for `gpt` active slots and KV rows from the
deltas of serving.active_slot_steps, kv_tokens_read, decode_steps) over the mean
device time of a complete `serving_decode` module event (scope_reduce.py)
over the chip's bf16 peak."""
import families
import flops
import scope_reduce

META = {"name": "serve.decode_step_mfu", "layer": "device", "unit": "%",
        "better": "higher", "source": "device_trace",
        "moves": "tpot_p95_ms", "drivers": ["serve_closed_loop"]}


def read(run):
    got = scope_reduce.per_event(run, "modules", "serving_decode")
    work = families.of(run["cfg"]).decode_step_work(run)
    if got is None or work is None:
        return None
    seconds, n = got
    fl = work[0]
    pk = flops.peak(run["peaks"], run["device_kind"], "bf16_flops_per_s")
    return 100.0 * fl / (seconds / n) / pk
