"""Model FLOPs of one step (the family's train_flops_per_token x the batch's
tokens) over the mean device time of a complete `train_step` module event
(scope_reduce.py) over the chip's bf16 peak. Device time, so the host's
gaps are not in it: train_tokens_per_s x FLOPs/token / peak is lower by the
idle share."""
import families
import flops
import scope_reduce

META = {"name": "train.step_mfu", "layer": "device", "unit": "%",
        "better": "higher", "source": "device_trace",
        "moves": "train_tokens_per_s", "drivers": ["train_fixed_shape"]}


def read(run):
    got = scope_reduce.per_event(run, "modules", "train_step")
    fl = families.of(run["cfg"]).train_flops_per_token(run)
    if got is None or fl is None:
        return None
    seconds, n = got
    tr = run["traffic"]
    tokens = int(tr["batch"]) * int(tr["seq_len"]) / int(run["wl"]["chips"])
    pk = flops.peak(run["peaks"], run["device_kind"], "bf16_flops_per_s")
    return 100.0 * fl * tokens / (seconds / n) / pk
