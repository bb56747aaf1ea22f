"""Mean host time of one `TrainStep.__call__` over the window, from inside
the program: delta of train.step_ns / delta of train.step_n (the
`train.step` span of paddle_tpu/profiler/spans.py)."""
META = {"name": "train.step_call_ms", "layer": "train entry", "unit": "ms",
        "better": "lower", "source": "program_counter",
        "moves": "train_tokens_per_s", "drivers": ["train_fixed_shape"]}


def read(run):
    c = run["counters"]
    n = c.get("train.step_n")
    return c["train.step_ns"] / n / 1e6 if n else None
