"""The scheduler's own host time a `step()`: the iteration less the two
engine calls inside it, (delta of serving.sched_step_ns - decode_step_ns -
prefill_ns) / delta of serving.sched_steps. Admission bookkeeping, the emit
loop and the engine's host work around its executables are in it."""
META = {"name": "serve.sched_self_ms", "layer": "serve entry", "unit": "ms",
        "better": "lower", "source": "program_counter",
        "moves": "tpot_p95_ms", "drivers": ["serve_closed_loop"]}


def read(run):
    c = run["counters"]
    n = c.get("serving.sched_steps")
    if not n:
        return None
    own = c["serving.sched_step_ns"] - c.get("serving.decode_step_ns", 0) \
        - c.get("serving.prefill_ns", 0)
    return own / n / 1e6
