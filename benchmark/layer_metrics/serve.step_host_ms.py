"""The host's time an iteration outside its decode call and outside
admissions, over the WHOLE window: (delta of serving.sched_step_ns -
decode_step_ns - admit_ns) / delta of serving.sched_steps. The engine's
prepare and finish, the emit loop and the scheduler's own checks; what
`serve.sched_self_ms` mixes with the admissions' bookkeeping. A run that is
slow all window long for the host's sake shows here, where the traced
stretch (the last seconds) may not."""
META = {"name": "serve.step_host_ms", "layer": "serve entry", "unit": "ms",
        "better": "lower", "source": "program_counter",
        "moves": "tpot_p95_ms", "drivers": ["serve_closed_loop"]}


def read(run):
    c = run["counters"]
    n = c.get("serving.sched_steps")
    if not n or "serving.admit_ns" not in c:
        return None
    own = c["serving.sched_step_ns"] - c.get("serving.decode_step_ns", 0) \
        - c["serving.admit_ns"]
    return own / n / 1e6
