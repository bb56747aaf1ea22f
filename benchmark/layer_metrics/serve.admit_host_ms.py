"""An admission's host time outside its prefill executable, over the WHOLE
window: (delta of serving.admit_ns - prefill_ns) / delta of
serving.admitted. The pool's budget check, the prompt's check, the radix
match and the allocation, the request's key, the arguments' transfers, the
signature radar, the slot's installation and the radix insert; the spans
`serving.admit_check`, `admit_blocks`, `admit_stage` and `admit_install` say
which."""
META = {"name": "serve.admit_host_ms", "layer": "serve entry", "unit": "ms",
        "better": "lower", "source": "program_counter",
        "moves": "serve_tokens_per_s", "drivers": ["serve_closed_loop"]}


def read(run):
    c = run["counters"]
    n = c.get("serving.admitted")
    if not n or "serving.admit_ns" not in c:
        return None
    return (c["serving.admit_ns"] - c.get("serving.prefill_ns", 0)) / n / 1e6
