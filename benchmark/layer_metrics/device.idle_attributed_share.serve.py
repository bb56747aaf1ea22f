"""Share of chip 0's idle time in the traced stretch that lies under a
program span (`serving.*` of paddle_tpu/profiler/spans.py) of any host
thread: scope_reduce.py puts each gap of 2 us or more down to the innermost
span over its middle; the table by span is on an earlier line."""
import scope_reduce

META = {"name": "device.idle_attributed_share.serve", "layer": "device",
        "unit": "%", "better": "higher", "source": "device_trace",
        "moves": "serve_tokens_per_s", "drivers": ["serve_closed_loop"]}


def read(run):
    red = scope_reduce.of_run(run)
    if not red or not red["idle"]["n_program_spans"] \
            or not red["idle"]["seconds"]:
        return None
    return 100.0 * red["idle"]["attributed_s"] / red["idle"]["seconds"]
