"""Idle ms of chip 0 a decode step under the host's own work of an
iteration: step_timeline.py's classes `decode_prepare`, `decode_finish`,
`emit`, `sched_step` (its self time), `host.gc` and `loop_idle` over the
traced stretch / the `serving.decode_step` spans begun in it. What faster
engine and scheduler code between two calls can take away."""
import step_timeline

META = {"name": "serve.idle_host_step_ms", "layer": "serve entry",
        "unit": "ms", "better": "lower", "source": "device_trace",
        "moves": "tpot_p95_ms", "drivers": ["serve_closed_loop"]}


def read(run):
    return step_timeline.per_step_ms(run, *step_timeline.HOST_STEP)
