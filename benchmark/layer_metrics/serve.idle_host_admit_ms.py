"""Idle ms of chip 0 a decode STEP that admissions cost through host code:
step_timeline.py's classes `admit_check`, `admit_blocks`, `admit_stage`,
`admit_install` and `admit` (its self time) over the traced stretch / the
`serving.decode_step` spans begun in it, so that this, `serve.idle_launch_ms`,
`serve.idle_return_ms` and `serve.idle_host_step_ms` add up to the idle of a
step (less `unattributed` and the device's own waits, on the table's lines)."""
import step_timeline

META = {"name": "serve.idle_host_admit_ms", "layer": "serve entry",
        "unit": "ms", "better": "lower", "source": "device_trace",
        "moves": "serve_tokens_per_s", "drivers": ["serve_closed_loop"]}


def read(run):
    return step_timeline.per_step_ms(run, *step_timeline.HOST_ADMIT)
