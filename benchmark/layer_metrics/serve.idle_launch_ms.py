"""Idle ms of chip 0 a decode step that lie between the host's CALL of an
executable (`serving.decode_step`'s / `serving.prefill`'s begin) and that
executable's first device op: class `launch` of step_timeline.py over the
traced stretch / the `serving.decode_step` spans begun in it. What calling
step N+1 before reading step N's tokens would hide; faster scheduler code
cannot touch it. The table by class is on an earlier line."""
import step_timeline

META = {"name": "serve.idle_launch_ms", "layer": "serve entry", "unit": "ms",
        "better": "lower", "source": "device_trace",
        "moves": "tpot_p95_ms", "drivers": ["serve_closed_loop"]}


def read(run):
    return step_timeline.per_step_ms(run, "launch")
