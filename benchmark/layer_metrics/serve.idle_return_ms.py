"""Idle ms of chip 0 a decode step that lie between the end of an
executable's last device op and the end of the host's wait on it
(`serving.decode_sync`'s end; `serving.prefill`'s): class `return` of
step_timeline.py over the traced stretch / the `serving.decode_step` spans
begun in it. The device is done and the host does not hold the tokens yet."""
import step_timeline

META = {"name": "serve.idle_return_ms", "layer": "serve entry", "unit": "ms",
        "better": "lower", "source": "device_trace",
        "moves": "tpot_p95_ms", "drivers": ["serve_closed_loop"]}


def read(run):
    return step_timeline.per_step_ms(run, "return")
