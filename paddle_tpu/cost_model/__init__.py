"""paddle.cost_model (reference `python/paddle/cost_model/cost_model.py` +
`static_op_benchmark.json`): per-op timing data for planners/tuners.

Static cost data here is a snapshot the caller passes (`static_cost_file=`:
`{op: {"fwd_ms", "fwd_bwd_ms"}}`) instead of the reference's frozen 2021 CI
JSON; `profile_measure` measures a real program through the Executor."""
from __future__ import annotations

import json
import os
import time

__all__ = ["CostModel", "device_peak_flops", "PEAK_BF16_FLOPS"]


# bf16 peak FLOP/s per chip, keyed by the `device_kind` string JAX reports.
# Peaks: Google Cloud TPU documentation, system-architecture pages (v4: 275
# TFLOP/s, v5e: 197, v5p: 459, v6e: 918, each per chip = per JAX device).
# Kind spellings: the installed jax's own table
# (jax/_src/pallas/mosaic/tpu_info.py); "TPU v5 lite" is what the v5e reports.
# A kind that is not listed is an error, never a default: a utilization
# against a guessed peak is not a measurement.
PEAK_BF16_FLOPS = {
    "TPU v4": 275e12,
    "TPU v5 lite": 197e12,
    "TPU v5e": 197e12,
    "TPU v5": 459e12,
    "TPU v5p": 459e12,
    "TPU v6 lite": 918e12,
    "TPU v6e": 918e12,
}


def device_peak_flops(device=None):
    """bf16 peak FLOP/s of `device` (default: the first JAX device) — the
    MFU denominator. Raises ``LookupError`` for any device kind missing
    from ``PEAK_BF16_FLOPS``, the host CPU included."""
    if device is None:
        import jax

        device = jax.devices()[0]
    kind = getattr(device, "device_kind", "")
    try:
        return PEAK_BF16_FLOPS[kind]
    except KeyError:
        raise LookupError(
            f"no bf16 peak on record for device kind {kind!r} (platform "
            f"{device.platform!r}); known kinds: {sorted(PEAK_BF16_FLOPS)}"
        ) from None


class CostModel:
    def __init__(self, static_cost_file=None):
        self._static_file = static_cost_file
        self._static_data = None

    # ----------------------------------------------------------- static data
    def static_cost_data(self):
        """Load the op-timing snapshot passed as ``static_cost_file=``."""
        if self._static_data is None:
            path = self._static_file
            if path is None or not os.path.isfile(path):
                raise FileNotFoundError(
                    f"no op-timing snapshot at {path!r}: pass "
                    "CostModel(static_cost_file=<json>) a file of "
                    '{op: {"fwd_ms": ..., "fwd_bwd_ms": ...}}')
            with open(path) as f:
                self._static_data = json.load(f)
        return self._static_data

    def get_static_op_time(self, op_name, forward=True, dtype="float32"):
        """Op time in ms from the snapshot; KeyError when unmeasured."""
        data = self.static_cost_data()
        rec = data.get(op_name)
        if not isinstance(rec, dict) or "fwd_ms" not in rec:
            raise KeyError(
                f"op {op_name!r} not in snapshot; known: "
                f"{[k for k in data if not k.startswith('_')]}")
        return rec["fwd_ms"] if forward else rec["fwd_bwd_ms"]

    # ------------------------------------------------------------- measured
    def profile_measure(self, main_program, startup_program=None,
                        feed=None, fetch_list=None, device=None,
                        repeat=5):
        """Run a static Program and return measured wall time per run
        (reference profile_measure runs the program under the profiler).
        Measurement happens on the process's current JAX device; a
        `device` that differs from it is not honored (warned, not
        silently relabeled)."""
        import warnings

        import jax

        from ..static import Executor

        actual = jax.devices()[0].platform
        if device is not None and device != actual:
            warnings.warn(
                f"profile_measure(device={device!r}) measures on the "
                f"current backend {actual!r}; set JAX_PLATFORMS to choose "
                "the device before importing")
        exe = Executor()
        if startup_program is not None:
            exe.run(startup_program)
        exe.run(main_program, feed=feed, fetch_list=fetch_list)  # compile
        t0 = time.perf_counter()
        for _ in range(repeat):
            exe.run(main_program, feed=feed, fetch_list=fetch_list)
        return {"program_ms": (time.perf_counter() - t0) / repeat * 1e3}
