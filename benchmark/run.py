#!/usr/bin/env python3
"""benchmark/run.py — one run of one cell, as the driver calls it:

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process: load, warm up (set-up), measure for --seconds, check the
outputs, print facts on earlier lines and the contract's JSON as the LAST
line of stdout. Any failure is a non-zero exit and no result line.

This file names no cell, configuration or metric. Everything specific is a
file found by name:

  workloads/<cell>.json      config, traffic, chips, the cell's own settings
  configs/<config>.json      the model's sizes as run, its source
  traffic/<traffic>.json     driver + the parameters its generator reads
  drivers/<driver>.py        setup(run) -> state; window(run, state, seconds)
                             -> samples; check(run, state, samples) -> bool,
                             leaving in run["compared"] each number it
                             compared: {name: [number, limit]}
  e2e_metrics/<name>.py      META + read(run): reported with --trace 0
  layer_metrics/<name>.py    META + read(run): reported with --trace 1
  rehearse/                  tiny cells marked "rehearsal": true — the only
                             ones allowed on a platform other than a TPU

A metric file applies to a cell when META["drivers"] holds the cell's
driver, or the driver that the cell's driver declares it samples as
(SAMPLES_AS = "<driver>": the promise that its setup/window/check fill state,
samples and the counters with the keys that driver's do); a reader that
finds nothing to read returns None and the metric is left out of the line.
What is specific to a model family, drivers and readers ask of
families/<family>.py (families/__init__.py).
"""
from __future__ import annotations

import time

T_START = time.perf_counter()  # set-up is timed from here

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def say(msg):
    """A fact about the run, on a line before the last."""
    print(f"[bench] {msg}", flush=True)


def load_json(*parts):
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def load_module(*parts):
    path = os.path.join(HERE, *parts)
    name = "bench_" + "_".join(parts).replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def find_workload(name):
    for d in ("workloads", "rehearse"):
        if os.path.exists(os.path.join(HERE, d, name + ".json")):
            return d, load_json(d, name + ".json")
    raise SystemExit(f"run.py: no workloads/{name}.json or "
                     f"rehearse/{name}.json")


def metric_files(kind, driver_name, driver):
    """The readers in <kind>/ that apply to this driver, by file name: those
    that name it, and those that name the driver it samples as."""
    names = {driver_name}
    as_ = getattr(driver, "SAMPLES_AS", None)
    if as_ is not None:
        if not os.path.exists(os.path.join(HERE, "drivers", f"{as_}.py")):
            raise SystemExit(f"run.py: drivers/{driver_name}.py: SAMPLES_AS "
                             f"= {as_!r}, and there is no drivers/{as_}.py")
        names.add(as_)
    out = []
    for fn in sorted(os.listdir(os.path.join(HERE, kind))):
        if fn.endswith(".py"):
            mod = load_module(kind, fn)
            if names & set(mod.META["drivers"]):
                out.append(mod)
    return out


class Tracer:
    """The device trace of a short stretch at the END of the window (so
    the rest of the window is undisturbed and stopping the trace falls
    outside it). Drivers call due()/start() inside the window and run.py
    stops and reduces after it. With --trace 0 every call is a no-op."""

    def __init__(self, on, seconds, stretch_s, out_dir):
        self.on, self.dir = bool(on), out_dir
        self.start_after = max(0.0, seconds - stretch_s)
        self.t_start = None

    def due(self, elapsed):
        return self.on and self.t_start is None \
            and elapsed >= self.start_after

    def start(self):
        import jax

        shutil.rmtree(self.dir, ignore_errors=True)
        # no Python tracer: it records every call of every thread, which
        # slows the host work that the idle share is there to measure
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self.t_start = time.perf_counter()

    def stop(self):
        if self.t_start is not None:
            import jax

            jax.profiler.stop_trace()

    def annotate(self, name):
        """A host span on the profiler's own clock (cheap when no trace
        is being taken)."""
        import jax

        return jax.profiler.TraceAnnotation(name)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default=None,
                    help="copy the .xplane.pb here (to look at one by hand)")
    args = ap.parse_args(argv)

    where, wl = find_workload(args.workload)
    rehearsal = bool(wl.get("rehearsal"))
    if rehearsal != (where == "rehearse"):
        raise SystemExit(f"run.py: {where}/{args.workload}.json: only files "
                         "under rehearse/ are rehearsals, and all of them")
    cfg_dir = "rehearse" if rehearsal else "configs"
    cfg = load_json(cfg_dir, wl["config"] + ".json")
    traffic = load_json("rehearse" if rehearsal else "traffic",
                        wl["traffic"] + ".json")
    driver_name = traffic["driver"]

    sys.path[:0] = [ROOT, HERE]  # the program; the benchmark's own modules
    import jax

    import paddle_tpu as paddle
    from paddle_tpu.profiler import CompileWatch, registry

    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu" and not rehearsal:
        raise SystemExit(f"run.py: cell {args.workload} needs a TPU; JAX "
                         f"found platform {dev.platform!r} "
                         f"({dev.device_kind})")
    chips = int(wl["chips"])
    if len(devs) < chips and not rehearsal:
        raise SystemExit(f"run.py: cell {args.workload} needs {chips} "
                         f"chips; JAX found {len(devs)}")
    cache_dir = None
    if dev.platform == "tpu":
        # honours JAX_COMPILATION_CACHE_DIR, else <checkout>/.jax_cache;
        # every program goes in, however quickly it compiled
        cache_dir = paddle.sysconfig.enable_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    paddle.set_device("tpu" if dev.platform == "tpu" else "cpu")

    import jaxlib
    from importlib import metadata

    try:
        libtpu = metadata.version("libtpu")
    except metadata.PackageNotFoundError:
        libtpu = None
    say(f"cell {args.workload} ({where}) config {wl['config']} traffic "
        f"{wl['traffic']} driver {driver_name} seed {args.seed} seconds "
        f"{args.seconds} trace {args.trace}")
    say(f"jax {jax.__version__} jaxlib {jaxlib.__version__} libtpu {libtpu} "
        f"python {sys.version.split()[0]}; device {dev.platform} "
        f"{dev.device_kind!r} x{len(devs)} (cell uses {chips}); compile "
        f"cache {cache_dir}")

    driver = load_module("drivers", driver_name + ".py")
    # found now, so that a reader or a SAMPLES_AS that is not there stops
    # the run before its set-up and not after its window
    readers = metric_files("layer_metrics" if args.trace else "e2e_metrics",
                           driver_name, driver)
    tracer = Tracer(args.trace, args.seconds,
                    float(traffic.get("trace_seconds", 3.0)),
                    os.path.join(ROOT, ".bench_trace"))
    run = {"cell": args.workload, "wl": wl, "cfg": cfg, "traffic": traffic,
           "driver": driver_name, "seed": args.seed, "rehearsal": rehearsal,
           "on_tpu": dev.platform == "tpu", "tracer": tracer, "say": say,
           "peaks": load_json("peaks.json"), "device_kind": dev.device_kind,
           "t_start": T_START}

    with CompileWatch() as warm:
        state = driver.setup(run)
    run["setup_s"] = time.perf_counter() - T_START
    say(f"set-up {run['setup_s']:.2f} s: {warm.compiles} executables built "
        f"or loaded, {warm.cache_hits} from the compile cache, "
        f"{warm.compiles - warm.cache_hits} compiled, "
        f"{warm.seconds:.1f} s in trace+lower+compile")

    c0 = registry.counters()
    with CompileWatch() as steady:
        samples = driver.window(run, state, args.seconds)
    c1 = registry.counters()
    tracer.stop()
    run["samples"] = samples
    run["window_s"] = samples["window_s"]
    run["counters"] = {k: c1[k] - c0.get(k, 0) for k in c1
                       if isinstance(c1[k], (int, float))}
    run["counters_abs"] = c1
    run["compiles_in_window"] = steady.compiles
    say(f"window {run['window_s']:.3f} s; compiles inside it "
        f"{steady.compiles}")

    correct = bool(driver.check(run, state, samples))

    used = devs[:chips]
    stats = [d.memory_stats() or {} for d in used]
    peak = max((int(s.get("peak_bytes_in_use", 0)) for s in stats),
               default=0)
    run["memory_peak_bytes"] = peak
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs), "memory_peak_bytes": peak}

    out = {"correct": correct, "attempted": int(samples["attempted"]),
           "failed": int(samples["failed"])}
    run["trace"] = None
    if args.trace:
        trace_reduce = load_module("trace_reduce.py")
        pb = trace_reduce.find_xplane(tracer.dir)
        say(f"trace file {os.path.getsize(pb)} bytes")
        if args.keep_trace:
            os.makedirs(os.path.dirname(args.keep_trace) or ".",
                        exist_ok=True)
            shutil.copy(pb, args.keep_trace)
        if dev.platform == "tpu":  # a CPU rehearsal's trace has no device
            red = run["trace"] = trace_reduce.reduce(pb, n_devices=chips)
            device["busy_s"] = red["busy_s"]
            device["window_s"] = red["window_s"]
            out["breakdown"] = {"device_ops": red["device_ops"][:10],
                                "idle_gaps": red["idle_gaps"][:10]}
            say(f"trace: {red['window_s']:.3f} s traced, device busy "
                f"{red['busy_s']:.3f} s, idle share "
                f"{100 * (1 - red['busy_s'] / red['window_s']):.2f} %")

    metrics = {}
    for mod in readers:
        v = mod.read(run)
        if v is not None:
            metrics[mod.META["name"]] = {"value": float(v),
                                         "unit": mod.META["unit"]}
    out["metrics"] = metrics
    out["device"] = device
    # each number compared beside its limit: the last key of the line and
    # the last lines of standard error, which is what is kept of a run
    # that is not correct
    out["compared"] = {
        k: [x if math.isfinite(x) else str(x) for x in pair]  # JSON has no NaN
        for k, pair in run.get("compared", {}).items()}
    if not correct:
        say("NOT CORRECT — see the lines above")
    for name, (value, limit) in out["compared"].items():
        print(f"[bench] compared {name}: {value} (limit {limit})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
