"""paddle.sysconfig (reference `python/paddle/sysconfig.py`): paths for
compiling native extensions against this framework, and the one rule that
places JAX's persistent compilation cache."""
from __future__ import annotations

import os

__all__ = ["get_include", "get_lib", "ensure_native_built",
           "enable_compile_cache"]

_ROOT = os.path.dirname(os.path.abspath(__file__))


def ensure_native_built(lib_name=None):
    """Bring the native runtime libraries up to date with `csrc/`.

    The shared objects are NOT committed to the repository (they embed the
    local Python ABI — libptinfer_capi links via `python3-config --embed` —
    so a prebuilt binary silently fails to load on any other interpreter).
    Every ctypes loader calls this once before dlopen, and `make` decides:
    it builds what is missing, rebuilds what is older than its source (a
    stale .so copied along with a tree must not be loaded silently) and is
    a no-op otherwise. An installed package without `csrc/` loads what it
    ships.

    Returns the path of `lib_name` (or the lib dir when None)."""
    lib_dir = os.path.join(_ROOT, "lib")
    src = os.path.abspath(os.path.join(_ROOT, "..", "csrc"))
    if os.path.exists(os.path.join(src, "Makefile")):
        import subprocess

        # serialize concurrent builds (8 ranks cold-starting would
        # otherwise race `make` into the same output dir and dlopen
        # half-written .so files)
        os.makedirs(lib_dir, exist_ok=True)
        with open(os.path.join(lib_dir, ".build.lock"), "w") as lock:
            try:
                import fcntl

                fcntl.flock(lock, fcntl.LOCK_EX)
            except ImportError:
                pass
            target = [f"../paddle_tpu/lib/{lib_name}"] if lib_name else []
            subprocess.run(["make", "-C", src, *target], check=True,
                           capture_output=True)
    return os.path.join(lib_dir, lib_name) if lib_name else lib_dir


def enable_compile_cache():
    """Turn on JAX's persistent compilation cache for an entry-point script
    (chip_smoke.py, benchmark/run.py) and return its directory. One rule:
    where ``JAX_COMPILATION_CACHE_DIR`` is set the cache is placed from outside —
    jax has already read it, and nothing is set in code; otherwise it goes
    to ``<checkout>/.jax_cache``, a fixed path (never derived from tempfile,
    a pid or the clock — a cache that moves never hits). Library entry
    points (TrainStep, GenerationServer) configure no cache; only scripts
    call this."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if path:
        return path
    import jax

    path = os.path.join(os.path.dirname(_ROOT), ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def get_include():
    """Directory of C headers (custom-op ABI `pt_custom_op.h`, inference C
    API `pt_inference_c.h`). Prefers an in-package `include/` (installed
    wheels ship headers there); falls back to the source checkout's
    `csrc/include`."""
    packaged = os.path.join(_ROOT, "include")
    if os.path.isdir(packaged):
        return packaged
    return os.path.abspath(os.path.join(_ROOT, "..", "csrc", "include"))


def get_lib():
    """Directory of native shared libraries (libtcpstore, libshmring,
    libptdatafeed, libptinfer_capi)."""
    return os.path.join(_ROOT, "lib")
