"""Shared knobs for multi-process tests (reference role:
tools/gen_ut_cmakelists.py timeout tiers — SURVEY §4).

Fresh interpreters importing jax are CPU-bound; on an oversubscribed
box (the whole suite shares ONE core in CI) N children contend with
each other and with the parent's accumulated state, so wall-clock
budgets that pass standalone can blow up 10-30x under a full-suite
run. Every subprocess wait in the suite goes through proc_timeout()
so one env var can re-tier all of them at once.
"""
import gc
import os


def load_factor():
    """Multiplier for subprocess timeouts. PADDLE_TPU_TEST_LOAD_FACTOR
    overrides; default 3x on boxes with <=2 usable cores, 1x otherwise."""
    env = os.environ.get("PADDLE_TPU_TEST_LOAD_FACTOR")
    if env:
        return float(env)
    try:
        cores = len(os.sched_getaffinity(0))
    except (AttributeError, OSError):
        cores = os.cpu_count() or 1
    return 3.0 if cores <= 2 else 1.0


def proc_timeout(base):
    return base * load_factor()


def shed_parent_memory():
    """Drop the parent pytest process's compiled executables before
    forking heavy children: a full-suite parent holds every jitted step
    compiled so far, and that residency is what pushes a 19s standalone
    test past a 600s budget once children start competing for RAM."""
    import jax

    jax.clear_caches()
    gc.collect()
