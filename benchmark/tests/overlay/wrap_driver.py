"""For the overlay's drivers alone: load a driver that is there, by name,
the way run.py loads the cell's own (drivers/ is no package)."""
import importlib.util
import os


def load(name):
    spec = importlib.util.spec_from_file_location(
        "bench_drivers_" + name + "_wrapped", os.path.join(
            os.path.dirname(os.path.abspath(__file__)), "drivers",
            name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
