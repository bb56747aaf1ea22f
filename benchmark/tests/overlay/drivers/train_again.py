"""Driver `train_again`, for benchmark/tests/test_families.py alone: a second
driver added as a file. It drives drivers/train_fixed_shape.py's own
functions, so it keeps that driver's promise and says so."""
import wrap_driver

SAMPLES_AS = "train_fixed_shape"

_inner = wrap_driver.load(SAMPLES_AS)
setup, window, check = _inner.setup, _inner.window, _inner.check
