"""Driver `train_state_unchanged`, for benchmark/tests/test_families.py
alone: the fixed-shape driver with the timed path broken underneath — the
step the window calls updates nothing and returns the first loss again — so
that the test sees `correct` come out false."""
import wrap_driver

SAMPLES_AS = "train_fixed_shape"

_inner = wrap_driver.load(SAMPLES_AS)
window, check = _inner.window, _inner.check


def setup(run):
    import paddle_tpu as paddle

    state = _inner.setup(run)
    same = paddle.to_tensor(state["first_loss"])
    state["train"] = lambda tokens, labels: same
    return state
