"""Driver `serve_token_altered`, for benchmark/tests/test_families.py alone:
the closed-loop driver with the timed path broken underneath — every second
token of every request is altered where the scheduler hands it out — so that
the test sees `correct` come out false."""
import wrap_driver

SAMPLES_AS = "serve_closed_loop"

_inner = wrap_driver.load(SAMPLES_AS)
window, check = _inner.window, _inner.check


def setup(run):
    state = _inner.setup(run)
    sched, vocab = state["server"].scheduler, state["vocab"]
    real = sched._append_token

    def altered(req, token, now, slot_len=None):
        if len(req.tokens) % 2:
            token = (token + 1) % vocab
        return real(req, token, now, slot_len)

    sched._append_token = altered
    return state
