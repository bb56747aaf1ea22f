"""95th percentile, over the requests given at least `tpot_min_gaps` tokens
inside the window, of the mean gap between the tokens each was given there:
(time of its last token in the window - time of its last token before it,
or of its first token) / tokens between them."""
import stats

META = {"name": "tpot_p95_ms", "unit": "ms", "better": "lower",
        "source": "host_clock", "drivers": ["serve_closed_loop"]}


def read(run):
    p = stats.percentile(run["samples"]["tpot_s"], 95)
    return None if p is None else 1e3 * p
