"""Tokens a request is given a forward of its slot, for a decoder that
generates by diffusion over blocks: delta of
serving.diffusion.tokens_committed / delta of serving.diffusion.slot_forwards
(active slots x forwards). A block of B tokens costs its denoise forwards and
one commit: 4 / 3 at block length 4 with 2 denoise forwards, less the
prompt's tail that opens a first block and the cut of a last one. It is what
any change to the schedule (fewer denoise forwards, a commit fused with the
next block's first forward, larger blocks) must move: the same forwards give
more tokens, so it moves `serve_tokens_per_s`. None where the program keeps
no such counters (a decoder that generates left to right, or a program
before them)."""
META = {"name": "diffusion.tokens_per_forward.serve", "layer": "serve entry",
        "unit": "tokens", "better": "higher", "source": "program_counter",
        "moves": "serve_tokens_per_s", "drivers": ["serve_closed_loop"]}


def read(run):
    c = run["counters"]
    forwards = c.get("serving.diffusion.slot_forwards")
    if not forwards:
        return None
    return c.get("serving.diffusion.tokens_committed", 0) / forwards
