"""The share of the window's prefill calls whose full layers wrote the
prompt's K/V rows into the pools as whole blocks (one block scatter a pool,
its index count the span's blocks) and not row by row: 100 x delta of
serving.prefill_block_writes / delta of serving.prefill_n. None where no
prefill call of the window wrote by blocks: a program without the block
write (no such counter), a cache whose rows its decoder writes in its own
forward (a latent cache), or a window without a prefill call."""
META = {"name": "kv.prompt_block_write_share.serve", "layer": "block pool",
        "unit": "%", "better": "higher", "source": "program_counter",
        "moves": "serve_tokens_per_s", "drivers": ["serve_closed_loop"]}


def read(run):
    c = run["counters"]
    calls = c.get("serving.prefill_n")
    blocks = c.get("serving.prefill_block_writes")
    if not calls or not blocks:
        return None
    return 100.0 * blocks / calls
