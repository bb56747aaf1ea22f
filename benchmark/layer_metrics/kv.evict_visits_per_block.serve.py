"""The radix tree nodes the window's evictions examined a block they freed:
delta of serving.prefix_evict_visits / delta of
serving.prefix_evicted_blocks. About 1 where the tree keeps its evictable
leaves in LRU order; a walk of the whole tree a round reads the tree's size
over the blocks a round frees. None where no block was evicted in the
window (a cell without prefix sharing, a pool never full) or where the
program has no such counter."""
META = {"name": "kv.evict_visits_per_block.serve", "layer": "block pool",
        "unit": "visits/block", "better": "lower",
        "source": "program_counter", "moves": "serve_tokens_per_s",
        "drivers": ["serve_closed_loop"]}


def read(run):
    c = run["counters"]
    visits = c.get("serving.prefix_evict_visits")
    freed = c.get("serving.prefix_evicted_blocks")
    if visits is None or not freed:
        return None
    return visits / freed
