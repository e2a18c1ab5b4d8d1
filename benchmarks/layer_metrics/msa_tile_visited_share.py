"""Share of the blocks a tile of queries could see that the chunk kernel
visited: ``msa_tile_blocks_visited`` over ``msa_tile_blocks_visible`` of the
engine (window delta; both summed over tiles, KV heads, layers and chunk
calls). A tile visits the union of what its queries chose: 100% says the skip
never engaged (the tile's queries chose, between them, every block they see);
``msa_selected_share`` is what one query alone reads. None where the program
has no such counter (a parent of the kernel)."""

from benchmarks.harness import program_trace as P


def read(ctx, result):
    return P.counter_ratio(result, "msa_tile_blocks_visited",
                           "msa_tile_blocks_visible", 100.0)
