"""``msa_tile_visited_share`` (PR 50): the share of the blocks a tile of
queries could see that the block-masked chunk kernel visited, read from the
two counters the prefill program of a model with the learned block selector
adds up; where it stands in the manifest; what it reads from a window's
counters, and that a program without them (the kernel's parent) reads
nothing."""

import pytest

from benchmarks.harness import manifest as mf

CELL = "serve-m3-longdoc-ttft"
NAME = "msa_tile_visited_share"


def test_the_metric_stands_behind_the_chunks_roofline():
    """Pinned to its neighbour, not to the end of the list: a later PR
    appends behind it."""
    man = mf.load_manifest()
    names = [m["name"] for m in man["per_layer"]]
    assert names.index(NAME) == names.index("msa_chunk_roofline") + 1
    assert man["per_layer"][names.index(NAME)] == {
        "name": NAME, "unit": "%", "better": "lower",
        "source": "program_counter", "layer": "kernels",
        "moves": "ttft_p50_ms", "workloads": [CELL]}
    assert NAME in {m["name"] for m in mf.metrics_of(man, "per_layer", CELL)}
    other = next(w["name"] for w in man["workloads"] if w["name"] != CELL)
    assert NAME not in {m["name"]
                        for m in mf.metrics_of(man, "per_layer", other)}


@pytest.mark.parametrize("engine,want", [
    ({"msa_tile_blocks_visited": 930, "msa_tile_blocks_visible": 1000}, 93.0),
    ({"msa_tile_blocks_visited": 19, "msa_tile_blocks_visible": 98},
     100.0 * 19 / 98),
    # a window without a chunk call, and the kernel's parent: no counter
    ({"msa_tile_blocks_visited": 0, "msa_tile_blocks_visible": 0}, None),
    ({"msa_blocks_chosen": 19, "msa_blocks_visible": 98}, None),
    ({}, None)], ids=["union", "rule", "no-chunk", "parent", "empty"])
def test_the_reader_divides_the_windows_counters(engine, want):
    reader = mf.load_module("layer_metrics", NAME)
    got = reader.read(None, {"counters": {"engine": engine}})
    assert got == (want if want is None else pytest.approx(want))
    assert reader.read(None, {}) is None
