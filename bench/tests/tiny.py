"""A cell of BENCHMARK.json cut to a size a CPU test can hold: 4,096 data
points, short requests, small batches.  Everything else (paths, loops,
comparison, limits) is the cell's own."""

from __future__ import annotations

from bench import generate
from bench import run as R


def cell(name: str):
    """``(spec, cell, config, mix)`` of the named cell at the tiny size."""
    spec = R.load_spec()
    entry = R.find(spec["workloads"], name)
    config = dict(R.load_config(entry["config"]), points=4096)
    mix = dict(generate.load_mix(R.BENCH, entry["traffic"]))
    if mix["loop"] == "open":
        mix.update(rate_per_s=20.0, check_queries=512,
                   read_queries={"lo": 64, "hi": 256, "quantum": 64},
                   server={"max_batch": 256, "min_bucket": 64})
        if "update" in mix:
            mix.update(update={"inserts": 16, "deletes": 16},
                       update_share=0.2)
    else:
        mix.update(call_queries=1024, check_queries=512)
    return spec, entry, config, mix
