"""A program family's share of ONE chip's roofline in a site-sharded
cell, from the device trace.

`family_roofline` over the configuration's `site_shards`: its floor is
the algorithm's bytes for the whole alignment (benchmarks/bytemodel.py),
every site pattern lives in one shard, and a chip runs the program on
its own shard, so a chip moves that share of the bytes.  `tracereduce`
already gives a family's seconds and executions as the mean over the
device planes, so seconds a call are a chip's.  Nothing to read (no
trace, no execution of the family in it, or a configuration that states
no `site_shards`) returns nothing.
"""

from benchmarks.readers import family_roofline


def read(run, spec):
    shards = run["config"].get("site_shards")
    whole = family_roofline.read(run, spec)
    if whole is None or not shards:
        return None
    return whole / shards
