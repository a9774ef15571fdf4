"""Smallest largest-free-block of device memory at a block end in the
window, in GiB: ``memory_stats()["largest_free_block_bytes"]`` as the
executor samples it after each block (``hbm.sample``), while it holds the
state of the jobs it ran."""

from chipbench import program


def read(ctx):
    free = [r.args["largest_free_block_bytes"]
            for r in program.records("hbm.sample")
            if ctx.in_window(r.start)
            and r.args["largest_free_block_bytes"] is not None]
    return min(free) / 2**30 if free else None
