"""Peak device memory in use over the run, in GiB, as the device's
allocator reports it (``memory_stats()["peak_bytes_in_use"]``) after the
window and before the output check."""


def read(ctx):
    if ctx.memory_peak_bytes is None:
        return None
    return ctx.memory_peak_bytes / 2**30
