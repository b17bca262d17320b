"""Peak device memory, GB, after the window, the highest over the cell's
chips: ``memory_stats()`` ``peak_bytes_in_use`` (buffers) plus
``peak_bytes_reserved`` (executables' temporaries)."""


def read(ctx):
    return ctx.memory_peak_bytes / 1e9
