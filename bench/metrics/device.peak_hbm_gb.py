"""Peak device memory of the fullest chip after the window
(``memory_stats()["peak_bytes_in_use"]``), in GB."""


def read(ctx):
    return ctx.peak_bytes / 1e9 if ctx.peak_bytes else None
