"""Peak device memory in use by the end of the window, in 1e9 bytes
(``memory_stats()["peak_bytes_in_use"]`` of the fullest chip)."""


def read(ctx):
    return ctx.peak_bytes / 1e9 if ctx.peak_bytes else None
