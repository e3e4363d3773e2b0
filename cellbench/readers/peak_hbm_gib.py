"""Peak bytes in use on the fullest chip (``memory_stats``), in GiB."""


def read(ctx):
    if ctx.peak_bytes is None:
        return None
    return ctx.peak_bytes / 2.0 ** 30
