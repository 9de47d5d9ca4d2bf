"""``memory_stats()["peak_bytes_in_use"]`` of the fullest chip after
the window, in GB.  A backend that reports none gives None."""


def read(ctx: dict, args: dict):
    return ctx["peak_bytes"] / 1e9 if ctx["peak_bytes"] else None
