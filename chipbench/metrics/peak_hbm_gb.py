"""The device allocator's ``peak_bytes_in_use`` on the fullest chip after
the window, in GB (1e9 bytes)."""


def read(ctx: dict):
    return ctx["memory_peak_bytes"] / 1e9
