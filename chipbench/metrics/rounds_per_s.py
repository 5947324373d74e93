"""Aggregation rounds the window's one ``run()`` completed over its whole
wall time."""


def read(ctx: dict):
    return ctx["rounds"] / ctx["window_s"]
