"""Seconds from the process's start to the window's start: imports,
loading, building the data and weights, compiling, warming up."""


def read(ctx: dict):
    return ctx["setup_s"]
