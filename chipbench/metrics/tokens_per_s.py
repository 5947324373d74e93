"""Tokens the window's client gradients were taken over (rounds x clients
x sequences x sequence length) over the window's whole wall time."""


def read(ctx: dict):
    if not ctx["tokens_per_round"]:
        return None
    return ctx["rounds"] * ctx["tokens_per_round"] / ctx["window_s"]
