"""Readers shared by the per-layer metrics under ``metrics/``.

Each takes the harness's context (``harness.metric_context``): ``trace``
(the reduced trace of the window, or None), ``rounds`` and ``window_s`` of
the window, the configuration's ``flops_per_round``, the ``peak`` row of
``peaks.json`` for the device, and ``chips``. Each returns None where it
has nothing to read, never 0.
"""
from __future__ import annotations


def idle_pct(ctx: dict):
    tr = ctx["trace"]
    if not tr or tr["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])


def collective_pct(ctx: dict):
    """Share of the chips' busy time in which a collective ran: a union
    within a union, so at most 100; None where none ran."""
    tr = ctx["trace"]
    if not tr or not tr["collective_s"] or tr["busy_s"] <= 0:
        return None
    return 100.0 * tr["collective_s"] / tr["busy_s"]


def mfu(ctx: dict):
    tr = ctx["trace"]
    if not tr or not ctx["flops_per_round"] or tr["window_s"] <= 0:
        return None
    rate = ctx["rounds"] / tr["window_s"]
    return 100.0 * rate * ctx["flops_per_round"] / (
        ctx["chips"] * ctx["peak"]["bf16_flops"])


def launches_per_round(ctx: dict):
    tr = ctx["trace"]
    if not tr or not tr["launches"] or not ctx["rounds"]:
        return None
    return tr["launches"] / ctx["rounds"]
