"""Plain reference of the sync policy over a synthetic fleet.

The policy waits for every contacted client: a round aggregates the
candidates that are online, lasts as long as the slowest of them, and
costs each contacted client one broadcast and each received client one
upload of the dense float32 parameters. A round in which every contacted
client is offline is abandoned: it bills its broadcasts, takes no time,
and leaves the state (and its key) untouched.
"""
from __future__ import annotations

import numpy as np

import reference


def round_host(cand: np.ndarray, arr: np.ndarray):
    """-> (mask, duration, uploads received, abandoned) of a sync round."""
    mask = cand & np.isfinite(arr)
    dur = float(arr[mask].max()) if mask.any() else 0.0
    abandoned = bool(cand.any() and not mask.any())
    rec_up = cand & np.isfinite(arr) & (arr <= dur + 1e-12)
    if abandoned:
        rec_up = np.zeros_like(cand)
    return mask, dur, rec_up, abandoned


def run_reference(task, cfg: dict, spec: dict, seed: int, rounds: int, *,
                  lower: bool = False, fault: str | None = None,
                  chips: int = 1) -> dict:
    """Follow ``rounds`` sync rounds from the seed on ``chips`` chips;
    -> the readings."""
    st = reference.Start(task, cfg, spec, seed, lower=lower, fault=fault,
                         chips=chips)
    cst = st.cst
    down_b = up_b = 4.0 * st.n_params
    work = reference.client_work_flops(cst.k0, st.n_params, st.d_local)
    fleet = st.fleet(spec["fleet"], seed)
    out = {"f": [], "rounds": [], "t": 0.0, "bytes_up": 0.0,
           "bytes_down": 0.0}
    for r in range(rounds):
        nxt, k_sel, k_noise = st.split3(st.key)
        cand = np.asarray(st.select(k_sel))
        arr = fleet.arrivals(work, down_b, up_b)
        mask, dur, rec_up, abandoned = round_host(cand, arr)
        if not abandoned:
            st.step(mask, k_noise)
            st.key = nxt
        bd, bu = float(cand.sum()) * down_b, float(rec_up.sum()) * up_b
        out["t"] += dur
        out["bytes_down"] += bd
        out["bytes_up"] += bu
        out["rounds"].append({"n_contacted": int(cand.sum()),
                              "n_aggregated": int(mask.sum()),
                              "t_round": dur, "bytes_down": bd,
                              "bytes_up": bu, "abandoned": abandoned})
        if task.OBJECTIVE_EVERY_ROUND or r == rounds - 1:
            out["f"].append(st.f())
    return st.readings(out)
