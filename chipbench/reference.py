"""Plain reference of FedEPM rounds: the round math, the simulated fleet's
client profiles, and the readings the check compares.

It imports nothing of the program under test. From the same seed it makes
the same participation draws, the same upload noise and the same simulated
client profiles as the published semantics say, and runs the rounds in
straightforward ``jax.numpy``:

* the server aggregate is the elastic-net solution (ENS) of the client
  uploads, coordinate-wise the middle of the 2m+1 values made of the m
  uploads and the m+1 interior candidates (paper Lemma III.1/III.2);
* every client takes one gradient at the broadcast point and k0
  closed-form prox steps (paper eq. (20)) with the growing weight
  mu = mu0 (1 + c ||w - w_tau||^2) alpha^(k+1);
* with ``eps_dp`` > 0 a client uploads its iterate plus Laplace noise of
  scale 2 ||g||_1 / (eps_dp mu) (Setup V.1 with the surrogate of eq. (39));
* clients not in the round's mask keep their iterate and upload (eq. (22)).

How a traffic mix's policy turns arrivals into rounds (which clients
aggregate, how long a round lasts, what it bills) is the mix's own plain
reference, ``mixes/<name>.py``, built on the pieces here. The task (data,
initial parameters, per-client loss) comes from the configuration's own
reference module beside its JSON file. ``lower=True`` runs the same rounds
in the task's next lower precision: that is the control that the
comparison must reject.

A configuration whose ``engine.mesh`` is n > 1 runs on n chips, and so
does its reference: a 1-D mesh of its own over the first n devices holds
every client's iterate and upload on the chip of its client, m/n clients a
chip. There a round takes its chip's clients one after another, and the
aggregate of each leaf is found on coordinate blocks that an all-to-all
brings together from every chip, then gathered whole on every chip.
"""
from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

tmap = jax.tree_util.tree_map

NOMINAL_FLOPS = 1e9          # simulated seconds per flop at speed 1
CLIENT_AXIS = "clients"
LATENCY_MODELS = ("deterministic", "lognormal", "pareto")


@dataclasses.dataclass(frozen=True)
class FedEPMConstants:
    m: int
    k0: int
    rho: float
    lam: float
    eta: float
    mu0: float
    c: float
    alpha: float
    eps_dp: float = 0.0
    sensitivity_clip: float = 0.0

    @staticmethod
    def paper(m: int, rho: float, k0: int, mu0: float = 0.05,
              c: float = 1e-8, alpha: float = 1.001,
              **kw) -> "FedEPMConstants":
        """Sec. VII.B: eta = (0.02 m + 1)(rho + 0.1) 1e-5, lam = eta / 2."""
        eta = (0.02 * m + 1.0) * (rho + 0.1) * 1e-5
        return FedEPMConstants(m=m, k0=k0, rho=rho, lam=eta / 2.0, eta=eta,
                               mu0=mu0, c=c, alpha=alpha, **kw)


def constants_from(spec: dict) -> FedEPMConstants:
    task, alg = spec["task"], spec["algorithm"]
    extra = {k: alg[k] for k in ("mu0", "c", "alpha", "eps_dp",
                                 "sensitivity_clip") if k in alg}
    return FedEPMConstants.paper(task["m"], alg["rho"], alg["k0"], **extra)


# ---------------------------------------------------------------------------
# the simulated fleet (host, float64)
# ---------------------------------------------------------------------------

class Fleet:
    """Client profiles and per-round arrival times of a synthetic fleet.

    Profiles: lognormal speed (sigma 0.4, mean 1), uplink (mean 1.25e6 B/s)
    and downlink (mean 1e7 B/s) bandwidths (sigma 0.6), all from one
    generator seeded with the fleet seed. Each round draws a compute
    jitter for every client, then one availability draw per client, from
    a second generator seeded with the experiment seed.
    """

    def __init__(self, m: int, *, fleet_seed: int, sim_seed: int,
                 latency: str, sigma: float, alpha: float,
                 availability: float = 1.0):
        if latency not in LATENCY_MODELS:
            raise ValueError(f"reference has no latency model {latency!r}")
        rng = np.random.default_rng(fleet_seed)

        def logn(mean, s):
            return mean * np.exp(s * rng.standard_normal(m) - 0.5 * s * s)

        self.m = m
        self.speed = logn(1.0, 0.4)
        self.bw_up = logn(1.25e6, 0.6)
        self.bw_down = logn(1e7, 0.6)
        self.availability = np.full(m, float(availability))
        self.latency, self.sigma, self.alpha = latency, sigma, alpha
        self.rng = np.random.default_rng(sim_seed)

    def _jitter(self) -> np.ndarray:
        m, rng = self.m, self.rng
        if self.latency == "lognormal":
            s = self.sigma
            return np.exp(s * rng.standard_normal(m) - 0.5 * s * s)
        if self.latency == "pareto":
            return 1.0 + rng.pareto(self.alpha, size=m)
        return np.ones(m)

    def arrivals(self, work_flops: float, down_bytes: float,
                 up_bytes: float) -> np.ndarray:
        m = self.m
        compute = (work_flops / NOMINAL_FLOPS) / self.speed * self._jitter()
        t = (down_bytes / self.bw_down + compute
             + np.broadcast_to(np.asarray(up_bytes, np.float64), (m,))
             / self.bw_up)
        up = self.rng.random(m) < self.availability
        return np.where(up, t, np.inf)


def client_work_flops(k0: int, n_params: int, d_local: float) -> float:
    """One gradient over d_local samples (4 flops/sample/param) plus k0
    prox steps (12 flops/param): the fleet model's compute per round."""
    return 4.0 * d_local * n_params + k0 * 12.0 * n_params


# ---------------------------------------------------------------------------
# the round math (device)
# ---------------------------------------------------------------------------

def sample_uniform(key, m: int, rho: float):
    """|S| = max(1, round(rho m)) clients, uniformly without replacement."""
    n_sel = max(1, int(round(rho * m)))
    perm = jax.random.permutation(key, m)
    return jnp.zeros((m,), bool).at[perm[:n_sel]].set(True)


def ens(Z, lam: float, eta: float):
    """Coordinate-wise argmin_w sum_i lam|w - Z_i| + eta/2 (w - Z_i)^2."""
    m = Z.shape[0]
    mean = jnp.mean(Z, axis=0, keepdims=True)
    a = jnp.arange(m + 1, dtype=Z.dtype)
    offs = ((lam / eta) * (2.0 * a - m) / m).reshape(
        (m + 1,) + (1,) * (Z.ndim - 1))
    return jnp.sort(jnp.concatenate([Z, mean + offs], axis=0), axis=0)[m]


def _soft(t, a):
    return jnp.sign(t) * jnp.maximum(jnp.abs(t) - a, 0.0)


def _sq_norm(tree):
    return sum(jnp.sum(jnp.square(x.astype(jnp.float32)))
               for x in jax.tree_util.tree_leaves(tree))


def _l1(tree):
    return sum(jnp.sum(jnp.abs(x.astype(jnp.float32)))
               for x in jax.tree_util.tree_leaves(tree))


def laplace_noise(key, tree, scale):
    """Laplace(0, scale) noise shaped like ``tree``: one key per leaf, split
    from ``key`` in leaf order, each coordinate the inverse CDF of a uniform
    draw on [-0.5 + 1e-7, 0.5)."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    out = []
    for k, x in zip(jax.random.split(key, len(leaves)), leaves):
        u = jax.random.uniform(k, x.shape, jnp.float32, -0.5 + 1e-7, 0.5)
        e = -jnp.sign(u) * jnp.log1p(-2.0 * jnp.abs(u))
        out.append((scale * e).astype(x.dtype))
    return jax.tree_util.tree_unflatten(treedef, out)


def prox_steps(wi, w_new, gi, k_start, cst: FedEPMConstants):
    """k0 closed-form prox steps of one client."""
    def step(w, t):
        k = k_start + t
        mu = cst.mu0 * (1.0 + cst.c * _sq_norm(tmap(jnp.subtract, w, w_new))) \
            * jnp.power(cst.alpha, (k + 1).astype(jnp.float32))
        w = tmap(lambda x, xn, g: (xn + _soft(mu * (x - xn) - g, cst.lam)
                                   / (cst.eta + mu)).astype(x.dtype),
                 w, w_new, gi)
        return w, mu

    w, mus = jax.lax.scan(step, wi, jnp.arange(cst.k0, dtype=jnp.int32))
    return w, mus[-1]


def make_clients(loss, cst: FedEPMConstants):
    """-> ``clients(W, Z, w_new, k, mask, batches, keys) -> (W, Z, grad_l1,
    per-client per-leaf squared gradient norms)``: the round of every
    client of the stack from the broadcast point ``w_new``, one after
    another (``lax.map``), so the largest model's gradients never sit on
    the device all at once. Clients outside ``mask`` keep W and Z."""
    grad = jax.grad(loss)
    noisy = cst.eps_dp > 0

    def one_client(wi, w_new, b, k, key):
        g = grad(w_new, b)
        w_upd, mu = prox_steps(wi, w_new, g, k, cst)
        gl1 = _l1(g)
        z_upd = w_upd
        if noisy:
            delta = 2.0 * gl1
            if cst.sensitivity_clip > 0:
                delta = jnp.minimum(delta, cst.sensitivity_clip)
            z_upd = tmap(jnp.add, w_upd, laplace_noise(
                key, w_upd, delta / (cst.eps_dp * mu)))
        leaf_sq = tmap(lambda x: jnp.sum(jnp.square(x.astype(jnp.float32))),
                       g)
        return w_upd, (z_upd if noisy else None), gl1, leaf_sq

    def clients(W, Z, w_new, k, mask, batches, keys):
        W_upd, Z_upd, gl1, leaf_sq = jax.lax.map(
            lambda a: one_client(a[0], w_new, a[1], k, a[2]),
            (W, batches, keys))
        if not noisy:
            Z_upd = W_upd

        def sel(new, old):
            return jnp.where(mask.reshape((-1,) + (1,) * (new.ndim - 1)),
                             new, old)
        return tmap(sel, W_upd, W), tmap(sel, Z_upd, Z), gl1, leaf_sq

    return clients


def make_round(loss, cst: FedEPMConstants):
    """-> jitted ``round(W, Z, k, mask, batches, key) -> (w_new, W, Z,
    grad_l1, per-leaf squared gradient norms)``. W and Z are donated; ``key``
    is the round's noise key (the third of its 3-way split).
    """
    clients = make_clients(loss, cst)

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def round_(W, Z, k, mask, batches, key):
        w_new = tmap(lambda z: ens(z, cst.lam, cst.eta), Z)
        keys = jax.random.split(key, cst.m)
        W, Z, gl1, leaf_sq = clients(W, Z, w_new, k, mask, batches, keys)
        return w_new, W, Z, gl1, tmap(jnp.sum, leaf_sq)

    return round_


def client_mesh(n: int):
    """A 1-D mesh over the first ``n`` devices, its one axis the clients'."""
    devs = jax.devices()
    if len(devs) < n:
        raise ValueError(f"the reference spans {n} chips; JAX found "
                         f"{len(devs)}")
    return jax.sharding.Mesh(np.array(devs[:n]), (CLIENT_AXIS,))


def make_sharded_round(loss, cst: FedEPMConstants, mesh, *,
                       exchange: bool = True):
    """``make_round`` with the client axis over ``mesh``: the same
    signature and results, W, Z, the mask, the batches and ``grad_l1``
    split m/n clients a chip.

    Each chip takes its own clients one after another. The aggregate is
    found leaf by leaf: an all-to-all turns the chip's (m/n, coords) rows
    into all m clients' rows of its coords/n block (the leaf flattened and
    padded to a multiple of n), the chip solves ENS there, and an
    all-gather makes the whole aggregate on every chip. So a chip holds
    its clients' W, Z and updates, one client's gradient and one leaf's
    (2m+1, coords/n) ENS block, never all m clients of a leaf.

    ``exchange=False`` is a fault: the all-to-all and the all-gather are
    left out, every chip aggregates its own clients and steps them from
    that, and the broadcast point returned is chip 0's.
    """
    from jax.sharding import PartitionSpec as P

    n = mesh.shape[CLIENT_AXIS]
    clients = make_clients(loss, cst)

    def aggregate(z):
        if not exchange:
            return ens(z, cst.lam, cst.eta)
        rows = z.reshape(z.shape[0], -1)
        size = rows.shape[1]
        rows = jnp.pad(rows, ((0, 0), (0, -size % n)))
        block = jax.lax.all_to_all(rows, CLIENT_AXIS, 1, 0, tiled=True)
        w = jax.lax.all_gather(ens(block, cst.lam, cst.eta), CLIENT_AXIS,
                               tiled=True)
        return w[:size].reshape(z.shape[1:])

    def local(W, Z, mask, batches, keys, k):
        w_new = tmap(aggregate, Z)
        W, Z, gl1, leaf_sq = clients(W, Z, w_new, k, mask, batches, keys)
        if not exchange:
            w_new = tmap(lambda x: x[None], w_new)
        return w_new, W, Z, gl1, leaf_sq

    c = P(CLIENT_AXIS)
    # an all-gather's result is the same on every chip, which shard_map's
    # check cannot infer: the check is off, and w_new is chip 0's
    sharded = jax.shard_map(local, mesh=mesh,
                            in_specs=(c, c, c, c, c, P()),
                            out_specs=(P() if exchange else c, c, c, c, c),
                            check_vma=False)

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def round_(W, Z, k, mask, batches, key):
        keys = jax.random.split(key, cst.m)
        w_new, W, Z, gl1, leaf_sq = sharded(W, Z, mask, batches, keys, k)
        if not exchange:
            w_new = tmap(lambda x: x[0], w_new)
        return w_new, W, Z, gl1, tmap(jnp.sum, leaf_sq)

    return round_


def make_sharded_objective(loss, mesh):
    """-> jitted ``objective(w, batches)``: each chip sums its own
    clients' losses one after another, and the chips' sums are added."""
    from jax.sharding import PartitionSpec as P

    def local(w, bs):
        return jax.lax.psum(jnp.sum(jax.lax.map(lambda b: loss(w, b), bs)),
                            CLIENT_AXIS)

    return jax.jit(jax.shard_map(local, mesh=mesh,
                                 in_specs=(P(), P(CLIENT_AXIS)),
                                 out_specs=P()))


class Start:
    """What every mix's reference starts from: the constants, the task's
    data, loss and initial parameters, the stacked client state, the
    experiment key and the jitted round, objective and draws.

    ``chips`` is the cell's, which is the configuration's ``engine.mesh``
    (``harness.mesh_chips``). On more than one chip the client axis of the
    state and the batches lies over ``client_mesh`` and the round is
    ``make_sharded_round``; on one, everything is on the default device.

    ``fault="half_batch"`` takes every client's loss over the first half of
    its rows only, the mean over those. ``fault="no_exchange"`` (more than
    one chip) leaves the exchange between chips out of the aggregate.
    """

    def __init__(self, task, cfg: dict, spec: dict, seed: int, *,
                 lower: bool = False, fault: str | None = None,
                 chips: int = 1):
        if fault == "no_exchange" and chips == 1:
            raise ValueError("no_exchange needs more than one chip")
        self.task, self.cst = task, constants_from(spec)
        batches, params0, self.d_local = task.make_data(cfg, seed)
        if fault == "half_batch":
            batches = task.half_batch(batches)
        loss = task.make_loss(cfg, lower)
        dt = task.state_dtype(lower)
        self.n_params = sum(int(np.prod(x.shape))
                            for x in jax.tree_util.tree_leaves(params0))
        m = self.cst.m
        if chips == 1:
            self.batches = tmap(jnp.asarray, batches)
            self.p0 = tmap(lambda x: jnp.asarray(x).astype(dt), params0)
            W = tmap(lambda x: jnp.broadcast_to(x[None], (m,) + x.shape),
                     self.p0)
            self.Z = tmap(lambda x: jnp.array(x, copy=True), W)
            self.W = tmap(lambda x: jnp.array(x, copy=True), W)
            self.round = make_round(loss, self.cst)
            self.objective = jax.jit(lambda w, bs: jnp.sum(
                jax.lax.map(lambda b: loss(w, b), bs)))
        else:
            mesh = client_mesh(chips)
            rep = jax.sharding.NamedSharding(mesh,
                                             jax.sharding.PartitionSpec())
            split = jax.sharding.NamedSharding(
                mesh, jax.sharding.PartitionSpec(CLIENT_AXIS))
            self.batches = jax.device_put(batches, split)
            self.p0 = jax.device_put(
                tmap(lambda x: jnp.asarray(x).astype(dt), params0), rep)
            stack = jax.jit(lambda p: tmap(
                lambda x: jnp.broadcast_to(x[None], (m,) + x.shape), p),
                out_shardings=split)
            self.Z, self.W = stack(self.p0), stack(self.p0)
            self.round = make_sharded_round(
                loss, self.cst, mesh, exchange=fault != "no_exchange")
            self.objective = make_sharded_objective(loss, mesh)
        self.key = jax.random.PRNGKey(seed)
        self.k = jnp.asarray(0, jnp.int32)
        self.w_tau = self.p0
        self.split3 = jax.jit(lambda kk: jax.random.split(kk, 3))
        # closes over the rate, not over self: a cycle would keep this
        # reference's device state alive after it returns
        rho = self.cst.rho
        self.select = jax.jit(lambda kk: sample_uniform(kk, m, rho))
        self.gl1 = self.leaf_g = None

    def fleet(self, fleet_spec: dict, seed: int) -> "Fleet":
        return Fleet(self.cst.m, fleet_seed=fleet_spec.get("seed", seed),
                     sim_seed=seed,
                     latency=fleet_spec.get("latency", "deterministic"),
                     sigma=fleet_spec.get("latency_sigma", 0.5),
                     alpha=fleet_spec.get("latency_alpha", 1.2),
                     availability=fleet_spec.get("availability", 1.0))

    def step(self, mask: np.ndarray, k_noise) -> None:
        """One aggregated round with the given mask."""
        (self.w_tau, self.W, self.Z, self.gl1, self.leaf_g) = self.round(
            self.W, self.Z, self.k, jnp.asarray(mask), self.batches, k_noise)
        self.k = self.k + self.cst.k0

    def f(self) -> float:
        return float(self.objective(self.w_tau, self.batches))

    def readings(self, out: dict) -> dict:
        """``out`` with the gradient and parameter-change readings added."""
        out["grad_l1"] = np.asarray(self.gl1, np.float64)
        out["grad_leaf_sq"] = {path: float(v) for path, v in
                               leaf_items(self.leaf_g)}
        out["change"] = leaf_change_norms(self.w_tau, self.p0)
        return out


# ---------------------------------------------------------------------------
# readings shared by the program side and the reference side
# ---------------------------------------------------------------------------

def leaf_items(tree):
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return [(jax.tree_util.keystr(p), x) for p, x in flat]


def leaf_change_norms(w, w0) -> dict:
    """||w - w0|| of every leaf, by its path."""
    norms = jax.jit(lambda a, b: tmap(
        lambda x, y: jnp.sqrt(jnp.sum(jnp.square(
            x.astype(jnp.float32) - y.astype(jnp.float32)))), a, b))(w, w0)
    return {p: float(v) for p, v in leaf_items(norms)}


def compare(prog: dict, ref: dict) -> dict:
    """The numbers that decide ``correct``, each a gap to the reference.

    loss_gap    worst relative gap of the objective over the rounds read
    grad_gap    worst client's gap of ||g_i||_1 (the chunk's last round),
                against that client's or the median client's, the larger
    change_gap  worst leaf's gap of ||w_tau - w0|| after the chunk, against
                that leaf's or the median leaf's, the larger; leaves whose
                reference gradient is under 1e-3 of the median leaf's are
                left out (round-off alone moves them)
    sim_mismatch  per-round fields of the simulated fleet (contacted,
                aggregated, duration, bytes) and the totals that differ
    """
    f_p, f_r = np.asarray(prog["f"], np.float64), np.asarray(ref["f"],
                                                             np.float64)
    if f_p.shape != f_r.shape:
        loss_gap = math.inf
    else:
        loss_gap = float(np.max(np.abs(f_p - f_r) / np.abs(f_r)))
    g_p, g_r = np.asarray(prog["grad_l1"]), np.asarray(ref["grad_l1"])
    g_den = np.maximum(g_r, np.median(g_r))
    grad_gap = float(np.max(np.abs(g_p - g_r) / g_den))
    gsq = ref["grad_leaf_sq"]
    g_med = float(np.median([math.sqrt(v) for v in gsq.values()]))
    keep = [p for p, v in gsq.items() if math.sqrt(v) >= 1e-3 * g_med]
    c_r = {p: ref["change"][p] for p in keep}
    c_med = float(np.median(list(c_r.values())))
    change_gap = max(
        (abs(prog["change"].get(p, math.inf) - v) / max(v, c_med)
         for p, v in c_r.items()), default=math.inf)
    mism = 0
    if len(prog["rounds"]) != len(ref["rounds"]):
        mism += 1 + abs(len(prog["rounds"]) - len(ref["rounds"]))
    for a, b in zip(prog["rounds"], ref["rounds"]):
        mism += sum(a[k] != b[k] for k in b)
    mism += sum(prog[k] != ref[k] for k in ("t", "bytes_up", "bytes_down"))
    vals = {"loss_gap": loss_gap, "grad_gap": grad_gap,
            "change_gap": change_gap, "sim_mismatch": float(mism)}
    # a reading that could not be taken (a shape mismatch, a NaN) is the
    # largest float, so it fails every limit and the line stays valid JSON
    return {k: (v if math.isfinite(v) else 1e300) for k, v in vals.items()}
