"""Plain reference of one COCS hierarchical-FL experiment.

Written from the paper (arXiv:2112.00925: Eq. 2-6, the CC-MAB index
policy, the P2 density greedy and the P3 cost-benefit greedy) and from
the configuration files under ``bench/configs``; it imports nothing of
the program under test. Randomness follows the published draw schedule
``threefry2x32/(seed,t,tag)/v1``: every draw is ``jax.random`` keyed by
``fold_in(PRNGKey(seed), 0|1)``, the round ``t`` and a fixed tag, so the
reference sees the same inputs as the program without taking any array
from it.

Two uses:

* ``replay``: the program's per-round selections are fed back in order
  (teacher forcing). The reference realizes each round itself, predicts
  the selection its own policy state would make, then updates that state
  with the program's selection and its own outcomes, and trains the edge
  models on the program's cohorts. What a run checks is how far the
  program's answers lie from these predictions (``bench/check.py``).
* ``simulate``: the reference makes its own selections. Run in bfloat16
  it stands in for a program that computes below the configuration's
  stated float32: the control that the check must refuse.

The reference is computed in ``dtype`` throughout. In float32 the model's
matmuls and convolutions run at the precision the configuration states
(``model.matmul_precision``: ``default``, the backend's own, or
``highest``) and the Eq. 3 means at ``Precision.HIGHEST``; in bfloat16
everything is stored and computed in bfloat16.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Dict, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax


class World(NamedTuple):
    """Static constants of one deployment, derived from its config file."""
    n: int
    m: int
    area: float
    es: tuple              # ((x, y), ...) per edge server, km
    tx_w: float
    noise_w: float
    rate_hi: float
    w: tuple               # the config's "world" dict as sorted items

    def __getitem__(self, key):
        return dict(self.w)[key]


def world(cfg: dict) -> World:
    w = cfg["world"]

    def watt(dbm):
        return 10 ** (dbm / 10.0) / 1000.0

    tx, noise = watt(w["tx_power_dbm"]), watt(w["noise_dbm_per_hz"])
    # context normalization: Eq. 4 at bandwidth_high, d = 0.05 km, |h|^2 = 4
    g = 4.0 * 10 ** (-(128.1 + 37.6 * math.log10(0.05)) / 10.0)
    rate_hi = w["bandwidth_high"] * math.log2(
        1.0 + tx * g / (noise * w["bandwidth_high"]))
    m = w["num_edge_servers"]
    ring = w["es_ring_km"]
    es = tuple((ring * math.cos(2 * math.pi * j / m),
                ring * math.sin(2 * math.pi * j / m)) for j in range(m))
    return World(n=w["num_clients"], m=m, area=ring + w["cell_radius_km"],
                 es=es, tx_w=tx, noise_w=noise, rate_hi=rate_hi,
                 w=tuple(sorted(w.items())))


# -- environment: Eq. 4-6 ------------------------------------------------------

def _uniform(key, tag, shape):
    return jax.random.uniform(jax.random.fold_in(key, tag), shape)


def init_clients(wd: World, seed, dt):
    """Initial positions, prices and resource profiles of every client."""
    k = jax.random.fold_in(jax.random.PRNGKey(seed), 0)
    n = wd.n

    def span(tag, shape, lo, hi):
        return lo + _uniform(k, tag, shape).astype(dt) * (hi - lo)

    return {"pos": span(0, (n, 2), -wd.area, wd.area),
            "price": span(1, (n,), wd["price_low"], wd["price_high"]),
            "bw0": span(2, (n,), wd["bandwidth_low"], wd["bandwidth_high"]),
            "comp0": span(3, (n,), wd["compute_low"], wd["compute_high"])}


def env_round(wd: World, seed, clients, pos, t, dt):
    """One realized round: ``(pos, t) -> (pos', observables)``."""
    n, m = wd.n, wd.m
    k = jax.random.fold_in(jax.random.fold_in(jax.random.PRNGKey(seed), 1),
                           t)

    def draw(fn, tag, shape):
        return fn(jax.random.fold_in(k, tag), shape).astype(dt)

    move = draw(jax.random.normal, 0, (n, 2))
    bw_n = draw(jax.random.normal, 1, (n,))
    comp_n = draw(jax.random.normal, 2, (n,))
    fad_dt = draw(jax.random.exponential, 3, (n, m))
    fad_ut = draw(jax.random.exponential, 4, (n, m))

    pos = jnp.clip(pos + wd["mobility"] * move, -wd.area, wd.area)
    bw = jnp.clip(clients["bw0"] * (1 + wd["jitter"] * bw_n),
                  wd["bandwidth_low"], wd["bandwidth_high"])
    comp = jnp.clip(clients["comp0"] * (1 + wd["jitter"] * comp_n),
                    wd["compute_low"], wd["compute_high"])
    es = jnp.asarray(np.asarray(wd.es, np.float32)).astype(dt)
    dist = jnp.sqrt(jnp.sum((pos[:, None, :] - es[None]) ** 2, axis=-1))
    gain = 10.0 ** (-(128.1 + 37.6 * jnp.log10(jnp.maximum(dist, 0.01)))
                    / 10.0)                                  # path loss

    def rate(fading):                                        # Eq. 4
        snr = wd.tx_w * (fading * gain) / (wd.noise_w * bw[:, None])
        return bw[:, None] * (jnp.log1p(snr) / jnp.log(2.0))

    bits = wd["update_bits"]
    tau = (bits / jnp.maximum(rate(fad_dt), 1e-9)            # Eq. 5
           + wd["workload"] / jnp.maximum(comp[:, None], 1e-9)
           + bits / jnp.maximum(rate(fad_ut), 1e-9))
    eligible = dist <= wd["cell_radius_km"]
    nearest = jax.nn.one_hot(jnp.argmin(dist, axis=1), m, dtype=bool)
    eligible = eligible | (~eligible.any(axis=1, keepdims=True) & nearest)
    costs = 2.0 * clients["price"] * bw / 1e6
    outcomes = (tau <= wd["deadline_s"]).astype(dt)          # Eq. 6
    phi_rate = jnp.clip(rate(1.0) / wd.rate_hi, 0.0, 1.0)
    phi_comp = ((comp - wd["compute_low"])
                / (wd["compute_high"] - wd["compute_low"]))
    contexts = jnp.stack([phi_rate, jnp.broadcast_to(phi_comp[:, None],
                                                     (n, m))], axis=-1)
    return pos, {"contexts": contexts, "eligible": eligible,
                 "costs": costs, "outcomes": outcomes, "tau": tau}


# -- the CC-MAB index policy and the budgeted greedy solvers ------------------

def _cubes(wd: World, contexts):
    h = wd["h_t"]
    idx = jnp.floor(jnp.nan_to_num(contexts) * h).astype(jnp.int32)
    return jnp.clip(idx, 0, h - 1)


def _at(arr, cubes):
    n, m = cubes.shape[:2]
    ii, jj = jnp.meshgrid(jnp.arange(n), jnp.arange(m), indexing="ij")
    return arr[ii, jj, cubes[..., 0], cubes[..., 1]]


def pair_values(wd: World, counters, p_hat, rd, t):
    """Optimistic estimates of every (client, ES) pair; under-explored
    pairs (visit count at most K(t) = t^z log t) get the UCB bonus."""
    alpha = wd["holder_alpha"]
    z = 2 * alpha / (3 * alpha + 2)                  # Theorem 2
    cubes = _cubes(wd, rd["contexts"])
    counts = _at(counters, cubes)
    est = _at(p_hat, cubes)
    tf = (t + 1).astype(jnp.float32)
    k_t = (wd["k_scale"] * jnp.maximum(tf, 1.0) ** z
           * jnp.log(jnp.maximum(tf, 2.0)))
    under = rd["eligible"] & (counts <= k_t)
    bonus = wd["bonus_scale"] * jnp.sqrt(
        2.0 * jnp.log(jnp.maximum(tf, 2.0)) / jnp.maximum(counts, 1))
    optimistic = jnp.where(counts == 0, 1.0, jnp.minimum(est + bonus, 1.0))
    values = jnp.where(under, optimistic, est).astype(p_hat.dtype)
    return values, under


def greedy_linear(values, costs, budgets, eligible):
    """P2: take the feasible pair of highest value per unit cost until
    none with a positive value fits (ties: larger flat index first)."""
    n, m = values.shape
    density = jnp.where(eligible, values / jnp.maximum(costs[:, None], 1e-12),
                        -jnp.inf)

    def body(carry):
        assign, left, k, _ = carry
        fits = ((assign < 0)[:, None] & eligible
                & (costs[:, None] <= left[None, :] + 1e-12))
        d = jnp.where(fits, density, -jnp.inf).reshape(-1)
        flat = n * m - 1 - jnp.argmax(d[::-1])
        ok = d[flat] > 0
        i, j = flat // m, flat % m
        assign = jnp.where(ok, assign.at[i].set(j.astype(jnp.int32)), assign)
        left = jnp.where(ok, left.at[j].add(-costs[i]), left)
        return assign, left, k + 1, ok

    out = lax.while_loop(lambda c: c[3] & (c[2] < n), body,
                         (jnp.full(n, -1, jnp.int32),
                          budgets.astype(values.dtype), 0, True))
    return out[0]


def greedy_sqrt(values, costs, budgets, eligible):
    """P3 (Eq. 19, utility sqrt(total / M)): take the feasible pair of
    highest marginal gain per unit cost (exact gains every step)."""
    n, m = values.shape

    def util(total):
        return jnp.sqrt(jnp.maximum(total, 0.0) / m)

    def body(carry):
        assign, left, total, k, _ = carry
        gains = util(total + values) - util(total)
        fits = ((assign < 0)[:, None] & eligible & (costs[:, None] > 0)
                & (costs[:, None] <= left[None, :] + 1e-12))
        d = jnp.where(fits, gains / jnp.maximum(costs[:, None], 1e-12),
                      -jnp.inf).reshape(-1)
        flat = n * m - 1 - jnp.argmax(d[::-1])
        i, j = flat // m, flat % m
        ok = fits.reshape(-1)[flat] & (gains[i, j] > 1e-15)
        assign = jnp.where(ok, assign.at[i].set(j.astype(jnp.int32)), assign)
        left = jnp.where(ok, left.at[j].add(-costs[i]), left)
        total = jnp.where(ok, total + values[i, j], total)
        return assign, left, total, k + 1, ok

    out = lax.while_loop(lambda c: c[4] & (c[3] < n), body,
                         (jnp.full(n, -1, jnp.int32),
                          budgets.astype(values.dtype),
                          jnp.zeros((), values.dtype), 0, True))
    return out[0]


def update_state(wd: World, counters, p_hat, rd, assign):
    """Running mean of the observed outcomes in each selected pair's cube."""
    n, m = wd.n, wd.m
    cubes = _cubes(wd, rd["contexts"])
    ii = jnp.arange(n)
    sel = assign >= 0
    j = jnp.clip(assign, 0, m - 1)
    a, b = cubes[ii, j, 0], cubes[ii, j, 1]
    x = rd["outcomes"][ii, j].astype(p_hat.dtype)
    c_old = counters[ii, j, a, b]
    p_old = p_hat[ii, j, a, b]
    p_new = (p_old * c_old + x) / (c_old + 1)
    p_hat = p_hat.at[ii, j, a, b].set(jnp.where(sel, p_new, p_old))
    counters = counters.at[ii, j, a, b].set(jnp.where(sel, c_old + 1, c_old))
    return counters, p_hat


@partial(jax.jit, static_argnums=(0, 2, 3, 4))
def _policy_run(wd: World, seeds, horizon: int, dt, forced: bool,
                assigns):
    """All seeds (vmapped) over ``horizon`` rounds. With ``forced`` the
    state follows ``assigns`` (S, T, N); otherwise the own predictions."""
    n, m, h = wd.n, wd.m, wd["h_t"]
    budgets = jnp.full((m,), wd["budget"], dt)
    solve = greedy_sqrt if wd["utility"] == "sqrt" else greedy_linear

    def one_seed(seed, forced_assign):
        clients = init_clients(wd, seed, dt)

        def step(carry, xs):
            pos, counters, p_hat = carry
            t, given = xs
            pos, rd = env_round(wd, seed, clients, pos, t, dt)
            values, under = pair_values(wd, counters, p_hat, rd, t)
            pred = solve(values, rd["costs"], budgets, rd["eligible"])
            used = given if forced else pred
            counters, p_hat = update_state(wd, counters, p_hat, rd, used)
            ii = jnp.arange(n)
            sel = used >= 0
            j = jnp.clip(used, 0, m - 1)
            arrived = jnp.where(sel, rd["outcomes"][ii, j], 0)
            parts = jnp.sum(arrived.astype(jnp.float32))
            util = (jnp.sqrt(jnp.maximum(parts, 0.0) / m)
                    if wd["utility"] == "sqrt" else parts)
            spend = jnp.zeros((m,), jnp.float32).at[j].add(
                jnp.where(sel, rd["costs"], 0).astype(jnp.float32))
            inelig = jnp.sum(sel & ~rd["eligible"][ii, j])
            return (pos, counters, p_hat), {
                "pred": pred, "used": used, "explored": under.any(),
                "participants": parts, "utilities": util, "spend": spend,
                "ineligible": inelig, "arrived": arrived.astype(jnp.float32),
                "tau": jnp.where(sel, rd["tau"][ii, j], jnp.inf
                                 ).astype(jnp.float32)}

        init = (clients["pos"], jnp.zeros((n, m, h, h), jnp.int32),
                jnp.zeros((n, m, h, h), dt))
        _, out = lax.scan(step, init,
                          (jnp.arange(horizon, dtype=jnp.int32),
                           forced_assign))
        return out

    return jax.vmap(one_seed)(seeds, assigns)


def policy_run(cfg: dict, seeds, horizon: int, dtype=jnp.float32,
               assigns: Optional[np.ndarray] = None) -> Dict[str, np.ndarray]:
    """Per-seed policy trajectories as host arrays, (S, T, ...)."""
    wd = world(cfg)
    forced = assigns is not None
    if not forced:
        assigns = np.full((len(seeds), horizon, wd.n), -1, np.int32)
    out = _policy_run(wd, jnp.asarray(np.asarray(seeds, np.uint32)),
                      int(horizon), jnp.dtype(dtype), forced,
                      jnp.asarray(assigns, jnp.int32))
    return {k: np.asarray(v) for k, v in out.items()}


# -- models and Eq. 2 / Eq. 3 training -----------------------------------------

def init_params(model: dict, seed: int, dt):
    """Edge-model initialization of one seed: the paper CNN (He-style
    normal over fan-in, zero biases) or a zero logistic regression."""
    shape = tuple(model["input_shape"])
    classes = model["classes"]
    if model["kind"] == "logreg":
        feats = int(np.prod(shape))
        return {"w": jnp.zeros((feats, classes), dt),
                "b": jnp.zeros((classes,), dt)}
    hgt, wid, ch = shape
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    c = model["conv_channels"]
    kk = model["kernel"]
    f1, f2 = model["fc"]
    flat = (hgt // 4) * (wid // 4) * c

    def normal(k, shp, fan_in):
        return (jax.random.normal(k, shp) / jnp.sqrt(float(fan_in))).astype(dt)

    return {"c1": normal(ks[0], (kk, kk, ch, c), kk * kk * ch),
            "b1": jnp.zeros((c,), dt),
            "c2": normal(ks[1], (kk, kk, c, c), kk * kk * c),
            "b2": jnp.zeros((c,), dt),
            "f1": normal(ks[2], (flat, f1), flat), "fb1": jnp.zeros((f1,), dt),
            "f2": normal(ks[3], (f1, f2), f1), "fb2": jnp.zeros((f2,), dt),
            "out": normal(ks[4], (f2, classes), f2),
            "outb": jnp.zeros((classes,), dt)}


def logits(model: dict, params, x, prec):
    if model["kind"] == "logreg":
        return jnp.dot(x.reshape(x.shape[0], -1), params["w"],
                       precision=prec) + params["b"]

    def conv(h, w):
        return lax.conv_general_dilated(
            h, w, (1, 1), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC"),
            precision=prec)

    def pool(h):
        return lax.reduce_window(h, -jnp.inf, lax.max, (1, 2, 2, 1),
                                 (1, 2, 2, 1), "VALID")

    h = pool(jax.nn.relu(conv(x, params["c1"]) + params["b1"]))
    h = pool(jax.nn.relu(conv(h, params["c2"]) + params["b2"]))
    h = h.reshape(h.shape[0], -1)
    h = jax.nn.relu(jnp.dot(h, params["f1"], precision=prec) + params["fb1"])
    h = jax.nn.relu(jnp.dot(h, params["f2"], precision=prec) + params["fb2"])
    return jnp.dot(h, params["out"], precision=prec) + params["outb"]


def xent(z, y):
    logp = jax.nn.log_softmax(z, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, y[:, None], axis=-1))


def _cohorts(assign: np.ndarray, n: int):
    """Per-round cohort lists in ascending client order, padded to the
    largest cohort: client id, ES, sampling id (ES * N + rank in its ES)
    and validity, each (T, K)."""
    t_len = assign.shape[0]
    k = max(1, int((assign >= 0).sum(axis=1).max()))
    cid = np.zeros((t_len, k), np.int32)
    es = np.zeros((t_len, k), np.int32)
    uid = np.zeros((t_len, k), np.int32)
    valid = np.zeros((t_len, k), np.float32)
    for t in range(t_len):
        sel = np.nonzero(assign[t] >= 0)[0]
        j = assign[t, sel]
        rank = np.zeros(len(sel), np.int32)
        for e in np.unique(j):
            rank[j == e] = np.arange(int((j == e).sum()))
        cid[t, :len(sel)] = sel
        es[t, :len(sel)] = j
        uid[t, :len(sel)] = j * n + rank
        valid[t, :len(sel)] = 1.0
    return cid, es, uid, valid, k


def _precisions(model: dict, dt):
    """(model matmuls, Eq. 3 means) precision for ``dt``."""
    if dt != jnp.float32:
        return None, None
    stated = model.get("matmul_precision", "highest")
    return (lax.Precision.HIGHEST if stated == "highest" else None,
            lax.Precision.HIGHEST)


@partial(jax.jit, static_argnums=(0, 1, 2, 3))
def _train_block(model_items, wd: World, steps: int, dt, edge, key, ts,
                 cid, es, uid, valid, arrived, tau, x, y, sizes):
    model = dict(model_items)
    prec, prec_mean = _precisions(model, dt)
    m, lr, z_min = wd.m, wd["lr"], wd["min_clients_z"]
    batch = model["batch"]

    def sgd(p, xb, yb):
        def step(p, b):
            g = jax.grad(lambda q: xent(logits(model, q, b[0], prec), b[1]))(p)
            return jax.tree.map(lambda a, ga: (a - lr * ga).astype(dt), p, g), None
        q, _ = lax.scan(step, p, (xb, yb))
        return jax.tree.map(lambda a, b: a - b, q, p)

    def round_step(edge, inp):
        t, c, e, u, v, arr, ta = inp
        rkey = jax.random.fold_in(key, t)
        idx = jax.vmap(lambda uu, sz: jax.random.randint(
            jax.random.fold_in(rkey, uu), (steps, batch), 0, sz))(u, sizes[c])
        xb = x[c[:, None, None], idx].astype(dt)
        yb = y[c[:, None, None], idx]
        start = jax.tree.map(lambda a: a[e], edge)
        if model["kind"] == "logreg":
            deltas = jax.vmap(sgd)(start, xb, yb)
        else:
            deltas = lax.map(lambda a: sgd(*a), (start, xb, yb))
        # Eq. 6: the arrivals, or the z_min fastest where fewer arrived
        same = (e[:, None] == e[None, :]) & (v[:, None] > 0) & (v[None, :] > 0)
        count = jnp.sum(same * arr[None, :], axis=1)
        k = jnp.arange(e.shape[0])
        faster = same & ((ta[None, :] < ta[:, None])
                         | ((ta[None, :] == ta[:, None]) & (k[None, :] < k[:, None])))
        fastest = jnp.sum(faster, axis=1) < z_min
        wgt = jnp.where(count >= z_min, arr, fastest.astype(jnp.float32)) * v
        onehot = (e[None, :] == jnp.arange(m)[:, None]) * wgt[None, :]  # (M, K)
        denom = jnp.maximum(jnp.sum(onehot, axis=1), 1.0)

        def agg(p, d):                                      # Eq. 3
            flat = d.reshape(d.shape[0], -1)
            s = jnp.dot(onehot.astype(dt), flat, precision=prec_mean)
            s = s / denom[:, None].astype(dt)
            return (p + s.reshape(p.shape)).astype(dt)

        edge = jax.tree.map(agg, edge, deltas)
        sync = (t + 1) % wd["t_es"] == 0
        edge = jax.tree.map(lambda a: jnp.where(
            sync, jnp.broadcast_to(jnp.mean(a, axis=0, dtype=jnp.float32
                                            ).astype(dt)[None], a.shape), a),
            edge)
        return edge, None

    edge, _ = lax.scan(round_step, edge,
                       (ts, cid, es, uid, valid, arrived, tau))
    return edge


@partial(jax.jit, static_argnums=(0, 1))
def _evaluate(model_items, dt, edge, test_x, test_y):
    model = dict(model_items)
    prec, _ = _precisions(model, dt)
    glob = jax.tree.map(lambda a: jnp.mean(a, axis=0), edge)
    z = logits(model, glob, test_x.astype(dt), prec)
    acc = jnp.mean((jnp.argmax(z, axis=-1) == test_y).astype(jnp.float32))
    return acc, xent(z, test_y).astype(jnp.float32)


def block_ends(horizon: int, eval_every: int):
    return [t + 1 for t in range(horizon)
            if (t + 1) % eval_every == 0 or t == horizon - 1]


def train_replay(cfg: dict, traffic: dict, data: dict, seeds, assigns,
                 arrived, tau, dtype=jnp.float32):
    """Edge models trained on the given cohorts: Eq. 2 local SGD of each
    selected client on minibatches drawn by the sampling schedule
    (``fold_in(fold_in(PRNGKey(seed + 11), t), ES * N + rank)``), Eq. 6
    deadline weights, Eq. 3 edge means and a cloud mean every ``t_es``
    rounds. Returns test (accuracy, loss), each (S, E)."""
    wd = world(cfg)
    dt = jnp.dtype(dtype)
    model = dict(cfg["model"], batch=min(int(traffic["batch_size"]),
                                         int(np.min(data["sizes"]))))
    model_items = tuple((k, tuple(v) if isinstance(v, list) else v)
                        for k, v in sorted(model.items()))
    steps = wd["local_epochs"] * int(traffic["batches_per_epoch"])
    ends = block_ends(assigns.shape[1], int(traffic["eval_every"]))
    x = jnp.asarray(data["x"])
    y = jnp.asarray(data["y"])
    sizes = jnp.asarray(data["sizes"])
    test_x, test_y = jnp.asarray(data["test_x"]), jnp.asarray(data["test_y"])
    accs, losses = [], []
    for si, seed in enumerate(seeds):
        cid, es, uid, valid, k = _cohorts(assigns[si], wd.n)
        rows = np.arange(assigns.shape[1])[:, None]
        arr = np.where(valid > 0, arrived[si][rows, cid], 0).astype(np.float32)
        ta = np.where(valid > 0, tau[si][rows, cid], np.inf).astype(np.float32)
        p0 = init_params(cfg["model"], int(seed), dt)
        edge = jax.tree.map(lambda a: jnp.broadcast_to(a[None], (wd.m,) + a.shape),
                            p0)
        key = jax.random.PRNGKey(int(seed) + 11)
        acc_s, loss_s, lo = [], [], 0
        for hi in ends:
            sl = slice(lo, hi)
            edge = _train_block(model_items, wd, steps, dt, edge, key,
                                jnp.arange(lo, hi, dtype=jnp.int32),
                                cid[sl], es[sl], uid[sl], valid[sl], arr[sl],
                                ta[sl], x, y, sizes)
            a, lval = _evaluate(model_items, dt, edge, test_x, test_y)
            acc_s.append(float(a))
            loss_s.append(float(lval))
            lo = hi
        accs.append(acc_s)
        losses.append(loss_s)
    return np.asarray(accs), np.asarray(losses)


def simulate(cfg: dict, traffic: dict, data: Optional[dict], seeds,
             dtype=jnp.bfloat16) -> Dict[str, np.ndarray]:
    """The reference making its own selections: the arrays a program run
    returns (selections, utilities, participants, explored, and with
    training accuracy and loss)."""
    out = policy_run(cfg, seeds, int(traffic["horizon"]), dtype)
    res = {"selections": out["used"], "utilities": out["utilities"],
           "participants": out["participants"], "explored": out["explored"]}
    if traffic.get("train"):
        res["accuracy"], res["loss"] = train_replay(
            cfg, traffic, data, seeds, out["used"], out["arrived"],
            out["tau"], dtype)
    return res


def replay(cfg: dict, traffic: dict, data: Optional[dict], seeds,
           result: Dict[str, np.ndarray], dtype=jnp.float32):
    """The reference following a run's selections (see module docstring)."""
    out = policy_run(cfg, seeds, int(traffic["horizon"]), dtype,
                     assigns=np.asarray(result["selections"], np.int32))
    if traffic.get("train"):
        out["accuracy"], out["loss"] = train_replay(
            cfg, traffic, data, seeds, out["used"], out["arrived"],
            out["tau"], dtype)
    return out
