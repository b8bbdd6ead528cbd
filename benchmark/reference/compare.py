"""The comparison that decides ``correct``: what one suggest round of the
timed path produced, held layer by layer against the plain reference.

Each function returns plain numbers, so that the program's outputs and the
control's (the reference computed at ``BF16`` in the program's place) are
judged by the same code. Layers:

- fit: the fit set is the right set of observations, the targets are
  transformed as stated, and ``alpha`` solves the kernel system at the
  fitted hyper-parameters, and the Adam fit falls short of the float64
  fit from the same warm start by no more than rounding (a fit that never
  moves falls far short); the marginal likelihood at the fitted
  hyper-parameters is reported beside them;
- scoring and selection: every Thompson draw's pick is, under the
  reference's draw, as good as the best candidate the reference can see,
  with the picks placed in the rows in draw order (first occurrences,
  after the exploit row);
- decoding: the returned params are the rows mapped to their bounds.
"""

import numpy as np

from benchmark.reference import gp, thompson
from benchmark.reference.precision import BF16


def encode(params, low, high):
    """Params -> unit-cube rows, as float32 (the history's dtype)."""
    return ((np.asarray(params, np.float64) - low) / (high - low)).astype(np.float32)


def fit_set_numbers(state, hist_x, hist_y, n_obs, local_m):
    """Exact checks of the fit set: (rows_unknown, set_wrong, raw targets)."""
    real = state["mask"] > 0
    fx = state["x"][real]
    table = {row.tobytes(): float(y) for row, y in zip(hist_x[:n_obs], hist_y[:n_obs])}
    raw = np.zeros(state["x"].shape[0], np.float32)
    found = [table.get(row.tobytes()) for row in fx]
    unknown = sum(v is None for v in found)
    raw[np.flatnonzero(real)] = [np.nan if v is None else v for v in found]
    expected = min(n_obs, local_m) if local_m else n_obs
    if unknown or fx.shape[0] != expected:
        return unknown, 1, raw
    if n_obs <= expected:
        same = {r.tobytes() for r in fx} == {r.tobytes() for r in hist_x[:n_obs]}
        return 0, int(not same), raw
    return 0, int(not _is_nearest_set(fx, hist_x[:n_obs], hist_y[:n_obs])), raw


def _is_nearest_set(fx, obs_x, obs_y, chunk=256):
    """Is ``fx`` the ``len(fx)`` observations nearest to one of its members?
    The incumbent is tried first; a trust-region restart centres elsewhere.
    Distances in float64, with room for the program's float32 ones."""
    members = {r.tobytes() for r in fx}
    inside = np.array([r.tobytes() in members for r in obs_x])
    obs = obs_x.astype(np.float64)
    sq = np.sum(obs * obs, axis=1)
    order = [int(np.argmin(obs_y))] + list(np.flatnonzero(inside))
    for start in range(0, len(order), chunk):
        idx = order[start:start + chunk]
        d2 = sq[None, :] + sq[idx][:, None] - 2.0 * obs[idx] @ obs.T
        far_in = np.where(inside[None, :], d2, -np.inf).max(1)
        near_out = np.where(inside[None, :], np.inf, d2).min(1)
        if np.any(inside[idx] & (far_in <= near_out * (1.0 + 1e-5) + 1e-9)):
            return True
    return False


def fit_numbers(rnd, algo, control=False):
    """Fit layer: y transform error, backward error of alpha, mll error at
    the candidate's hyper-parameters, and how far its fit fell short of the
    reference's."""
    st = rnd["state"]
    x, mask = st["x"].astype(np.float64), st["mask"].astype(np.float64)
    real = mask > 0
    raw = rnd["raw_y"]
    if algo["y_transform"] == "copula":
        y_ref = gp.copula(raw, mask)
    else:
        y_ref = np.where(real, raw, 0.0)
    yn_ref, _, _ = gp.normalize(y_ref, mask)
    d = x.shape[1]
    warm = rnd["warm"] if rnd["warm"] is not None else gp.init_hypers(d)
    steps = algo["fit_steps"]
    if rnd["warm"] is not None and algo.get("refit_steps") is not None:
        steps = algo["refit_steps"]
    theta_ref = gp.fit(warm, x, yn_ref, mask, steps)
    best = gp.mll(theta_ref, x, yn_ref, mask)
    if control:
        y_c = gp.copula(raw, mask, BF16) if algo["y_transform"] == "copula" else BF16(y_ref)
        yn_c, _, _ = gp.normalize(y_c, mask, BF16)
        theta = gp.fit(warm, x, yn_c, mask, steps, BF16)
        y_out, mll_out = y_c, BF16(gp.mll(theta, x, yn_c, mask, BF16))
        alpha = BF16(gp.solve(theta, x, yn_c, mask, BF16))
    else:
        theta, y_out, mll_out, alpha = st["hypers"], st["y"], st["mll"], st["alpha"]
    theta = np.asarray(theta, np.float64)
    at_theta = gp.mll(theta, x, yn_ref, mask)
    return {
        "fit_y_err": float(np.max(np.abs(np.asarray(y_out, np.float64) - y_ref)[real])),
        "fit_solve_resid": gp.backward_error(theta, x, yn_ref, mask, alpha),
        "fit_mll_err": float(abs(float(mll_out) - at_theta)),
        "fit_opt_gap": float(max(0.0, best - at_theta)),
    }


def order_gap(row_values, best, first_free):
    """Least, over the ways the draws can map onto the rows, of the worst
    draw's gap.

    ``row_values`` (rows, draws) holds each draw's value at each row, in row
    order; ``best`` each draw's best value. Draw j either repeats a row
    already placed or takes the next row, since the rows hold the distinct
    picks in draw order (after ``first_free`` leading rows, the exploit
    row). Near-ties cost only their own small gap; a row that no draw can
    have picked forces a large one.
    """
    n_rows, n_draws = row_values.shape
    gaps = row_values - best[None, :]
    prefix = np.minimum.accumulate(gaps, axis=0)
    inf = np.inf
    # cost[p]: least worst gap so far with rows[:p] placed.
    cost = np.full(n_rows + 1, inf)
    cost[first_free] = 0.0
    for j in range(n_draws):
        g = gaps[:, j]
        repeat = np.full(n_rows + 1, inf)
        repeat[1:] = np.maximum(cost[1:], prefix[:, j])
        advance = np.full(n_rows + 1, inf)
        advance[1:] = np.maximum(cost[:-1], g)
        cost = np.minimum(repeat, advance)
    return float(cost.min())


def thompson_numbers(rnd, raw, algo, control=False, chunk=4096):
    """Scoring and selection: the least worst draw gap over the rows, as
    the program's rows stand, or the worst gap of the control's own picks
    over the same candidates."""
    st = rnd["state"]
    x, mask = st["x"].astype(np.float64), st["mask"].astype(np.float64)
    y_norm = (st["y"].astype(np.float64) - float(st["y_mean"])) / float(st["y_std"]) * mask
    rows = rnd["rows"].astype(np.float64)
    n_draws = raw["eps"].shape[1] - 1 if algo["trust_region"] else raw["eps"].shape[1]
    raw = dict(raw, eps=raw["eps"][:, :n_draws])
    draw = thompson.posterior_draws(raw, st["hypers"], x, y_norm, mask)
    draw_c = (thompson.posterior_draws(raw, st["hypers"], x, y_norm, mask, BF16)
              if control else None)
    on_rows = thompson.scores(rows, draw)
    best = on_rows.min(0)
    # The control's pick per draw over pool and rows, and its reference value.
    cur = np.full(n_draws, np.inf)
    chosen = np.full(n_draws, np.nan)
    seen = np.concatenate([raw["pool"].astype(np.float64), rows])
    cols = np.arange(n_draws)
    for i in range(0, len(seen), chunk):
        part = seen[i:i + chunk]
        ref = thompson.scores(part, draw)
        best = np.minimum(best, ref.min(0))
        if control:
            c = thompson.scores(part, draw_c, BF16)
            pick = np.argmin(c, axis=0)
            better = c[pick, cols] < cur
            cur = np.where(better, c[pick, cols], cur)
            chosen = np.where(better, ref[pick, cols], chosen)
    if control:
        return {"thompson_gap": float(np.max(chosen - best))}
    return {"thompson_gap": order_gap(on_rows, best, int(algo["trust_region"]))}


def decode_numbers(rnd, low, high, control=False):
    """Decode layer: largest error of a returned param, as a share of its
    range."""
    u = rnd["rows"].astype(np.float64)
    ref = low + u * (high - low)
    out = BF16(low + BF16(rnd["rows"]) * (high - low)) if control else rnd["params"]
    return {"decode_err": float(np.max(np.abs(np.asarray(out, np.float64) - ref)) / (high - low))}


def rows_numbers(rnd):
    rows = rnd["rows"]
    return {"rows_outside": int(np.sum(np.any((rows < 0.0) | (rows > 1.0), axis=1)))}


def judge_round(rnd, raw, algo, low, high, control=False):
    """Every number of one round; the fit set's exact checks ride in
    ``rnd["fit_set"]``, taken when the round's targets were looked up."""
    out = dict(rnd["fit_set"])
    out.update(fit_numbers(rnd, algo, control))
    out.update(thompson_numbers(rnd, raw, algo, control))
    if rnd.get("params") is not None:
        out.update(decode_numbers(rnd, low, high, control))
    out.update(rows_numbers(rnd))
    return out


def worst(per_round):
    """Per number, the worst (largest) reading over the rounds; NaN wins."""
    keys = sorted({k for r in per_round for k in r})
    out = {}
    for k in keys:
        vals = [r[k] for r in per_round if k in r]
        out[k] = float("nan") if any(v != v for v in vals) else max(vals)
    return out

