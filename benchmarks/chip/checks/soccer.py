"""The check of a SOCCER fit: what is kept from its result, and how it is
compared with the float32 reference once the window has closed.

A traffic mix names its check (``"check"`` in ``traffic/<name>.json``);
the harness calls ``record`` on every timed fit's result and ``compare``
on each record after the window. ``NUMBERS`` are the names ``compare``
returns, each with a limit in every cell that runs this check:

- ``flip_share``: points whose survival after the last round differs
  from the reference's removal chain at the fit's own recorded centers
  and thresholds, as a share of all points;
- ``count_gap``: the worst gap between a live count the fit reported (at
  the start of each round, after the last, and the one its stopping rule
  last read) and the reference's, as a share of all points;
- ``rounds_over_max``: rounds beyond the fit's ``max_rounds``.
"""
from __future__ import annotations

NUMBERS = ("flip_share", "count_gap", "rounds_over_max")


def record(res) -> dict:
    """What the check needs of one ``fit()`` result. ``round_centers`` (the
    centers each round removed against) is also read by the
    ``remove_below`` roofline."""
    import numpy as np
    state = res.extra["state"]
    rounds = int(res.rounds)
    centers = np.asarray(state.centers)
    valid = np.asarray(state.centers_valid)
    return {"max_rounds": int(res.extra["const"].max_rounds),
            "n_hist": np.asarray(state.n_hist),
            "n_remaining": int(state.n_remaining),
            "round_centers": [centers[r][valid[r]] for r in range(rounds)],
            "v_hist": np.asarray(state.v_hist),
            "alive": state.alive}       # survivors after the last round


def compare(xs, n: int, fit) -> dict:
    """One fit's numbers against the reference; ``xs`` are the data's
    per-device pieces in machine order, ``n`` the points in all."""
    import jax.numpy as jnp

    import gen
    import reference
    own = fit.own
    out = {"flip_share": 0.0,
           "count_gap": abs(int(own["n_hist"][0]) - n) / n,
           "rounds_over_max": max(fit.rounds - own["max_rounds"], 0)}
    rounds = [(c, float(v)) for c, v in zip(own["round_centers"],
                                            own["v_hist"])]
    if not rounds:
        return out
    chains = [reference.removal_chain(p, rounds) for p in xs]
    live = [n] + [sum(int(jnp.sum(ch[r])) for ch in chains)
                  for r in range(len(rounds))]
    gaps = [abs(int(own["n_hist"][r]) - live[r])
            for r in range(1, len(live))]
    gaps.append(abs(own["n_remaining"] - live[-1]))
    out["count_gap"] = max(out["count_gap"], max(gaps) / n)
    flips = sum(int(reference.count_diff(a, ch[-1]))
                for a, ch in zip(gen.parts(own["alive"]), chains))
    out["flip_share"] = flips / n
    return out
