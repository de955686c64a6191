#!/usr/bin/env python3
"""Chip smoke test: SOCCER's ``fit()`` main path on a TPU at the paper's size.

    python chip_smoke.py              # one chip: device, kernels, fit
    python chip_smoke.py --four-chip  # four chips: mesh vs virtual only

One process, no child processes. Phases, in order:

1. device  — the platform JAX found; anything but ``tpu`` exits 2.
2. kernels — each ``kernels.ops`` entry point compiled for the chip
   (``tpu_custom_call`` in its HLO, so neither the oracle nor interpret
   mode ran), checked against the jnp oracle and against a float32
   reference that takes explicit differences and no matmul, on the
   paper's data at d = 15: one machine's 1,280,000 points with k = 25,
   and 262,144 points with k = 1100 > ``ops._MAX_PALLAS_K`` (the
   chunked variants).
3. fit     — the paper's §8 mixture (n = 10,240,000, d = 15, k = 25,
   σ = 0.001, Zipf 1.5) through ``fit(algo="soccer", m=8, epsilon=0.05,
   backend="auto")`` and the paper's baseline ``fit(algo=
   "kmeans_parallel", rounds=5)`` on the same data. Each cost comes from
   the float32 reference on the full data, over the cost of the 25
   generating means.

``--four-chip`` runs only the mesh path and what it is compared with:
``fit(m=4, backend="mesh")`` over the host's four chips against
``backend="virtual"`` on the same pre-sharded data and key.

Any failure raises before the last line. The last line of stdout is one
JSON object: ``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

from repro.launch.cache import use_compile_cache  # noqa: E402  (no jax)

N = 10_240_000          # the paper's §8 synthetic size
D, K = 15, 25
M = 8                   # machines on one chip (virtual backend)
EPSILON = 0.05
N_MACHINE = N // M      # one machine's share: the kernel phase's n
# k > ops._MAX_PALLAS_K selects the chunked kernels. Fewer points there:
# the oracle materializes (n, k) float32 panels, 5.6 GB each at 1.28M
# points, and several of them would not fit the chip's 16 GB.
N_CHUNKED, K_CHUNKED = 262_144, 1100
RATIO_LIMIT = 1.1       # fit cost over the generating means' cost

# Tolerance of every min-d2 against the difference-form reference. The
# kernels (and the oracle) expand ||x - c||^2 = ||x||^2 - 2 x.c + ||c||^2
# in float32; each term is about ||x||^2 ~ 5 here while the answer is
# about 15 sigma^2 ~ 1.5e-5, so float32 rounding of the terms alone costs
# a few units of 2^-24 (||x||^2 + ||c||^2). On a TPU v5e at
# Precision.HIGHEST the kernels and the oracle stay within 3.7 units on
# 1.28M of these points (XLA's CPU backend reaches 5.1); 16 units leaves
# room for both. DEFAULT precision (one bf16 MXU pass) measured 5.4e4
# units on the v5e, and HIGH (the oracle only: Mosaic refuses it) 309, so
# the bound tells them apart.
D2_UNITS = 16
EPS32 = 2.0 ** -24
# Weighted sums of points (Lloyd sums, counts, masses) against float32
# adds: float32 accumulation over ~1e6 terms, relative to sum(w |x|).
SUM_RTOL = 2.0 ** -14
# Mesh vs virtual (--four-chip): the same algorithm and key; only the
# order of float adds inside collectives differs.
MESH_CENTER_ATOL = 1e-5     # 1% of sigma
MESH_COST_RTOL = 1e-4

_COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                   "/jax/core/compile/jaxpr_to_mlir_module_duration",
                   "/jax/core/compile/backend_compile_duration",
                   "/jax/compilation_cache/cache_retrieval_time_sec")
_CLOCK = {"compile_s": 0.0, "cache_hits": 0}


def log(*parts) -> None:
    print(*parts, flush=True)


def check(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(what)


# ---------------------------------------------------------------- device


def device_phase(want: int):
    import jax
    devs = jax.devices()
    d0 = devs[0]
    log(f"[device] jax.devices() = {devs}")
    log(f"[device] platform={d0.platform} kind={d0.device_kind} "
        f"count={len(devs)}")
    if d0.platform != "tpu":
        log(f"[device] FAIL: found platform {d0.platform!r}, need 'tpu'; "
            f"there is no CPU fallback")
        sys.exit(2)
    if len(devs) < want:
        log(f"[device] FAIL: need {want} chips, found {len(devs)}")
        sys.exit(2)
    return {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(devs)}


def _listen_compiles() -> None:
    import jax

    def on_duration(event, secs, **_):
        if event in _COMPILE_EVENTS:
            _CLOCK["compile_s"] += secs

    def on_event(event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            _CLOCK["cache_hits"] += 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    jax.monitoring.register_event_listener(on_event)


# ------------------------------------------------------------- reference


def exact_min(x, c, valid=None, block: int = 2048):
    """Float32 min over valid centers of sum((x - c)^2), by explicit
    differences (no matmul, so no MXU pass): ((n,) d2, (n,) argmin).
    ``x`` may carry leading machine axes; they are flattened."""
    import jax
    import jax.numpy as jnp
    d = x.shape[-1]
    x = x.reshape(-1, d)
    n = x.shape[0]
    ct = jnp.asarray(c, jnp.float32).T                  # (d, k) lanes = k
    ok = (jnp.ones((c.shape[0],), bool) if valid is None
          else jnp.asarray(valid, bool))
    xb = jnp.pad(x.astype(jnp.float32), ((0, -n % block), (0, 0)))

    def one(xs):
        diff = xs[:, :, None] - ct[None]                # (block, d, k)
        d2 = jnp.where(ok[None], jnp.sum(diff * diff, axis=1), jnp.inf)
        return jnp.min(d2, axis=1), jnp.argmin(d2, axis=1).astype(jnp.int32)

    d2, idx = jax.lax.map(one, xb.reshape(-1, block, d))
    return d2.reshape(-1)[:n], idx.reshape(-1)[:n]


def exact_cost(x, c) -> float:
    """sum_i min_j ||x_i - c_j||^2 by ``exact_min``, summed in float64."""
    import functools

    import jax
    import numpy as np
    d2, _ = jax.jit(functools.partial(exact_min, block=8192))(x, c)
    return float(np.sum(np.asarray(d2, np.float64)))


# ---------------------------------------------------------------- kernels


def _compiled(name, fn, *args):
    """AOT-compile ``fn`` for the chip; its HLO must hold the kernel."""
    import jax
    compiled = jax.jit(fn).lower(*args).compile()
    check("tpu_custom_call" in compiled.as_text(),
          f"{name}: no tpu_custom_call in the compiled HLO")
    return compiled(*args)


def _close(name, got, want, tol, what) -> float:
    """Assert |got - want| <= tol elementwise; return the worst ratio."""
    import numpy as np
    got, want, tol = (np.asarray(a, np.float64) for a in (got, want, tol))
    ratio = float(np.max(np.abs(got - want) / tol))
    check(ratio <= 1.0, f"{name}: {what} off by {ratio:.3g}x its tolerance")
    return ratio


def _groups(v, group, ngroups):
    """Sum per-center rows over duplicate-center groups."""
    import numpy as np
    out = np.zeros((ngroups,) + np.shape(v)[1:], np.float64)
    np.add.at(out, group, np.asarray(v, np.float64))
    return out


def kernel_case(tag, x, w, c, valid, group, ngroups):
    """All seven entry points on one (x, c) pair, each with the Pallas
    backend compiled for the chip, against the oracle and the exact
    reference. ``group`` maps each center to its distinct-center id
    (duplicated centers tie exactly; their mass is compared summed)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels import ops

    k = c.shape[0]
    d2_e, idx_e = jax.jit(exact_min)(x, c, valid)
    x2 = jnp.sum(x * x, axis=1)
    tol = D2_UNITS * EPS32 * (x2 + jnp.sum(c * c, axis=1)[idx_e])
    tol_np = np.asarray(tol, np.float64)
    w_np = np.asarray(w, np.float64)
    g_e = np.asarray(group)[np.asarray(idx_e)]
    worst = {}

    def both(name, f, *args):
        out_p = _compiled(f"{name}[{tag}]",
                          lambda *a: f(*a, backend="pallas"), *args)
        out_r = jax.jit(lambda *a: f(*a, backend="ref"))(*args)
        return out_p, out_r

    # min_dist: per point, and the argmin realizes the minimum
    (d2_p, idx_p), (d2_r, _) = both("min_dist", ops.min_dist, x, c, valid)
    worst["min_dist"] = max(
        _close("min_dist", d2_p, d2_e, tol, "min-d2 vs exact"),
        _close("min_dist/ref", d2_r, d2_e, tol, "oracle min-d2 vs exact"))
    check(bool(np.all(np.asarray(valid)[np.asarray(idx_p)])),
          "min_dist: argmin picked an invalid center")
    check(bool(np.all(np.asarray(group)[np.asarray(idx_p)] == g_e)),
          "min_dist: argmin is not the nearest distinct center")

    # lloyd_reduce on the exact assignment
    sums_e = np.zeros((ngroups, x.shape[1]))
    scale = np.zeros((ngroups, 1))
    np.add.at(sums_e, g_e, np.asarray(x, np.float64) * w_np[:, None])
    np.add.at(scale, g_e, (w_np * np.abs(np.asarray(x)).sum(1))[:, None])
    cnt_e = np.zeros((ngroups,))
    np.add.at(cnt_e, g_e, w_np)
    stol = SUM_RTOL * np.maximum(scale, 1e-30)
    ctol = SUM_RTOL * np.maximum(cnt_e, 1e-30)
    (s_p, n_p), (s_r, n_r) = both("lloyd_reduce",
                                  lambda *a, backend: ops.lloyd_reduce(
                                      *a, k, backend=backend),
                                  x, w, idx_e)
    worst["lloyd_reduce"] = max(
        _close("lloyd_reduce", _groups(s_p, group, ngroups), sums_e, stol,
               "sums"),
        _close("lloyd_reduce", _groups(n_p, group, ngroups), cnt_e, ctol,
               "counts"),
        _close("lloyd_reduce/ref", _groups(s_r, group, ngroups), sums_e,
               stol, "oracle sums"))

    # fused_assign_reduce: sums/counts as above, cost within sum(w tol)
    cost_e = float(np.sum(w_np * np.asarray(d2_e, np.float64)))
    cost_tol = float(np.sum(w_np * tol_np)) + SUM_RTOL * cost_e
    (s_p, n_p, c_p), (_, _, c_r) = both("fused_assign_reduce",
                                        ops.fused_assign_reduce,
                                        x, w, c, valid)
    worst["fused_assign_reduce"] = max(
        _close("fused_assign_reduce", _groups(s_p, group, ngroups), sums_e,
               stol, "sums"),
        _close("fused_assign_reduce", _groups(n_p, group, ngroups), cnt_e,
               ctol, "counts"),
        _close("fused_assign_reduce", c_p, cost_e, cost_tol, "cost"),
        _close("fused_assign_reduce/ref", c_r, cost_e, cost_tol,
               "oracle cost"))

    # remove_below over M machines: only points within tol of v may flip
    xm = x.reshape(M, -1, x.shape[1])
    rng = np.random.default_rng(1)
    alive = jnp.asarray(rng.random(xm.shape[:2]) > 0.1)
    v = jnp.float32(np.median(np.asarray(d2_e)))
    (a_p, l_p), (a_r, _) = both("remove_below", ops.remove_below,
                                xm, c, alive, v, valid)
    keep_e = np.asarray(alive).reshape(-1) & (np.asarray(d2_e) > float(v))
    band = np.abs(np.asarray(d2_e, np.float64) - float(v)) <= tol_np
    for nm, a in (("remove_below", a_p), ("remove_below/ref", a_r)):
        bad = (np.asarray(a).reshape(-1) != keep_e) & ~band
        check(not bad.any(), f"{nm}: {int(bad.sum())} points flipped "
              f"outside the tolerance band")
    check(bool(np.all(np.asarray(l_p) == np.asarray(a_p).sum(1))),
          "remove_below: live counts disagree with the mask")
    worst["remove_below"] = float(band.sum())       # points in the band

    # update_min_dist: running min against a previous d2, weighted mass
    prev = jnp.asarray(np.asarray(d2_e) * rng.uniform(0.5, 2.0, x.shape[0]),
                       jnp.float32)
    new_e = np.minimum(np.asarray(prev, np.float64),
                       np.asarray(d2_e, np.float64))
    (u_p, m_p), (u_r, _) = both("update_min_dist", ops.update_min_dist,
                                x, w, c, prev, valid)
    mass_tol = float(np.sum(w_np * tol_np)) + SUM_RTOL * float(
        np.sum(w_np * new_e))
    worst["update_min_dist"] = max(
        _close("update_min_dist", u_p, new_e, tol, "new min-d2"),
        _close("update_min_dist/ref", u_r, new_e, tol, "oracle min-d2"),
        _close("update_min_dist", m_p, np.sum(w_np * new_e), mass_tol,
               "mass"))

    # sensitivity_scores: scores, per-center masses, cost
    (sc_p, as_p, ms_p, co_p), _ = both("sensitivity_scores",
                                       ops.sensitivity_scores,
                                       x, w, c, valid)
    worst["sensitivity_scores"] = max(
        _close("sensitivity_scores", sc_p, w_np * np.asarray(d2_e),
               w_np * tol_np + 1e-30, "scores"),
        _close("sensitivity_scores", _groups(ms_p, group, ngroups), cnt_e,
               ctol, "masses"),
        _close("sensitivity_scores", co_p, cost_e, cost_tol, "cost"))
    check(bool(np.all(np.asarray(group)[np.asarray(as_p)] == g_e)),
          "sensitivity_scores: assignment is not the nearest center")

    # truncated_cost at the median: kept + tail == cost, tail mass
    (kc_p, tm_p, tc_p), _ = both("truncated_cost", ops.truncated_cost,
                                 x, w, c, v, valid)
    below = np.asarray(d2_e) <= float(v)
    tm_e = float(np.sum(np.where(below, 0.0, w_np)))
    worst["truncated_cost"] = max(
        _close("truncated_cost", kc_p + tc_p, cost_e, cost_tol,
               "kept + tail cost"),
        _close("truncated_cost", tm_p, tm_e,
               float(np.sum(w_np[band])) + SUM_RTOL * tm_e, "tail mass"))

    log(f"[kernels] {tag}: n={x.shape[0]} d={x.shape[1]} k={k} — all 7 "
        f"entry points compiled (tpu_custom_call) and within tolerance")
    for name, r in worst.items():
        if name == "remove_below":
            log(f"[kernels]   {name:<20s} points (of {x.shape[0]}) in the "
                f"±tol band at v: {int(r)}")
        else:
            log(f"[kernels]   {name:<20s} worst |err| / tolerance: {r:.4g}")


def kernel_phase(x_all, means, seed: int) -> None:
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels import ops

    rng = np.random.default_rng(seed + 100)
    sigma = 0.001
    # k = 25: the generating means, jittered by sigma, all valid
    c25 = means + rng.normal(0.0, sigma, means.shape).astype(np.float32)
    x = jnp.asarray(x_all[:N_MACHINE])
    w = jnp.asarray(rng.uniform(0.5, 2.0, N_MACHINE).astype(np.float32))
    kernel_case("k=25", x, w, jnp.asarray(c25), jnp.ones((K,), bool),
                np.arange(K), K)

    # k = 1100 > _MAX_PALLAS_K: the 25 centers tiled 44x in a random
    # order with ~30% masked off — exact duplicates tie bit for bit, and
    # the first valid copy of each lands in a random chunk
    check(K_CHUNKED > ops._MAX_PALLAS_K, "chunked case must exceed the "
          "resident-center limit")
    perm = rng.permutation(K_CHUNKED)
    group = np.arange(K_CHUNKED) % K
    group = group[perm]
    valid = rng.random(K_CHUNKED) > 0.3
    for g in range(K):                                # keep one copy valid
        valid[np.flatnonzero(group == g)[0]] = True
    kernel_case(f"k={K_CHUNKED}", jnp.asarray(x_all[:N_CHUNKED]),
                w[:N_CHUNKED], jnp.asarray(c25[group]),
                jnp.asarray(valid), group, K)


# -------------------------------------------------------------------- fit


def _fit_timed(label, x, **kw):
    from repro.api import fit
    c0, h0 = _CLOCK["compile_s"], _CLOCK["cache_hits"]
    res = fit(x, **kw)
    res.extra["compile_s"] = _CLOCK["compile_s"] - c0
    res.extra["cache_hits"] = _CLOCK["cache_hits"] - h0
    return res


def _report(label, res, cost, base) -> float:
    ratio = cost / base
    start = "cold" if res.extra["cache_hits"] == 0 else "compile-cache-warm"
    log(f"[fit] {label}: rounds={res.rounds} centers={len(res.centers)} "
        f"uplink_rows={res.uplink_points_total} "
        f"uplink_bytes={res.uplink_bytes_total} "
        f"wire_bytes={res.wire_bytes_total} "
        f"compile_s={res.extra['compile_s']:.3f} "
        f"cache_hits={res.extra['cache_hits']} "
        f"wall_s={res.wall_time_s:.3f} (one {start} run, not a benchmark)")
    log(f"[fit] {label}: cost={cost:.6g} / generating-means cost "
        f"{base:.6g} = {ratio:.4f}")
    return ratio


def fit_phase(x_host, means, seed: int) -> None:
    import gc

    import jax.numpy as jnp
    import numpy as np

    res = _fit_timed("soccer", x_host, k=K, algo="soccer", m=M,
                     epsilon=EPSILON, backend="auto", seed=seed,
                     trace="rounds")
    const = res.extra["const"]
    centers = np.asarray(res.centers)
    xd = res.extra["state"].x                         # (M, p, D) on device
    cost, base = exact_cost(xd, centers), exact_cost(xd, means)
    log(f"[fit] soccer: backend={res.backend} eta={const.eta} "
        f"max_rounds={const.max_rounds} n_hist={[int(v) for v in res.n_hist]}")
    ratio = _report("soccer", res, cost, base)
    check(res.rounds <= const.max_rounds, "soccer: rounds > max_rounds")
    # SOCCER returns C_out: every round's k_plus centers plus the final k
    check(centers.shape[1] == D and bool(np.isfinite(centers).all()),
          f"soccer: centers {centers.shape} not all finite")
    check(ratio <= RATIO_LIMIT, f"soccer: cost ratio {ratio:.4f} > "
          f"{RATIO_LIMIT}")
    del res, xd
    gc.collect()

    res = _fit_timed("kmeans_parallel", x_host, k=K, algo="kmeans_parallel",
                     m=M, rounds=5, backend="auto", seed=seed,
                     trace="rounds")
    centers = np.asarray(res.centers)
    xd = jnp.asarray(x_host)
    _report("kmeans_parallel", res, exact_cost(xd, centers), base)
    check(bool(np.isfinite(centers).all()),
          "kmeans_parallel: centers not finite")


# --------------------------------------------------------------- 4 chips


def four_chip_phase(x_host, seed: int) -> None:
    import gc

    import jax
    import numpy as np
    from repro.api import fit
    from repro.data.synthetic import shard_points

    parts = shard_points(x_host, 4, seed=seed)       # (4, p, d), shared
    key = jax.random.PRNGKey(seed)
    kw = dict(k=K, algo="soccer", epsilon=EPSILON, key=key, seed=seed)
    mesh = fit(parts, backend="mesh", **kw)
    shards = mesh.extra.pop("state").x.addressable_shards
    log(f"[four-chip] mesh shards of x: "
        f"{[(str(s.device), s.data.shape) for s in shards]}")
    check(len({s.device for s in shards}) == 4 and all(
        s.data.shape[0] == 1 for s in shards),
        "mesh: the 4 machines do not sit one per device")
    del shards
    mesh.extra.clear()
    virt = fit(parts, backend="virtual", **kw)
    virt.extra.clear()
    gc.collect()
    c_m, c_v = np.asarray(mesh.centers), np.asarray(virt.centers)
    xd = jax.device_put(parts, jax.devices()[0])
    cost_m, cost_v = exact_cost(xd, c_m), exact_cost(xd, c_v)
    diff = float(np.max(np.abs(c_m - c_v)))
    log(f"[four-chip] mesh: rounds={mesh.rounds} wall_s="
        f"{mesh.wall_time_s:.3f}; virtual: rounds={virt.rounds} wall_s="
        f"{virt.wall_time_s:.3f} (one cold run each, not a benchmark)")
    log(f"[four-chip] max |center diff|={diff:.3g} (tol "
        f"{MESH_CENTER_ATOL}); cost mesh={cost_m:.6g} virtual="
        f"{cost_v:.6g} (rtol {MESH_COST_RTOL})")
    check(mesh.rounds == virt.rounds, "mesh and virtual rounds differ")
    check(diff <= MESH_CENTER_ATOL, "mesh and virtual centers differ")
    check(abs(cost_m - cost_v) <= MESH_COST_RTOL * cost_v,
          "mesh and virtual costs differ")


# ------------------------------------------------------------------- main


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the data and of every fit")
    ap.add_argument("--four-chip", action="store_true",
                    help="run only mesh vs virtual over four chips")
    args = ap.parse_args(argv)

    log(f"[cache] JAX compilation cache: {use_compile_cache()}")
    device = device_phase(4 if args.four_chip else 1)
    _listen_compiles()

    from repro.configs.soccer_paper import GaussianMixtureSpec
    from repro.data.synthetic import gaussian_mixture
    t0 = time.perf_counter()
    spec = GaussianMixtureSpec(n=N, dim=D, k=K, sigma=0.001,
                               zipf_gamma=1.5, seed=args.seed)
    x_host, _, means = gaussian_mixture(spec)
    log(f"[data] {spec} generated in {time.perf_counter() - t0:.1f}s")

    if args.four_chip:
        four_chip_phase(x_host, args.seed)
    else:
        kernel_phase(x_host, means, args.seed)
        fit_phase(x_host, means, args.seed)
    from repro.kernels import tuning
    log(f"[done] tuning lookups {dict(tuning.TUNE_COUNTS)}; tables read: "
        f"{tuning.TABLES_READ or 'none (analytic model only)'}")
    log(f"[done] compile_s total={_CLOCK['compile_s']:.3f} "
        f"cache_hits={_CLOCK['cache_hits']}")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
