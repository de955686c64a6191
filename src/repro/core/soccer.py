"""SOCCER — Sampling, Optimal Clustering Cost Estimation, Removal (Alg. 1).

One jitted round == one communication round of the paper:

  sample P1,P2 (exact-size, HT-weighted)  ->  offset-scatter psum "upload"
  coordinator:  C_iter = A(P1, k_plus)     (replicated, or sharded — see
                v from truncated cost on P2  `sharded_coordinator`)
  "broadcast":  (v, C_iter) already replicated
  machines:     remove points with rho(x, C_iter)^2 <= v   (Pallas hot spot)
  stop when N <= eta  ->  finalize: gather survivors, A(V, k)

The number of rounds is data-dependent (the paper's built-in stopping
mechanism), so the driver is a host loop around the jitted round with one
scalar device->host sync per round — exactly the synchronization barrier a
real deployment pays. All shapes are static; removed points are masked.
"""
from __future__ import annotations

import dataclasses
import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.configs.soccer_paper import SoccerParams
from repro.core import programs
from repro.core.comm import VirtualCluster
from repro.obs import trace as obs_trace
from repro.obs.trace import current_trace, span
from repro.core.kmeans import kmeans
from repro.core.minibatch import minibatch_kmeans
from repro.core.sampling import draw_global_sample
from repro.core.truncated_cost import removal_threshold, trim_top_mass
from repro.kernels import ops


@dataclasses.dataclass(frozen=True)
class SoccerConstants:
    """Static (jit-constant) quantities derived from the paper's formulas."""
    k: int
    k_plus: int          # k + 9·log(1.1k/(δε))
    d_k: float           # 6.5·log(1.1k/(δε))
    eta: int             # coordinator capacity 36·k·n^ε·log(1.1k/(δε))
    max_rounds: int
    cap: int             # per-machine sample buffer width (gather mode)
    cap_sharded: int     # per-machine sample buffer (sharded coordinator):
                         # ~8x the balanced share eta/m instead of eta —
                         # the k_plus seeding scan and Lloyd sweep the
                         # whole buffer, so width is the memory term
    lloyd_iters: int
    blackbox: str
    minibatch_size: int
    sharded_coordinator: bool
    sharded_threshold: str = "bisect"   # bisect | topk (see sharded_kmeans)
    sharded_seeding: str = "d2"         # d2 | kmeanspar (latency: ~600 vs
                                        # ~15 collectives per round)
    outlier_frac: float = 0.0           # beyond-paper (the paper's §9
                                        # future work): exclude the
                                        # farthest mass from the FINAL
                                        # clustering fit
    straggler_rate: float = 0.0
    uplink_dtype: str = "float32"       # machine->coordinator payload
                                        # precision (see api.backends)
    uplink_wire: str = "values"         # resolved wire transport:
                                        # "values" | "codes" (int8 codes
                                        # + per-machine qparams on the
                                        # wire — core.comm compressed
                                        # gathers)
    uplink_mode: str = "points"         # points | coreset (repro.coresets):
                                        # "coreset" compresses each
                                        # machine's sample share to a
                                        # sensitivity coreset before the
                                        # upload — uplink decouples from eta
    coreset_rows: int = 0               # per-machine coreset rows t
    coreset_kb: int = 0                 # machine-side bicriteria centers


def derive_constants(n: int, p_local: int, params: SoccerParams,
                     eta_override: int = 0, m: int = 0,
                     uplink_dtype: str = "float32",
                     uplink_wire: str = "values") -> SoccerConstants:
    log_term = math.log(1.1 * params.k / (params.delta * params.epsilon))
    d_k = 6.5 * log_term
    k_plus = int(math.ceil(params.k + 9.0 * log_term))
    eta = eta_override or int(math.ceil(
        36.0 * params.k * (n ** params.epsilon) * log_term))
    eta = min(eta, n)
    max_rounds = params.max_rounds or (int(math.ceil(1.0 / params.epsilon)) + 2)
    m = m or params.n_machines
    cap_sharded = min(p_local, eta,
                      max(64, int(math.ceil(8.0 * eta / max(m, 1)))))
    coreset_rows = coreset_kb = 0
    if params.uplink_mode == "coreset":
        # uplink budget in rows, decoupled from eta: auto keeps enough
        # rows for the k_plus-center black box and a 4x wire reduction
        total_cs = params.coreset_size or max(4 * k_plus, eta // 4)
        total_cs = min(total_cs, eta)
        coreset_rows = max(1, min(-(-total_cs // max(m, 1)),
                                  min(p_local, eta)))
        coreset_kb = params.coreset_bicriteria or max(
            1, min(params.k, coreset_rows))
    return SoccerConstants(
        k=params.k, k_plus=k_plus, d_k=d_k, eta=eta, max_rounds=max_rounds,
        cap=min(p_local, eta), cap_sharded=cap_sharded,
        lloyd_iters=params.lloyd_iters,
        blackbox=params.blackbox, minibatch_size=params.minibatch_size,
        sharded_coordinator=params.sharded_coordinator,
        sharded_threshold=params.sharded_threshold,
        sharded_seeding=params.sharded_seeding,
        outlier_frac=params.outlier_frac,
        straggler_rate=params.straggler_rate,
        uplink_dtype=uplink_dtype, uplink_wire=uplink_wire,
        uplink_mode=params.uplink_mode,
        coreset_rows=coreset_rows, coreset_kb=coreset_kb)


class SoccerState(NamedTuple):
    """(local_m, ...) leaves are per-machine; the rest are replicated."""
    x: jax.Array             # (local_m, p, d) points
    w: jax.Array             # (local_m, p) data weights (1.0 = plain points)
    alive: jax.Array         # (local_m, p) not-yet-removed mask
    machine_ok: jax.Array    # (local_m,) False = machine failed (see repro.ft)
    key: jax.Array
    round_idx: jax.Array     # ()
    n_remaining: jax.Array   # ()
    centers: jax.Array       # (R, k_plus, d) C_out buffer (R = max_rounds+1)
    centers_valid: jax.Array  # (R, k_plus)
    v_hist: jax.Array        # (R,) thresholds
    n_hist: jax.Array        # (R,) N at the start of each round
    uplink: jax.Array        # (R,) realized points uploaded per round
    alpha_hist: jax.Array    # (R,) realized P2 sampling rate per round


def _new_leaves(alive: Optional[jax.Array], *, comm,
                const: SoccerConstants, p: int, d: int,
                make_w: bool) -> SoccerState:
    """The leaves of the state before round 1 that the caller does not
    give, for ``comm.local_m`` machines of ``p`` points in R^d: ``x``,
    ``key``, ``w`` unless ``make_w``, and a given ``alive`` are None.
    Run under a backend, each machine's leaves are built on its own
    device; ``n_remaining`` counts the live points of all machines."""
    local_m = comm.local_m
    r = const.max_rounds + 1
    live = jnp.ones((local_m, p), bool) if alive is None else alive
    return SoccerState(
        x=None, key=None,
        w=jnp.ones((local_m, p), jnp.float32) if make_w else None,
        alive=live if alive is None else None,
        machine_ok=jnp.ones((local_m,), bool),
        round_idx=jnp.int32(0),
        n_remaining=comm.psum(jnp.sum(live, axis=1, dtype=jnp.int32)),
        centers=jnp.zeros((r, const.k_plus, d), jnp.float32),
        centers_valid=jnp.zeros((r, const.k_plus), bool),
        v_hist=jnp.zeros((r,), jnp.float32),
        n_hist=jnp.zeros((r,), jnp.int32),
        uplink=jnp.zeros((r,), jnp.int32),
        alpha_hist=jnp.zeros((r,), jnp.float32))


def _with_given(state: SoccerState, x: jax.Array, key: jax.Array,
                w: Optional[jax.Array], alive: Optional[jax.Array]
                ) -> SoccerState:
    """``_new_leaves``' state completed with the caller's arrays."""
    state = state._replace(x=x.astype(jnp.float32), key=key)
    if w is not None:
        state = state._replace(w=w)
    if alive is not None:
        state = state._replace(alive=alive)
    return state


def init_state(x_parts: jax.Array, const: SoccerConstants, key: jax.Array,
               w: Optional[jax.Array] = None,
               alive: Optional[jax.Array] = None) -> SoccerState:
    """The state before round 1, built eagerly where ``x_parts`` lives
    (``run_soccer`` builds it under its backend instead)."""
    m, p, d = x_parts.shape
    state = _new_leaves(alive, comm=VirtualCluster(m), const=const, p=p,
                        d=d, make_w=w is None)
    return _with_given(state, x_parts, key, w, alive)


def _blackbox(const: SoccerConstants, key: jax.Array, x: jax.Array,
              w: jax.Array, k: int) -> jax.Array:
    if const.blackbox == "minibatch":
        c, _ = minibatch_kmeans(key, x, w, k, batch=const.minibatch_size)
    else:
        c, _ = kmeans(key, x, w, k, const.lloyd_iters)
    return c


def _draw_sample(comm, const: SoccerConstants, key: jax.Array,
                 state: SoccerState, alive_eff: jax.Array,
                 n_vec_resp: jax.Array):
    """One exact-size global sample -> (points, weights, uplink_rows,
    sample_real).

    ``uplink_mode="points"``: the paper's raw upload — (eta, d) points,
    uplink_rows == sample_real == the realized draw.
    ``uplink_mode="coreset"``: each machine compresses its share of the
    SAME eta-point draw to a sensitivity coreset before the upload
    (repro.coresets.uplink) — the coordinator sees m·t weighted rows,
    uplink_rows shrinks to them while sample_real keeps the underlying
    draw size (it drives the alpha = |P1|/N threshold scaling).
    """
    if const.uplink_mode == "coreset":
        from repro.coresets.uplink import draw_coreset_sample
        return draw_coreset_sample(comm, key, state.x, state.w, alive_eff,
                                   n_vec_resp, const.eta, const.cap,
                                   const.coreset_rows, const.coreset_kb,
                                   upload_dtype=const.uplink_dtype,
                                   wire=const.uplink_wire)
    pts, wts, real = draw_global_sample(comm, key, state.x, state.w,
                                        alive_eff, n_vec_resp, const.eta,
                                        const.cap,
                                        upload_dtype=const.uplink_dtype,
                                        wire=const.uplink_wire)
    return pts, wts, real, real


def soccer_round(state: SoccerState, comm, const: SoccerConstants
                 ) -> SoccerState:
    key, k_s1, k_s2, k_bb, k_strag1, k_strag2 = jax.random.split(
        state.key, 6)
    alive_eff = state.alive & state.machine_ok[:, None]

    # --- machine counts (the only per-machine metadata the coordinator needs)
    n_local = jnp.sum(alive_eff, axis=1).astype(jnp.int32)
    n_vec = comm.all_machines(n_local)
    n_total = jnp.sum(n_vec)

    # --- straggler deadline (repro.ft): laggards skip *sampling* this
    # round. Each upload (P1, P2) is its own communication event with its
    # own deadline, so the respond masks are drawn independently — the
    # two draws can (and under imbalance do) realize different sizes.
    def _respond(kk):
        if const.straggler_rate <= 0.0:
            return jnp.ones((comm.m,), bool)
        r = jax.random.uniform(kk, (comm.m,)) >= const.straggler_rate
        return r | (jnp.sum(jnp.where(r, n_vec, 0)) == 0)

    n_vec_r1 = jnp.where(_respond(k_strag1), n_vec, 0)
    n_vec_r2 = jnp.where(_respond(k_strag2), n_vec, 0)

    # the (k, z) truncation mass: z = outlier_frac·N population points
    # must not inflate the removal threshold (0 when the knob is off)
    outlier_mass = jnp.float32(const.outlier_frac) * n_total.astype(
        jnp.float32)

    if const.sharded_coordinator:
        # beyond-paper: samples stay sharded; collectives shrink from
        # O(eta*d) to O(k_plus*d*iters)  (see core/sharded_kmeans.py)
        from repro.core.sharded_kmeans import sharded_center_threshold
        c_iter, v, uplink_pts, alpha = sharded_center_threshold(
            comm, const, k_s1, k_s2, k_bb, state, alive_eff,
            n_vec_r1, n_vec_r2, n_total)
    else:
        # --- paper-faithful: upload P1, P2 (independent draws; in
        # coreset mode each is compressed machine-side before upload)
        p1, w1, up1, real1 = _draw_sample(comm, const, k_s1, state,
                                          alive_eff, n_vec_r1)
        p2, w2, up2, real2 = _draw_sample(comm, const, k_s2, state,
                                          alive_eff, n_vec_r2)
        # --- coordinator: C_iter = A(P1, k_plus); threshold from P2.
        # alpha is P2's OWN realized sampling rate: the truncation mass
        # L = l/alpha and the psi->population rescale both describe the
        # P2 statistic, so scaling it by P1's draw (which cap truncation
        # and per-draw straggler deadlines can make differ) biases v.
        c_iter = _blackbox(const, k_bb, p1, w1, const.k_plus)
        d2_p2, _ = ops.min_dist(p2, c_iter)
        alpha = real2.astype(jnp.float32) / jnp.maximum(
            n_total.astype(jnp.float32), 1.0)
        v = removal_threshold(d2_p2, w2, const.k, const.d_k, alpha,
                              outlier_mass=outlier_mass)
        uplink_pts = up1 + up2

    # --- broadcast (v, C_iter) is free (replicated); machines remove points
    # in ONE fused sweep: min-d2, threshold compare, mask update and live
    # counts — the (m, p) distance array is never materialized.
    alive_new, live = ops.remove_below(state.x, c_iter, alive_eff, v)
    n_rem = comm.psum(live)

    # --- bookkeeping
    i = state.round_idx
    centers = lax.dynamic_update_slice(
        state.centers, c_iter[None].astype(jnp.float32), (i, 0, 0))
    centers_valid = lax.dynamic_update_slice(
        state.centers_valid, jnp.ones((1, const.k_plus), bool), (i, 0))
    return state._replace(
        key=key, alive=alive_new, round_idx=i + 1, n_remaining=n_rem,
        centers=centers, centers_valid=centers_valid,
        v_hist=state.v_hist.at[i].set(v),
        n_hist=state.n_hist.at[i].set(n_total),
        uplink=state.uplink.at[i].set(uplink_pts),
        alpha_hist=state.alpha_hist.at[i].set(alpha))


def soccer_finalize(state: SoccerState, comm, const: SoccerConstants
                    ) -> SoccerState:
    """Gather the <= eta survivors and cluster them with A(V, k).

    With ``outlier_frac > 0`` (the paper's §9 robustness knob) the
    finalize is one trimmed-k-means step: a provisional A(V, k) fit,
    then the top ``z = outlier_frac·N`` weight mass of the gathered
    survivors (by distance to the provisional centers) is zeroed out of
    the HT weights before the final fit — the blackbox never spends
    centers chasing the z farthest cost units.
    """
    key, k_bb = jax.random.split(state.key)
    alive_eff = state.alive & state.machine_ok[:, None]
    n_local = jnp.sum(alive_eff, axis=1).astype(jnp.int32)
    n_vec = comm.all_machines(n_local)
    n_total = jnp.sum(n_vec)

    v_pts, v_w, up, _ = _draw_sample(comm, const, key, state, alive_eff,
                                     n_vec)
    if const.outlier_frac > 0.0:
        k_prov, k_bb = jax.random.split(k_bb)
        c_prov = _blackbox(const, k_prov, v_pts, v_w, const.k)
        d2, _ = ops.min_dist(v_pts, c_prov)
        z_mass = jnp.float32(const.outlier_frac) * n_total.astype(
            jnp.float32)
        v_w = trim_top_mass(d2, v_w, z_mass)
    c_fin = _blackbox(const, k_bb, v_pts, v_w, const.k)

    i = state.round_idx
    pad = jnp.zeros((const.k_plus - const.k, c_fin.shape[-1]), jnp.float32)
    row = jnp.concatenate([c_fin.astype(jnp.float32), pad], axis=0)
    valid_row = jnp.arange(const.k_plus) < const.k
    centers = lax.dynamic_update_slice(state.centers, row[None], (i, 0, 0))
    centers_valid = lax.dynamic_update_slice(
        state.centers_valid, valid_row[None], (i, 0))
    return state._replace(
        key=key, centers=centers, centers_valid=centers_valid,
        n_hist=state.n_hist.at[i].set(n_total),
        uplink=state.uplink.at[i].set(up))


@dataclasses.dataclass
class SoccerResult:
    centers: np.ndarray        # (|C_out|, d) valid centers, flattened
    rounds: int                # I (communication rounds before finalize)
    const: SoccerConstants
    n_hist: np.ndarray
    v_hist: np.ndarray
    uplink: np.ndarray         # points uploaded per round (incl. finalize)
    state: SoccerState
    # achieved wire traffic per round (incl. finalize), measured at the
    # traced collectives' itemsizes (core.comm.WireTally) — payload vs
    # metadata (count vectors, HT weights, qparams) split out
    wire_payload: np.ndarray = dataclasses.field(
        default_factory=lambda: np.zeros((0,), np.int64))
    wire_meta: np.ndarray = dataclasses.field(
        default_factory=lambda: np.zeros((0,), np.int64))


def flatten_centers(state: SoccerState) -> np.ndarray:
    c = obs_trace.to_host(state.centers)
    valid = obs_trace.to_host(state.centers_valid)
    return c[valid]


# Placement marks for SoccerState (see repro.api.backends): data leaves
# carry the machine axis, bookkeeping is replicated.
STATE_MARKS = SoccerState(
    x="machine", w="machine", alive="machine", machine_ok="machine",
    key="rep", round_idx="rep", n_remaining="rep", centers="rep",
    centers_valid="rep", v_hist="rep", n_hist="rep", uplink="rep",
    alpha_hist="rep")

# Placement marks for run_soccer's inputs (x, w, alive, key): the caller's
# arrays are put first, so each device receives only its machines' rows.
INPUT_MARKS = ("machine", "machine", "machine", "rep")


def effective_n(m: int, p: int, w: Optional[jax.Array],
                alive: Optional[jax.Array]) -> int:
    """Instance size for the paper's formulas: total live *weight*.

    Weighted inputs represent ``w`` duplicated points, so sizing the
    coordinator from the raw alive count would derive a too-small eta;
    the weight mass is what the guarantees are stated over.
    """
    if w is None and alive is None:
        return m * p
    w_np = np.ones((m, p), np.float64) if w is None else np.asarray(
        w, np.float64)
    if alive is not None:
        w_np = np.where(np.asarray(alive), w_np, 0.0)
    return max(int(round(float(np.sum(w_np)))), 1)


def stopping_rule(remaining: float, capacity: float, prev: float) -> bool:
    """THE SOCCER host-loop predicate: issue more work iff ``remaining``
    still exceeds ``capacity`` AND the last step made progress
    (``remaining < prev``; pass ``math.inf`` before the first step).

    ``run_soccer`` evaluates it on live-point counts against the
    coordinator capacity eta — "rounds only when needed". The streaming
    drift trigger (``repro.streaming.update``) evaluates the same
    predicate on the tree-coreset cost against the re-cluster budget
    ``drift_tol * ref_cost`` — "re-clusters only when needed".
    """
    return remaining > capacity and remaining < prev


def _place(backend, comm, const: SoccerConstants, given: tuple
           ) -> SoccerState:
    """Put the caller's ``(x, w, alive, key)`` on the devices, each
    machine's rows on its own, and build the rest of the state there with
    the init program; returns the first state. Only the state holds the
    placed arrays once this returns, so a round's copy of ``x`` replaces
    it rather than adding to it."""
    obs_trace.count_put(*given[:3])
    placed = backend.put(given, tuple(
        None if a is None else mk for a, mk in zip(given, INPUT_MARKS)))
    obs_trace.count_placement(given, placed)
    x, w, alive, key = placed
    _, p, d = x.shape
    init, _ = programs.build(
        "soccer", "init", _new_leaves, backend,
        (None if alive is None else "machine",),
        STATE_MARKS._replace(x=None, key=None,
                             w="machine" if w is None else None,
                             alive="machine" if alive is None else None),
        (alive,), static=dict(comm=comm, const=const, p=p, d=d,
                              make_w=w is None))
    return _with_given(init(alive), x, key, w, alive)


def run_soccer(x_parts: jax.Array, params: SoccerParams, *,
               backend=None,
               key: Optional[jax.Array] = None,
               w: Optional[jax.Array] = None,
               alive: Optional[jax.Array] = None,
               eta_override: int = 0,
               on_round=None) -> SoccerResult:
    """THE SOCCER host driver — the only round loop in the codebase.

    ``backend`` is anything ``repro.api.backends.resolve_backend``
    accepts ("virtual" default, "mesh", "auto", a Mesh, or a Backend);
    the stopping mechanism, no-progress guard, and round accounting below
    are shared by every deployment. ``on_round(round_idx, state)`` is an
    optional host callback after each round (checkpointing, failure
    injection); if it returns a state, the loop continues from it, placed
    as the round program expects.

    The init, round and finalize programs come from ``core.programs``:
    built once per process and key, each the same executable whether the
    fit is traced or not.
    """
    from repro.api.backends import check_uplink_wire, resolve_backend
    m, p, _ = x_parts.shape
    backend = resolve_backend(backend, m)
    comm = backend.make_comm(m)
    n = effective_n(m, p, w, alive)
    ud = getattr(backend, "uplink_dtype", "float32")
    const = derive_constants(
        n, p, params, eta_override, m=m, uplink_dtype=ud,
        uplink_wire=check_uplink_wire(
            getattr(backend, "uplink_wire", "auto"), ud))
    key = jax.random.PRNGKey(params.seed) if key is None else key
    trace = current_trace()
    with span("soccer.place"):
        state = _place(backend, comm, const, (x_parts, w, alive, key))
        if trace is not None:   # the copy lands here, not in round 1
            jax.block_until_ready(state)

    # the bodies are read from this module's globals here, at build time
    step, compile_round = programs.build(
        "soccer", "round", soccer_round, backend, (STATE_MARKS,),
        STATE_MARKS, (state,), static=dict(comm=comm, const=const))
    fin, compile_fin = programs.build(
        "soccer", "finalize", soccer_finalize, backend, (STATE_MARKS,),
        STATE_MARKS, (state,), static=dict(comm=comm, const=const))

    # The progress half of stopping_rule doubles as the no-progress
    # guard: if the threshold cannot remove anything (e.g. the truncation
    # mass exceeds N — coordinator far too small for this n), further
    # rounds are pure overhead; finalize on a subsample instead of
    # spinning to max_rounds. Each record's wall and compile time is its
    # span's own interval.
    rounds = 0
    prev_n = math.inf
    round_walls = []
    fin_wall = None
    if trace is not None:
        trace.meta.setdefault("eta", const.eta)
        trace.meta.setdefault("k", const.k)
        trace.meta.setdefault("max_rounds", const.max_rounds)
    while rounds < const.max_rounds:
        n_live = int(obs_trace.to_host(state.n_remaining))
        if not stopping_rule(n_live, const.eta, prev_n):
            break
        prev_n = n_live
        with span("soccer.round", round=rounds + 1) as sp:
            state = step(state)
            if trace is not None:
                jax.block_until_ready(state.n_remaining)
        if trace is not None:
            round_walls.append(sp.t1 - sp.t0)
        rounds += 1
        if on_round is not None:
            new = on_round(rounds, state)
            if new is not None and new is not state:
                state = backend.put(new, STATE_MARKS)
    with span("soccer.finalize") as sp:
        state = fin(state)
        if trace is not None:
            jax.block_until_ready(state.centers)
    if trace is not None:
        fin_wall = sp.t1 - sp.t0

    with span("soccer.readback"):
        up = obs_trace.to_host(state.uplink)
        n_hist = obs_trace.to_host(state.n_hist)
        v_hist = obs_trace.to_host(state.v_hist)
        centers = flatten_centers(state)
        if trace is not None:
            a_hist = obs_trace.to_host(state.alpha_hist)
            n_rem = int(obs_trace.to_host(state.n_remaining))
    # achieved wire bytes: static per-trace payload + per-row widths of
    # the ragged channels x the realized row counts the state tracked
    wire_payload = np.concatenate(
        [step.tally.bytes_at(up[:rounds]),
         fin.tally.bytes_at(up[rounds:rounds + 1])])
    wire_meta = np.concatenate(
        [step.tally.meta_bytes_at(up[:rounds]),
         fin.tally.meta_bytes_at(up[rounds:rounds + 1])])
    if trace is not None:
        _emit_soccer_records(trace, const, rounds, prev_n, n_rem, up,
                             n_hist, v_hist, a_hist, wire_payload,
                             wire_meta, round_walls, fin_wall,
                             compile_round, compile_fin)
    return SoccerResult(
        centers=centers, rounds=rounds, const=const,
        n_hist=n_hist, v_hist=v_hist, uplink=up, state=state,
        wire_payload=wire_payload, wire_meta=wire_meta)


def _emit_soccer_records(trace, const: SoccerConstants, rounds: int,
                         prev_n: float, n_rem: int, up: np.ndarray,
                         n_hist: np.ndarray, v_hist: np.ndarray,
                         a_hist: np.ndarray, wire_payload: np.ndarray,
                         wire_meta: np.ndarray, round_walls, fin_wall,
                         compile_round, compile_fin) -> None:
    """Turn the state histories into the pinned per-round records.

    ``n_hist[i]`` is N at the *start* of (0-indexed) round ``i``;
    finalize writes ``n_hist[rounds]``, so the post-removal live count of
    round ``r`` (1-based) is ``n_hist[r]`` for every r — that is the
    number the stopping rule compared against eta.
    """
    for r in range(1, rounds + 1):
        n_after = int(n_hist[r])
        trace.emit_round(
            round=r, phase="round",
            n_live=n_hist[r - 1], capacity=const.eta,
            alpha=a_hist[r - 1], v=v_hist[r - 1],
            removed=int(n_hist[r - 1]) - n_after,
            stop_ratio=n_after / const.eta,
            stop_margin=n_after - const.eta,
            uplink_rows=up[r - 1],
            wire_payload_bytes=wire_payload[r - 1],
            wire_meta_bytes=wire_meta[r - 1],
            wall_s=round_walls[r - 1] if r <= len(round_walls) else None,
            compile_s=compile_round if r == 1 else None)
    trace.emit_round(
        round=rounds + 1, phase="finalize",
        n_live=n_hist[rounds], capacity=const.eta,
        uplink_rows=up[rounds],
        wire_payload_bytes=wire_payload[rounds],
        wire_meta_bytes=wire_meta[rounds],
        wall_s=fin_wall, compile_s=compile_fin)
    if n_rem <= const.eta:
        trace.stop_reason = "capacity"
    elif prev_n != math.inf and n_rem >= prev_n:
        trace.stop_reason = "no_progress"
    else:
        trace.stop_reason = "max_rounds"
