"""``fit_update`` — incremental clustering over a live point stream.

One call folds a new batch into the per-machine merge-and-reduce coreset
trees (``repro.streaming.tree`` — machine-local, zero uplink), warm-starts
Lloyd from the previous centers over the flattened tree coreset (uplink:
``m * k`` rows of center sums per iteration, independent of the batch or
tree size), and only escalates to a **full SOCCER re-cluster** over the
tree when the drift trigger fires.

The trigger is SOCCER's own stopping rule (``core.soccer.stopping_rule``)
evaluated on costs instead of counts: just as ``run_soccer`` issues
another round only while the live set exceeds the coordinator capacity,
``fit_update`` issues a re-cluster only while the warm-started centers'
per-weight cost on the tree coreset exceeds ``drift_tol`` times the
reference cost recorded at the last full re-cluster. Stationary streams
therefore never re-cluster (the warm start keeps the cost at the
reference level); a mean shift or cluster birth that Lloyd cannot track
from stale centers pushes the cost over the budget and fires exactly
when needed — "rounds only when needed" becomes "re-clusters only when
needed".

Uplink accounting (``ClusterResult.uplink_points``/``bytes`` are the
*per-update* realized uploads, so totals are cumulative over the
stream):

* fold: 0 — compression is machine-local;
* warm-start refine: ``m * k * refine_iters`` rows (each machine uploads
  its (k, d) weighted sums per Lloyd iteration);
* escalation: whatever the SOCCER run reports (typically one finalize
  gather of the live tree rows; rounds happen only if the caller
  constrains the coordinator via ``recluster_params``).

Backends: the virtual backend is supported; the mesh leg needs the
tree fold re-driven through ``Backend.compile`` and is left as the
multi-host extension point (ROADMAP).
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.api.backends import MeshBackend, resolve_backend
from repro.api.registry import get_algorithm
from repro.api.result import ClusterResult, uplink_bytes
from repro.core import programs
from repro.core.kmeans import kmeans
from repro.core.metrics import assignment_counts, distributed_cost
from repro.core.sharded_kmeans import distributed_lloyd
from repro.core.soccer import stopping_rule
from repro.coresets.sensitivity import default_coreset_size
from repro.obs import trace as obs_trace
from repro.obs.metrics import REGISTRY
from repro.streaming.state import StreamState
from repro.streaming.tree import flatten_tree, fold_batch, stream_bucket

# Every drift-trigger evaluation lands here (fired or not) with the cost
# ratio it saw — the re-cluster decision history of a live stream.
DRIFT_EVENTS = REGISTRY.event_log("streaming.drift.events")


def _refine(pts, ws, centers, *, comm, iters: int):
    """Warm-start Lloyd over the tree coreset, with the refined centers'
    cost and the coreset's total weight."""
    new = distributed_lloyd(comm, pts, ws, centers, iters)
    cost = distributed_cost(comm, pts, ws, new)
    total_w = comm.psum(jnp.sum(ws, axis=1))
    return new, cost, total_w


def _refined(backend, comm, iters: int, pts, ws, centers):
    """``_refine`` run as the program built for these inputs."""
    prog, _ = programs.build("stream", "refine", _refine, backend,
                             ("machine", "machine", "rep"),
                             ("rep", "rep", "rep"), (pts, ws, centers),
                             static=dict(comm=comm, iters=iters))
    return prog(pts, ws, centers)


def _shard_stream_batch(x_new: np.ndarray, w_new: Optional[np.ndarray],
                        m: int) -> tuple:
    """(n, d) batch -> ((m, pb, d), (m, pb)) with a bucketed static width.

    ``pb = stream_bucket(ceil(n / m))`` so any stream of batch sizes maps
    to O(log max_batch) distinct shapes; empty slots carry weight 0 (the
    compressor never samples them). Points land contiguously — in a real
    service each machine ingests its own stream, so placement is not a
    statistical knob here the way ``shard_policy`` is for batch ``fit``.
    """
    x_new = np.asarray(x_new, np.float32)
    n, d = x_new.shape
    w_new = (np.ones((n,), np.float32) if w_new is None
             else np.asarray(w_new, np.float32))
    pb = stream_bucket(-(-n // m))
    xs = np.zeros((m, pb, d), np.float32)
    ws = np.zeros((m, pb), np.float32)
    # contiguous split: machine j gets rows [j*q_j ...) via even quotas
    quota = [n // m + (1 if j < n % m else 0) for j in range(m)]
    off = 0
    for j, q in enumerate(quota):
        xs[j, :q] = x_new[off:off + q]
        ws[j, :q] = w_new[off:off + q]
        off += q
    return jnp.asarray(xs), jnp.asarray(ws)


def _condense_centers(key: jax.Array, centers: np.ndarray, k: int
                      ) -> np.ndarray:
    """A prior fit's center set (SOCCER returns the round union, which
    can exceed k rows) -> exactly (k, d) serving centers."""
    centers = np.asarray(centers, np.float32)
    if centers.shape[0] == k:
        return centers
    c = jnp.asarray(centers)
    w = jnp.ones((c.shape[0],), jnp.float32)
    out, _ = kmeans(key, c, w, k, 10)
    return np.asarray(out)


def init_stream(result: ClusterResult, *, m: Optional[int] = None,
                coreset_rows: int = 0, bicriteria: int = 0,
                seed: int = 0) -> StreamState:
    """Fresh StreamState warm-started from a batch ``fit`` result."""
    k = result.k
    m = m or int(result.params.get("m", 8))
    t = coreset_rows or max(128, default_coreset_size(k) // m)
    kb = bicriteria or max(1, min(k, t))
    key = jax.random.fold_in(jax.random.PRNGKey(seed), 0x5742)
    key, k_c = jax.random.split(key)
    return StreamState(
        levels=[], occupied=[],
        centers=_condense_centers(k_c, result.centers, k),
        version=1, key=key, k=k, m=m, t=t, kb=kb)


def fit_update(result: ClusterResult, x_new, *, backend=None,
               w: Optional[np.ndarray] = None, m: Optional[int] = None,
               seed: int = 0, refine_iters: int = 4,
               drift_tol: float = 2.0, recluster: str = "auto",
               coreset_rows: int = 0, bicriteria: int = 0,
               recluster_params: Optional[dict] = None) -> ClusterResult:
    """Fold a new batch into a stream and return refreshed centers.

    Args:
      result: the previous ``fit``/``fit_update`` result. The stream
        state rides in ``result.extra["stream"]``; a plain batch-fit
        result initializes a fresh stream warm-started from its centers.
      x_new: (n_new, d) new points (any batch size; shapes are bucketed
        so repeated updates hit the jit cache).
      backend: "virtual" (default) or a virtual-family Backend; the mesh
        leg is not wired yet (see module docstring).
      w: optional (n_new,) weights for the new points.
      m / seed / coreset_rows / bicriteria: stream-init knobs (ignored
        after the first update; the state carries them).
      refine_iters: warm-start Lloyd iterations per update.
      drift_tol: re-cluster budget — escalate when the post-refine
        per-weight tree cost exceeds ``drift_tol * ref_cost``.
      recluster: "auto" (drift-triggered) | "always" | "never".
      recluster_params: extra SOCCER params for the escalation run
        (e.g. ``eta_override`` to force a constrained-coordinator
        multi-round re-cluster).

    Returns:
      A ``ClusterResult`` whose ``centers`` are the (k, d) refreshed
      serving centers, ``rounds`` counts full re-clusters so far, and
      ``uplink_points``/``uplink_bytes`` list every update's realized
      upload (totals are cumulative over the stream). The carried
      ``StreamState`` is at ``extra["stream"]``; the center snapshot
      version at ``extra["version"]``.
    """
    if recluster not in ("auto", "always", "never"):
        raise ValueError(
            f"unknown recluster mode {recluster!r}: expected 'auto', "
            f"'always' or 'never'")
    t0 = obs_trace.clock()
    state: Optional[StreamState] = result.extra.get("stream")
    if state is None:
        state = init_stream(result, m=m, coreset_rows=coreset_rows,
                            bicriteria=bicriteria, seed=seed)
    elif m is not None and m != state.m:
        raise ValueError(f"m={m} conflicts with the carried stream state "
                         f"(m={state.m})")
    bk = resolve_backend(backend, state.m)
    if isinstance(bk, MeshBackend):
        raise NotImplementedError(
            "fit_update currently runs on the virtual backend; the "
            "mesh leg is the multi-host extension point (ROADMAP)")
    comm = bk.make_comm(state.m)
    d = state.centers.shape[1]

    # --- 1. fold the batch into the per-machine trees (zero uplink)
    xs, ws = _shard_stream_batch(x_new, w, state.m)
    if xs.shape[-1] != d:
        raise ValueError(f"x_new has d={xs.shape[-1]}, stream carries d={d}")
    state.key, k_fold = jax.random.split(state.key)
    fold_batch(state.levels, state.occupied, k_fold, xs, ws,
               state.t, state.kb)
    state.n_seen += float(np.sum(np.asarray(ws)))

    # --- 2. warm-start Lloyd over the flattened tree coreset
    pts, wts = flatten_tree(state.levels, state.occupied, state.m,
                            state.t, d)
    centers, cost, total_w = _refined(
        bk, comm, refine_iters, pts, wts,
        jnp.asarray(state.centers, jnp.float32))
    cost_per_w = float(cost) / max(float(total_w), 1e-30)
    up_rows = state.m * state.k * refine_iters

    # --- 3. drift trigger: SOCCER's stopping rule on costs
    fire = {"auto": stopping_rule(cost_per_w,
                                  drift_tol * state.ref_cost, math.inf)
            if math.isfinite(state.ref_cost) else False,
            "always": True, "never": False}[recluster]
    DRIFT_EVENTS.append(
        update=int(state.n_updates), fired=bool(fire),
        cost_per_weight=cost_per_w, ref_cost=state.ref_cost,
        version=int(state.version))
    if fire:
        obs_trace.event("streaming.drift.recluster",
                        update=int(state.n_updates),
                        cost_per_weight=cost_per_w,
                        ref_cost=state.ref_cost)
    reclustered = False
    if fire:
        state.key, k_rc = jax.random.split(state.key)
        driver = get_algorithm("soccer")
        rc = driver(np.asarray(pts), state.k, backend=bk, key=k_rc,
                    w=np.asarray(wts), alive=np.asarray(wts) > 0,
                    seed=int(state.n_updates) + 1,
                    **(recluster_params or {}))
        # SOCCER's solution is the UNION of every round's centers plus
        # the finalize block (> k rows once removal rounds ran), so the
        # k serving centers come from condensing the union: weight each
        # union center by its assigned tree-coreset mass, run a tiny
        # replicated weighted k-means, then warm-refine over the tree.
        union = jnp.asarray(rc.centers, jnp.float32)
        masses = assignment_counts(comm, pts, wts, union)
        state.key, k_cond = jax.random.split(state.key)
        cond, _ = kmeans(k_cond, union, masses, state.k, 10)
        centers, cost, total_w = _refined(bk, comm, refine_iters, pts, wts,
                                          cond)
        cost_per_w = float(cost) / max(float(total_w), 1e-30)
        up_rows += int(rc.uplink_points_total)
        state.n_reclusters += 1
        state.ref_cost = cost_per_w
        reclustered = True
    elif not math.isfinite(state.ref_cost):
        state.ref_cost = cost_per_w      # first update sets the reference
    else:
        # ratchet: the reference is the best per-weight cost ever seen,
        # so a lucky warm start tightens the drift band instead of a
        # stale early reference masking later drift
        state.ref_cost = min(state.ref_cost, cost_per_w)

    # --- 4. bookkeeping + result
    state.centers = np.asarray(centers, np.float32)
    state.version += 1
    state.n_updates += 1
    state.uplink_points.append(int(up_rows))
    state.uplink_bytes.append(
        int(uplink_bytes(np.int64(up_rows), d, np.float32)))
    res = ClusterResult(
        centers=state.centers, k=state.k, algo="stream", backend=bk.name,
        rounds=state.n_reclusters,
        uplink_points=np.asarray(state.uplink_points, np.int64),
        uplink_bytes=np.asarray(state.uplink_bytes, np.int64),
        wall_time_s=obs_trace.clock() - t0,
        params=dict(k=state.k, m=state.m, t=state.t, kb=state.kb,
                    refine_iters=refine_iters, drift_tol=drift_tol,
                    recluster=recluster),
        extra={"stream": state, "version": state.version,
               "reclustered": reclustered, "cost_per_weight": cost_per_w,
               "ref_cost": state.ref_cost,
               "epsilon_bound": state.epsilon_bound,
               "resident_rows": state.resident_rows_per_machine})
    return res
