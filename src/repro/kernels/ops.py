"""Jit'd public wrappers over the clustering kernels.

Backend resolution (per call, cheapest check first):
  * ``auto``   — compiled Pallas on TPU; pure-jnp XLA oracle elsewhere
                 (this CPU container). TPU is the TARGET; interpret mode is
                 the validation vehicle.
  * ``ref``    — force the jnp oracle.
  * ``pallas`` — force Pallas (compiled on TPU, interpret=True elsewhere).

The default is controlled by the ``REPRO_KERNEL_BACKEND`` environment
variable (``ref`` | ``pallas``); unset means ``auto``. An explicit
``backend=`` argument always wins over the environment.

Entry points (``ENTRY_POINTS`` below; every one is exercised differentially
ref-vs-pallas by tests/test_kernel_conformance.py — ``make test-kernels``):
  * ``min_dist(x, c, c_valid)``            — (n,) min-d2 + argmin sweep.
  * ``lloyd_reduce(x, w, assign, k)``      — per-center (sums, counts).
  * ``fused_assign_reduce(x, w, c, c_valid)`` — ONE sweep of ``x`` doing
    assignment + reduction + weighted cost; replaces the
    min_dist->lloyd_reduce pair on the Lloyd hot path (~2x less HBM
    traffic, and the (n,) assignment never round-trips through HBM).
  * ``remove_below(x, c, alive, v, c_valid)`` — fused SOCCER removal over
    (m, p, d) machine-sharded points: min-d2, threshold compare, alive-mask
    update and per-machine live counts in one sweep (the (m, p) distance
    array is never materialized).
  * ``update_min_dist(x, w, c, d2, c_valid)`` — fused D²-seeding step:
    lower the running min-d2 against newly chosen center(s) and total the
    weighted sampling mass, one sweep of ``x`` (adopted by k-means++,
    minibatch seeding and the sharded-coordinator seeding paths).
  * ``sensitivity_scores(x, w, c, c_valid)`` — fused coreset sensitivity
    pass (repro.coresets): per-point weighted cost shares, assignment,
    per-center cluster masses and the total cost of the bicriteria
    centers in one sweep of ``x`` (replaces a min_dist ->
    lloyd_reduce-counts -> cost-reduction chain).
  * ``truncated_cost(x, w, c, v, c_valid)`` — fused threshold-split
    truncated cost (repro.robust): ONE sweep of ``x`` splits the
    weighted cost of ``c`` at the distance threshold ``v`` into
    (kept cost, tail mass, tail cost) without materializing the (n,)
    distance array — the (k, z)-objective scoring pass.

Shape guards: feature dims above ``_MAX_PALLAS_D`` fall back to the XLA
oracle path. Center counts above ``_MAX_PALLAS_K`` no longer fall back:
the fused kernels switch to chunked-K variants that tile the center set
through VMEM (EIM11-sized center sets stay on the Pallas path). All
kernels take float32, bfloat16 or float16 points/centers (every
``UPLINK_DTYPES`` precision) and accumulate in float32.
The oracle and the kernels agree to float tolerance for every shape/dtype
in the conformance grid; callers never see which backend ran.
"""
from __future__ import annotations

import os
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.fused_lloyd import (fused_assign_reduce_chunked_pallas,
                                       fused_assign_reduce_pallas,
                                       remove_below_chunked_pallas,
                                       remove_below_pallas,
                                       update_min_dist_pallas)
from repro.kernels.lloyd import lloyd_reduce_pallas
from repro.kernels.min_dist import min_dist_pallas
from repro.kernels.sensitivity import sensitivity_scores_pallas
from repro.kernels.truncated import truncated_cost_pallas

_MAX_PALLAS_D = 512   # larger feature dims fall back to the XLA path
_MAX_PALLAS_K = 1024  # fused kernels keep all centers in VMEM up to this;
                      # beyond it the chunked-K Pallas variants take over

# The public kernel surface; the conformance harness iterates over this.
ENTRY_POINTS = ("min_dist", "lloyd_reduce", "fused_assign_reduce",
                "remove_below", "update_min_dist", "sensitivity_scores",
                "truncated_cost")


def on_tpu() -> bool:
    """Whether kernels compile for the chip (else Pallas interprets them
    and ``auto`` takes the oracle)."""
    return jax.default_backend() == "tpu"


def _backend(explicit: Optional[str]) -> str:
    choice = explicit or os.environ.get("REPRO_KERNEL_BACKEND") or "auto"
    if choice == "auto":
        return "pallas" if on_tpu() else "ref"
    if choice not in ("ref", "pallas"):
        raise ValueError(
            f"unknown kernel backend {choice!r} (from "
            f"{'backend=' if explicit else 'REPRO_KERNEL_BACKEND'}); "
            "expected 'auto', 'ref' or 'pallas'")
    return choice


def min_dist(x: jax.Array, c: jax.Array,
             c_valid: Optional[jax.Array] = None,
             *, backend: Optional[str] = None
             ) -> Tuple[jax.Array, jax.Array]:
    """(n,) min squared distance to valid centers and (n,) argmin."""
    b = _backend(backend)
    if b == "pallas" and x.shape[-1] <= _MAX_PALLAS_D:
        interpret = not on_tpu()
        return min_dist_pallas(x, c, c_valid, interpret=interpret)
    return ref.min_dist_ref(x, c, c_valid)


def lloyd_reduce(x: jax.Array, w: jax.Array, assign: jax.Array, k: int,
                 *, backend: Optional[str] = None
                 ) -> Tuple[jax.Array, jax.Array]:
    """Weighted per-center (sums, counts) for a Lloyd step."""
    b = _backend(backend)
    if b == "pallas" and x.shape[-1] <= _MAX_PALLAS_D:
        interpret = not on_tpu()
        return lloyd_reduce_pallas(x, w, assign, k, interpret=interpret)
    return ref.lloyd_reduce_ref(x, w, assign, k)


def fused_assign_reduce(x: jax.Array, w: jax.Array, c: jax.Array,
                        c_valid: Optional[jax.Array] = None,
                        *, backend: Optional[str] = None
                        ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """One-sweep Lloyd step: ((k, d) sums, (k,) counts, () weighted cost).

    Semantics == min_dist followed by lloyd_reduce plus the weighted cost
    of ``c`` on (x, w); every Pallas path reads ``x`` from HBM exactly
    once. Center sets beyond ``_MAX_PALLAS_K`` run the chunked-K
    kernel — a SINGLE grid walk with walk-resident (kp, d) accumulators
    and a per-chunk scatter once each panel's argmin is final (see
    ``benchmarks/bench_kernels.analytic`` for the byte model).
    """
    b = _backend(backend)
    if b == "pallas" and x.shape[-1] <= _MAX_PALLAS_D:
        interpret = not on_tpu()
        if c.shape[0] <= _MAX_PALLAS_K:
            return fused_assign_reduce_pallas(x, w, c, c_valid,
                                              interpret=interpret)
        return fused_assign_reduce_chunked_pallas(x, w, c, c_valid,
                                                  interpret=interpret)
    return ref.fused_assign_reduce_ref(x, w, c, c_valid)


def remove_below(x: jax.Array, c: jax.Array, alive: jax.Array, v: jax.Array,
                 c_valid: Optional[jax.Array] = None,
                 *, backend: Optional[str] = None
                 ) -> Tuple[jax.Array, jax.Array]:
    """Fused SOCCER removal: ((m, p) bool alive & min-d2 > v, (m,) counts)."""
    b = _backend(backend)
    if b == "pallas" and x.shape[-1] <= _MAX_PALLAS_D:
        interpret = not on_tpu()
        if c.shape[0] <= _MAX_PALLAS_K:
            return remove_below_pallas(x, c, alive, v, c_valid,
                                       interpret=interpret)
        return remove_below_chunked_pallas(x, c, alive, v, c_valid,
                                           interpret=interpret)
    return ref.remove_below_ref(x, c, alive, v, c_valid)


def update_min_dist(x: jax.Array, w: jax.Array, c: jax.Array,
                    d2: jax.Array,
                    c_valid: Optional[jax.Array] = None,
                    *, backend: Optional[str] = None
                    ) -> Tuple[jax.Array, jax.Array]:
    """Fused D²-seeding step: ((n,) min(d2, d2(x, c)), () sum w * new d2).

    With zero valid centers the update is a no-op on ``d2`` (both
    backends). The new-center block usually stays resident (1 row for
    sequential seeding, a candidate block for k-means‖ rounds); blocks
    beyond ``_MAX_PALLAS_K`` (k-means‖ seeding at large k_plus: the
    per-round buffer is ~6·k rows) run as a static sequence of resident
    sweeps — the elementwise min is associative, so slicing the block is
    exact, and the path stays on Pallas.
    """
    b = _backend(backend)
    if b == "pallas" and x.shape[-1] <= _MAX_PALLAS_D:
        interpret = not on_tpu()
        kc = c.shape[0]
        if kc <= _MAX_PALLAS_K:
            return update_min_dist_pallas(x, w, c, d2, c_valid,
                                          interpret=interpret)
        for s in range(0, kc, _MAX_PALLAS_K):
            cv = None if c_valid is None else c_valid[s:s + _MAX_PALLAS_K]
            d2, mass = update_min_dist_pallas(
                x, w, c[s:s + _MAX_PALLAS_K], d2, cv, interpret=interpret)
        return d2, mass
    return ref.update_min_dist_ref(x, w, c, d2, c_valid)


def sensitivity_scores(x: jax.Array, w: jax.Array, c: jax.Array,
                       c_valid: Optional[jax.Array] = None,
                       *, backend: Optional[str] = None
                       ) -> Tuple[jax.Array, jax.Array, jax.Array,
                                  jax.Array]:
    """Fused coreset sensitivity pass: ((n,) w·min-d2 scores, (n,) argmin
    assignment, (k,) per-center weight mass, () weighted cost of ``c``).

    One HBM sweep of ``x`` with the center set resident (the chain it
    replaces reads ``x`` three times — see kernels/sensitivity.py).
    Center sets beyond ``_MAX_PALLAS_K`` never arise on the coreset path
    (the bicriteria solution has O(k) centers), so instead of a chunked
    twin the sweep runs through the tiled ``min_dist`` kernel and the
    (n,)/(k,)-sized reductions (which never touch ``x``) run in XLA.
    Requires at least one valid center (guaranteed by the k-means++
    bicriteria seeding); with all centers invalid the oracle's +inf and
    the kernel's finite sentinel diverge.
    """
    b = _backend(backend)
    if b == "pallas" and x.shape[-1] <= _MAX_PALLAS_D:
        interpret = not on_tpu()
        if c.shape[0] <= _MAX_PALLAS_K:
            return sensitivity_scores_pallas(x, w, c, c_valid,
                                             interpret=interpret)
        d2, assign = min_dist_pallas(x, c, c_valid, interpret=interpret)
        return ref.sensitivity_from_min(w, d2, assign, c.shape[0])
    return ref.sensitivity_scores_ref(x, w, c, c_valid)


def truncated_cost(x: jax.Array, w: jax.Array, c: jax.Array, v: jax.Array,
                   c_valid: Optional[jax.Array] = None,
                   *, backend: Optional[str] = None
                   ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Fused truncated-cost split: (() kept cost of points with
    min-d2 <= v, () tail weight mass above v, () tail cost above v).

    The robust tier's scoring pass (repro.robust): one HBM sweep of
    ``x`` with the center set resident — nothing (n,)-sized is written
    back, so evaluating a (k, z) objective over the full data costs the
    same traffic as a removal pass. Per-machine triples psum into the
    global split (all three terms are plain sums). Center sets beyond
    ``_MAX_PALLAS_K`` never arise on the robust path (the final center
    set has k rows), so instead of a chunked twin the sweep runs through
    the tiled ``min_dist`` kernel with the (n,)-sized tail in XLA.
    Requires at least one valid center (like ``sensitivity_scores``);
    with all centers invalid the oracle's +inf and the kernel's finite
    sentinel land the tail on different sides of ``v``.
    """
    b = _backend(backend)
    if b == "pallas" and x.shape[-1] <= _MAX_PALLAS_D:
        interpret = not on_tpu()
        if c.shape[0] <= _MAX_PALLAS_K:
            return truncated_cost_pallas(x, w, c, v, c_valid,
                                         interpret=interpret)
        d2, _ = min_dist_pallas(x, c, c_valid, interpret=interpret)
        return ref.truncated_from_min(w, d2, v)
    return ref.truncated_cost_ref(x, w, c, v, c_valid)
