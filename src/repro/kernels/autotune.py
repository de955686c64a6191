"""Measured block-size autotuning for the clustering kernels.

``kernels.tuning`` ships an *analytic* VMEM model; this module replaces
guesses with measurements. For every ``(d, k, dtype)`` bucket of the
shared tuning table it times the Pallas kernels over a candidate grid of
``(bn, bk)`` point/center panel sizes (and ``(bn, k_chunk)`` for the
chunked-K fused kernels) **on the hardware the process is running on**,
then persists the winners to a per-backend JSON table that
``tuning.block_sizes`` / ``tuning.chunk_sizes`` consult before falling
back to the analytic model (see ``REPRO_AUTOTUNE`` in tuning.py).

What is timed: the kernels are invoked through their Pallas entry points
with explicit size overrides (the ``bn=``/``bk=``/``k_chunk=`` static
kwargs), compiled on TPU and interpreted elsewhere. On a CPU container
the interpret-mode timings do not model TPU performance — they tune the
conformance-suite runtime only — so tables are keyed by
``jax.default_backend()`` and a table measured on one backend is never
consulted on another.

Usage:
    python -m repro.kernels.autotune            # full sweep -> .cache/repro
    python -m repro.kernels.autotune --quick    # small-n sweep
    python -m repro.kernels.autotune --package  # write the committed table
    make autotune

Candidate sizes are multiples of the 128-sublane tile by construction and
are re-normalized through the same rounding ``clamp_bn`` applies, so a
measured table can never hand a kernel a non-tile panel. Tests inject a
deterministic fake ``timer`` so CI never depends on wall-clock noise.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import time
from typing import Callable, Dict, Iterable, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops, tuning

# Candidate panel grids (all 128-tile multiples; clamp_bn round-trips).
CANDIDATE_BN = (128, 256, 512, 1024)
CANDIDATE_BK = (128, 256)
CANDIDATE_CHUNK_BN = (128, 256, 512)
CANDIDATE_K_CHUNK = (256, 512, 1024)

# Candidates whose live VMEM panels exceed this are skipped outright —
# timing them would only discover the compile failure the analytic model
# already predicts. Matches the ~4 MiB budget of tuning.py with headroom
# for double-buffered streams.
VMEM_CANDIDATE_BUDGET = 10 * 2**20

# k used to exercise the chunked-K kernels (must exceed ops._MAX_PALLAS_K
# conceptually, but the kernel functions are called directly so any k
# spanning several chunks works).
CHUNK_SWEEP_K = 2048

Timer = Callable[[Callable[[], object], dict], float]


def _default_timer(fn: Callable[[], object], meta: dict) -> float:
    """Median wall seconds of ``fn()`` after a compile/warm-up call."""
    del meta
    jax.block_until_ready(fn())
    ts = []
    for _ in range(3):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts))


def _itemsize(dtype: str) -> int:
    return jnp.dtype(dtype).itemsize


def _block_vmem_bytes(bn: int, bk: int, d: int, k: int, dtype: str) -> int:
    """Upper bound on the live VMEM panels of the resident-k kernels at
    (bn, bk): the fused kernel's x/centers/distance/one-hot/accumulator
    set dominates min_dist's, so one bound serves the shared table."""
    kp = -(-k // 128) * 128
    isz = _itemsize(dtype)
    return (bn * d * isz                 # x panel
            + kp * d * 4                 # resident centers (widened)
            + 2 * bn * kp * 4            # distance + one-hot panels
            + kp * d * 4 + kp * 4)       # (kp, d) sums + (kp,) counts


def _chunk_vmem_bytes(bn: int, kc: int, d: int, k: int, dtype: str) -> int:
    kp = -(-k // kc) * kc
    isz = _itemsize(dtype)
    return (bn * d * isz + kc * d * 4 + 2 * bn * kc * 4
            + kp * d * 4 + kp * 4)       # walk-resident accumulators


def _sweep_block_bucket(d: int, k: int, dtype: str, n: int,
                        timer: Timer) -> Optional[dict]:
    """Best (bn, bk) for one (d, k, dtype) bucket, or None if every
    candidate was VMEM-infeasible (analytic fallback covers it)."""
    from repro.kernels.fused_lloyd import fused_assign_reduce_pallas
    from repro.kernels.min_dist import min_dist_pallas

    rng = np.random.default_rng(d + k)
    x = jnp.asarray(rng.normal(size=(n, d)), jnp.dtype(dtype))
    c = jnp.asarray(rng.normal(size=(k, d)), jnp.dtype(dtype))
    w = jnp.ones((n,), jnp.float32)
    interpret = not ops.on_tpu()

    best = None
    for bn in CANDIDATE_BN:
        for bk in CANDIDATE_BK:
            if _block_vmem_bytes(bn, bk, d, k, dtype) > VMEM_CANDIDATE_BUDGET:
                continue
            meta = dict(kind="block", d=d, k=k, dtype=dtype, bn=bn, bk=bk)
            # the shared table serves both grid structures: score a
            # candidate by the two kernels that consume its sizes —
            # min_dist uses (bn, bk), the fused sweep uses bn
            t = timer(lambda: min_dist_pallas(
                x, c, interpret=interpret, bn=bn, bk=bk), meta)
            t += timer(lambda: fused_assign_reduce_pallas(
                x, w, c, interpret=interpret, bn=bn), meta)
            if best is None or t < best["s"]:
                best = {"bn": bn, "bk": bk, "s": t}
    return best


def _sweep_chunk_bucket(d: int, dtype: str, n: int,
                        timer: Timer) -> Optional[dict]:
    """Best (bn, k_chunk) for the chunked-K fused kernels at feature
    dim bucket ``d``."""
    from repro.kernels.fused_lloyd import fused_assign_reduce_chunked_pallas

    k = CHUNK_SWEEP_K
    rng = np.random.default_rng(d)
    x = jnp.asarray(rng.normal(size=(n, d)), jnp.dtype(dtype))
    c = jnp.asarray(rng.normal(size=(k, d)), jnp.dtype(dtype))
    w = jnp.ones((n,), jnp.float32)
    interpret = not ops.on_tpu()

    best = None
    for bn in CANDIDATE_CHUNK_BN:
        for kc in CANDIDATE_K_CHUNK:
            if _chunk_vmem_bytes(bn, kc, d, k, dtype) > VMEM_CANDIDATE_BUDGET:
                continue
            meta = dict(kind="chunk", d=d, k=k, dtype=dtype, bn=bn, bk=kc)
            t = timer(lambda: fused_assign_reduce_chunked_pallas(
                x, w, c, interpret=interpret, bn=bn, k_chunk=kc), meta)
            if best is None or t < best["s"]:
                best = {"bn": bn, "bk": kc, "s": t}
    return best


def sweep(d_buckets: Sequence[int] = tuning._D_BUCKETS,
          k_buckets: Sequence[int] = tuning._K_BUCKETS,
          dtypes: Iterable[str] = ("float32",),
          n: int = 65536, quick: bool = False,
          timer: Optional[Timer] = None,
          verbose: bool = False) -> dict:
    """Run the measured sweep; returns the table payload (not persisted).

    ``timer(fn, meta) -> seconds`` is injectable so tests can drive the
    selection deterministically; the default times real calls.
    """
    timer = timer or _default_timer
    if quick:
        n = min(n, 2048)
    entries: Dict[str, dict] = {}
    prev = tuning._SWEEP_ACTIVE
    tuning._SWEEP_ACTIVE = True       # candidates must not read the table
    try:
        for dtype in dtypes:
            for d in d_buckets:
                for k in k_buckets:
                    best = _sweep_block_bucket(d, k, dtype, n, timer)
                    if best is None:
                        continue
                    key = tuning.measured_key("block", d, k, dtype)
                    entries[key] = {"bn": best["bn"], "bk": best["bk"],
                                    "us": best["s"] * 1e6}
                    if verbose:
                        print(f"{key}: bn={best['bn']} bk={best['bk']} "
                              f"({best['s'] * 1e6:.0f} us)", flush=True)
                best = _sweep_chunk_bucket(d, dtype, n, timer)
                if best is None:
                    continue
                key = tuning.measured_key("chunk", d, 0, dtype)
                entries[key] = {"bn": best["bn"], "bk": best["bk"],
                                "us": best["s"] * 1e6}
                if verbose:
                    print(f"{key}: bn={best['bn']} k_chunk={best['bk']} "
                          f"({best['s'] * 1e6:.0f} us)", flush=True)
    finally:
        tuning._SWEEP_ACTIVE = prev
    return {"backend": jax.default_backend(), "n": n, "quick": quick,
            "entries": entries}


def save_table(payload: dict, path) -> pathlib.Path:
    path = pathlib.Path(path)
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(json.dumps(payload, indent=1, sort_keys=True))
    tuning.invalidate_measured_cache()
    return path


_ENSURED = set()


def ensure_tuned(backend: str) -> None:
    """REPRO_AUTOTUNE=force miss handler: quick-sweep this backend once
    per process and cache the winners as the local override."""
    if backend in _ENSURED:
        return
    _ENSURED.add(backend)
    save_table(sweep(quick=True), tuning.cache_table_path(backend))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="measure best kernel block sizes on this hardware")
    ap.add_argument("--quick", action="store_true",
                    help="small-n sweep (seconds instead of minutes)")
    ap.add_argument("--n", type=int, default=65536,
                    help="points per timed call (full sweep)")
    ap.add_argument("--dtypes", default="float32",
                    help="comma-separated point dtypes to tune for")
    ap.add_argument("--package", action="store_true",
                    help="write the committed package table "
                         "(kernels/tuned/<backend>.json) instead of the "
                         "user cache")
    ap.add_argument("--out", default=None,
                    help="explicit output path (overrides --package)")
    args = ap.parse_args(argv)

    backend = jax.default_backend()
    if backend != "tpu":
        print(f"# backend={backend}: timings tune the interpret-mode "
              f"conformance path, not TPU performance", flush=True)
    payload = sweep(dtypes=tuple(args.dtypes.split(",")), n=args.n,
                    quick=args.quick, verbose=True)
    out = (pathlib.Path(args.out) if args.out
           else tuning.package_table_path(backend) if args.package
           else tuning.cache_table_path(backend))
    path = save_table(payload, out)
    print(f"# wrote {len(payload['entries'])} entries -> {path}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
