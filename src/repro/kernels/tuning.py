"""Block-size selection for the clustering kernels: measured, then analytic.

One table serves ``min_dist``, ``fused_assign_reduce``, ``remove_below``
and ``sensitivity_scores`` (and the point-panel size of ``lloyd_reduce``):
all of them stream (bn, d) point panels against a center panel set, so the
right block sizes depend only on (d, k) — and, since the bf16-input
change, on the point dtype (halved panel bytes shift the VMEM sweet
spot). Lookup order per query:

1. **Measured table** — winners of the timed sweep in
   ``repro.kernels.autotune`` (``python -m repro.kernels.autotune``,
   ``make autotune``), persisted per JAX backend as JSON:
   ``<checkout>/.cache/repro/tuned_<backend>.json`` (local override,
   written by the CLI by default; ``REPRO_CACHE_DIR`` moves it) first,
   then the committed package table ``kernels/tuned/<backend>.json``.
   Gated by ``REPRO_AUTOTUNE``:

   * ``cached`` (default) — consult the persisted tables, fall back to
     the analytic model on a miss;
   * ``off``    — analytic model only (the pre-autotune behavior);
   * ``force``  — on a miss, run the quick measured sweep for this
     backend right now, cache it as the local override and use it.

2. **Analytic model** — the static tables below: values chosen so the
   resident f32 panels — x (bn, d), centers (bk, d), the (bn, bk)
   distance panel and, for the fused kernel, the (bk, d) + (bk,)
   accumulators — stay within a ~4 MiB VMEM budget (v5e has 16 MiB less
   double-buffering headroom).

Every size handed out (measured or analytic) round-trips through
``clamp_bn``: multiples of the 128-sublane tile, shrunk toward n so tiny
inputs don't pad to a full panel. Sizes are resolved at kernel *trace*
time — a process that already traced a shape keeps its sizes until the
jit cache is dropped, so regenerate tables before the first kernel call.
"""
from __future__ import annotations

import collections
import json
import os
import pathlib
from typing import Dict, List, Optional, Tuple

from repro.launch.cache import CHECKOUT

# Measured-table lookup outcomes ("measured_hit" / "measured_miss" /
# "analytic_only"), surfaced through the metrics registry as
# ``kernels.tuning.autotune`` (repro.obs.metrics). Counted at kernel
# *trace* time — a warm jit cache adds nothing, which is itself signal.
TUNE_COUNTS = collections.Counter()

# Every table file a lookup actually loaded, in load order — so a run can
# show which committed or local tables its block sizes rest on.
TABLES_READ: List[str] = []

_D_BUCKETS = (128, 256, 512)
_K_BUCKETS = (128, 256, 1024)

# (d_bucket, k_bucket) -> (bn, bk) — the analytic fallback model.
_TABLE = {
    (128, 128):  (1024, 128),
    (128, 256):  (1024, 256),
    (128, 1024): (512, 256),
    (256, 128):  (512, 128),
    (256, 256):  (512, 256),
    (256, 1024): (256, 256),
    (512, 128):  (256, 128),
    (512, 256):  (256, 128),
    (512, 1024): (128, 128),
}

# (d_bucket) -> (bn, k_chunk) for the chunked-K fused kernels: the center
# set does NOT stay resident; k_chunk-row center panels are tiled through
# VMEM with a running (min, argmin) per point panel. The live panels are
# x (bn, d), centers (k_chunk, d), the (bn, k_chunk) distance/one-hot
# panel and the (k_chunk, d) + (k_chunk,) chunk accumulators — sized for
# the same ~4 MiB budget as the resident table above.
_CHUNK_TABLE = {
    128: (512, 1024),
    256: (512, 512),
    512: (256, 512),
}

_MODES = ("off", "cached", "force")

# Set by repro.kernels.autotune while its sweep is running so the candidate
# sizes being timed are never shadowed by a previously persisted table
# (and a `force` miss cannot recurse into another sweep).
_SWEEP_ACTIVE = False

# backend name -> merged measured table ({} = loaded, nothing found).
_MEASURED_CACHE: Dict[str, Dict[str, dict]] = {}


def _tile(v: int) -> int:
    """Round a block size down to the 128-sublane tile (floor, min 128)."""
    return max(128, (int(v) // 128) * 128)


def _bucket(v: int, buckets: Tuple[int, ...]) -> int:
    for b in buckets:
        if v <= b:
            return b
    return buckets[-1]


def autotune_mode() -> str:
    mode = os.environ.get("REPRO_AUTOTUNE", "cached")
    if mode not in _MODES:
        raise ValueError(
            f"unknown REPRO_AUTOTUNE={mode!r}; expected one of {_MODES}")
    return mode


def package_table_path(backend: str) -> pathlib.Path:
    """The committed per-backend tuned table inside the package."""
    return pathlib.Path(__file__).resolve().parent / "tuned" / (
        f"{backend}.json")


def cache_table_path(backend: str) -> pathlib.Path:
    """The local override inside the checkout (written by the autotune
    CLI by default)."""
    root = os.environ.get("REPRO_CACHE_DIR") or CHECKOUT / ".cache" / "repro"
    return pathlib.Path(root) / f"tuned_{backend}.json"


def measured_key(kind: str, d: int, k: int, dtype: str) -> str:
    """Bucketed lookup key: e.g. ``block:128x256:float32``."""
    db = _bucket(d, _D_BUCKETS)
    if kind == "chunk":
        return f"chunk:{db}:{dtype}"
    return f"block:{db}x{_bucket(k, _K_BUCKETS)}:{dtype}"


def invalidate_measured_cache() -> None:
    """Drop the in-process measured-table cache (tests, post-sweep)."""
    _MEASURED_CACHE.clear()


def _load_measured(backend: str) -> Dict[str, dict]:
    if backend in _MEASURED_CACHE:
        return _MEASURED_CACHE[backend]
    table: Dict[str, dict] = {}
    # package table first so the local override wins
    for path in (package_table_path(backend), cache_table_path(backend)):
        try:
            payload = json.loads(path.read_text())
        except (OSError, ValueError):
            continue
        if payload.get("backend", backend) != backend:
            continue
        TABLES_READ.append(str(path))
        table.update(payload.get("entries", {}))
    _MEASURED_CACHE[backend] = table
    return table


def _measured_sizes(kind: str, d: int, k: int,
                    dtype: str) -> Optional[Tuple[int, int]]:
    """Measured (bn, bk|k_chunk) for the bucket, or None (analytic)."""
    if _SWEEP_ACTIVE:
        return None
    mode = autotune_mode()
    if mode == "off":
        TUNE_COUNTS["analytic_only"] += 1
        return None
    import jax
    backend = jax.default_backend()
    entry = _load_measured(backend).get(measured_key(kind, d, k, dtype))
    if entry is None and mode == "force":
        from repro.kernels import autotune
        autotune.ensure_tuned(backend)
        entry = _load_measured(backend).get(measured_key(kind, d, k, dtype))
    if entry is None:
        TUNE_COUNTS["measured_miss"] += 1
        return None
    TUNE_COUNTS["measured_hit"] += 1
    # measured sizes round-trip through the same tile normalization that
    # clamp_bn applies, so a hand-edited or stale table can never hand a
    # kernel a non-tile panel
    return _tile(entry["bn"]), _tile(entry["bk"])


def block_sizes(d: int, k: int, dtype: str = "float32") -> Tuple[int, int]:
    """(bn, bk) point/center panel sizes for feature dim d and k centers."""
    measured = _measured_sizes("block", d, k, dtype)
    if measured is not None:
        return measured
    return _TABLE[(_bucket(d, _D_BUCKETS), _bucket(k, _K_BUCKETS))]


def chunk_sizes(d: int, dtype: str = "float32") -> Tuple[int, int]:
    """(bn, k_chunk) panel sizes for the chunked-K (k > resident-VMEM)
    variants of the fused kernels; keyed by feature dim only because the
    chunk width replaces k as the free center-axis parameter."""
    measured = _measured_sizes("chunk", d, 0, dtype)
    if measured is not None:
        return measured
    return _CHUNK_TABLE[_bucket(d, _D_BUCKETS)]


def clamp_bn(bn: int, n: int) -> int:
    """Normalize bn to the 128-sublane tile (rounding down, min 128) and
    shrink it toward n (rounded up to the tile) so tiny inputs don't pad
    to a full panel. Idempotent: every emitted size round-trips."""
    return min(_tile(bn), max(128, -(-n // 128) * 128))
