"""Measured-autotuning layer: deterministic sweep, cache round-trip,
mode gating, and clamp_bn tile normalization.

The sweep is driven by an injected fake timer (no wall-clock, no real
kernel calls), so these tests are CI-deterministic: the timer prefers a
known candidate and the assertions check that exactly that candidate
comes back out of ``tuning.block_sizes`` after the JSON round-trip.
"""
import json

import pytest

from repro.kernels import autotune, tuning

# The candidate the fake timer makes the winner.
WANT_BN, WANT_BK = 256, 128
WANT_CHUNK_BN, WANT_KC = 256, 512


def fake_timer(fn, meta):
    """Deterministic 'measurement': the wanted candidate wins, everything
    else ties at a higher time. Never calls fn."""
    del fn
    if meta["kind"] == "block":
        return 0.001 if (meta["bn"], meta["bk"]) == (WANT_BN, WANT_BK) \
            else 0.002
    return 0.001 if (meta["bn"], meta["bk"]) == (WANT_CHUNK_BN, WANT_KC) \
        else 0.002


@pytest.fixture
def tuned_env(tmp_path, monkeypatch):
    """Isolated cache dir + cached mode + clean in-process table cache.
    Also points the package table at an empty tmp location so the
    committed kernels/tuned/<backend>.json cannot leak into assertions
    about analytic fallbacks."""
    monkeypatch.setenv("REPRO_CACHE_DIR", str(tmp_path))
    monkeypatch.setenv("REPRO_AUTOTUNE", "cached")
    monkeypatch.setattr(tuning, "package_table_path",
                        lambda b: tmp_path / f"pkg_{b}.json")
    tuning.invalidate_measured_cache()
    yield tmp_path
    tuning.invalidate_measured_cache()


def test_sweep_cache_roundtrip(tuned_env):
    """sweep -> save_table -> block_sizes/chunk_sizes returns the
    measured winners; analytic model covers the unmeasured buckets."""
    import jax
    backend = jax.default_backend()

    payload = autotune.sweep(d_buckets=(128,), k_buckets=(128,),
                             dtypes=("float32",), n=256, quick=True,
                             timer=fake_timer)
    assert payload["backend"] == backend
    key = tuning.measured_key("block", 64, 100, "float32")
    assert payload["entries"][key] == {
        "bn": WANT_BN, "bk": WANT_BK,
        "us": pytest.approx(0.001 * 1e6 * 2)}  # two kernels scored

    autotune.save_table(payload, tuning.cache_table_path(backend))
    # measured bucket: the fake winner comes back out
    assert tuning.block_sizes(64, 100) == (WANT_BN, WANT_BK)
    assert tuning.chunk_sizes(100) == (WANT_CHUNK_BN, WANT_KC)
    # unmeasured bucket (d=512 not swept): analytic fallback
    assert tuning.block_sizes(512, 100) == tuning._TABLE[(512, 128)]


def test_autotune_off_ignores_table(tuned_env, monkeypatch):
    import jax
    payload = autotune.sweep(d_buckets=(128,), k_buckets=(128,),
                             dtypes=("float32",), n=256, quick=True,
                             timer=fake_timer)
    autotune.save_table(payload, tuning.cache_table_path(
        jax.default_backend()))
    monkeypatch.setenv("REPRO_AUTOTUNE", "off")
    assert tuning.block_sizes(64, 100) == tuning._TABLE[(128, 128)]
    monkeypatch.setenv("REPRO_AUTOTUNE", "bogus")
    with pytest.raises(ValueError, match="REPRO_AUTOTUNE"):
        tuning.block_sizes(64, 100)


def test_user_cache_overrides_package_table(tuned_env, monkeypatch):
    """The local override table must shadow the committed package table."""
    import jax
    backend = jax.default_backend()
    key = tuning.measured_key("block", 64, 64, "float32")
    pkg = {"backend": backend,
           "entries": {key: {"bn": 512, "bk": 256, "us": 1.0}}}
    usr = {"backend": backend,
           "entries": {key: {"bn": 256, "bk": 128, "us": 1.0}}}
    monkeypatch.setattr(tuning, "package_table_path",
                        lambda b: tuned_env / f"pkg_{b}.json")
    tuning.package_table_path(backend).write_text(json.dumps(pkg))
    tuning.cache_table_path(backend).write_text(json.dumps(usr))
    tuning.invalidate_measured_cache()
    assert tuning.block_sizes(64, 64) == (256, 128)


def test_wrong_backend_table_never_consulted(tuned_env):
    """A table measured on another backend must not leak in."""
    import jax
    backend = jax.default_backend()
    other = "tpu" if backend != "tpu" else "cpu"
    key = tuning.measured_key("block", 64, 64, "float32")
    tuning.cache_table_path(backend).write_text(json.dumps(
        {"backend": other, "entries": {key: {"bn": 896, "bk": 256}}}))
    tuning.invalidate_measured_cache()
    assert tuning.block_sizes(64, 64) == tuning._TABLE[(128, 128)]


def test_measured_sizes_tile_normalized(tuned_env):
    """A hand-edited table with non-tile sizes is re-normalized through
    the same rounding clamp_bn applies (never hands out a bad panel)."""
    import jax
    backend = jax.default_backend()
    key = tuning.measured_key("block", 64, 64, "float32")
    tuning.cache_table_path(backend).write_text(json.dumps(
        {"backend": backend,
         "entries": {key: {"bn": 300, "bk": 130, "us": 1.0}}}))
    tuning.invalidate_measured_cache()
    bn, bk = tuning.block_sizes(64, 64)
    assert bn == 256 and bk == 128                 # floored to the tile
    assert tuning.clamp_bn(bn, 10**9) == bn        # round-trips unchanged


def test_vmem_feasibility_filter():
    """Candidates whose panels blow the VMEM budget are skipped; a bucket
    where everything is infeasible yields no entry (analytic fallback)."""
    assert autotune._block_vmem_bytes(1024, 256, 512, 1024, "float32") \
        > autotune.VMEM_CANDIDATE_BUDGET
    payload = autotune.sweep(d_buckets=(512,), k_buckets=(1024,),
                             dtypes=("float32",), n=256, quick=True,
                             timer=lambda fn, meta: 1.0)
    key = tuning.measured_key("block", 512, 1024, "float32")
    if key in payload["entries"]:       # whatever survived must be feasible
        e = payload["entries"][key]
        assert autotune._block_vmem_bytes(
            e["bn"], e["bk"], 512, 1024, "float32") \
            <= autotune.VMEM_CANDIDATE_BUDGET


# ---- clamp_bn tiny-n edge cases ---------------------------------------

@pytest.mark.parametrize("bn,n,want", [
    (512, 1, 128),        # tiny n: shrink to the minimum tile
    (512, 128, 128),      # n exactly one tile
    (512, 129, 256),      # n just over one tile: round n UP, not down
    (512, 511, 512),      # n rounds up to bn exactly
    (512, 513, 512),      # bn already <= padded n
    (100, 10**6, 128),    # sub-tile bn request: floor comes up to 128
    (1000, 10**6, 896),   # non-tile bn request: floored to 7*128
    (128, 1, 128),        # smallest legal everything
])
def test_clamp_bn_edges(bn, n, want):
    got = tuning.clamp_bn(bn, n)
    assert got == want
    assert got % 128 == 0
    assert tuning.clamp_bn(got, n) == got          # idempotent


def test_clamp_bn_autotune_candidates_roundtrip():
    """Every candidate the sweep can emit survives clamp_bn unchanged for
    large n (the measured table must never fight the clamp)."""
    for bn in autotune.CANDIDATE_BN + autotune.CANDIDATE_CHUNK_BN:
        assert tuning.clamp_bn(bn, 10**9) == bn
