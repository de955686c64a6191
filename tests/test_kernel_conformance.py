"""Kernel conformance harness: every kernels/ops.py entry point, ref vs env.

"The oracle and the kernels agree" is enforced here rather than claimed in
docstrings. Each test calls the public ``ops.*`` entry point with NO
explicit backend — the ``REPRO_KERNEL_BACKEND`` env var decides what runs —
and checks the result against the jnp oracle in ``kernels/ref.py`` (the
semantics of record). ``make test-kernels`` executes this file twice:

  REPRO_KERNEL_BACKEND=ref     — self-consistency of the dispatch plumbing
  REPRO_KERNEL_BACKEND=pallas  — the Pallas kernels (interpret mode on CPU:
                                 the exact BlockSpec tiling/grid logic of
                                 the TPU path), including the chunked-K
                                 variants (k > _MAX_PALLAS_K) and the
                                 reduced-precision inputs (bf16/f16, every
                                 UPLINK_DTYPES member) with f32 accumulators

The shape grid sits at and just over every dispatch/fallback boundary
(``_MAX_PALLAS_D``, ``_MAX_PALLAS_K``, the 128-row point block), and the
degenerate tests cover k = 1, all-invalid center masks, all-zero weights
and n smaller than one block. New ops.py entry points must be added to
the coverage map at the bottom — ``test_every_entry_point_covered`` fails
otherwise.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

# (name, n, d, k) — boundaries annotated against the ops.py guards
POINT_SHAPES = [
    ("tiny_subblock", 7, 3, 1),           # k = 1, n < one 128-row block
    ("small_unaligned", 100, 8, 5),
    ("n_at_block", 128, 16, 32),
    ("n_over_block", 129, 16, 32),
    ("k_over_panel", 200, 37, 130),       # k just over one 128 center panel
    ("d_at_max", 48, 512, 6),             # d == _MAX_PALLAS_D
    ("d_over_max", 48, 513, 6),           # d > _MAX_PALLAS_D -> oracle path
    ("k_at_max", 72, 9, 1024),            # k == _MAX_PALLAS_K (resident)
    ("k_over_max", 72, 9, 1025),          # k > _MAX_PALLAS_K -> chunked
    ("k_chunked_multi", 64, 33, 2100),    # several center chunks
]
IDS = [s[0] for s in POINT_SHAPES]

MP_SHAPES = [
    ("tiny", 2, 40, 7, 5),
    ("n_over_block", 3, 129, 16, 33),
    ("k_chunked", 2, 50, 9, 1300),
    ("d_fallback", 1, 40, 513, 5),
]
MP_IDS = [s[0] for s in MP_SHAPES]

# every precision UPLINK_DTYPES advertises must be gated here: payloads
# reach the kernels un-widened since the bf16-uplink change
DTYPES = [jnp.float32, jnp.bfloat16, jnp.float16]


def _tols(dtype):
    """(loose, tight) tolerances: reduced-precision inputs keep f32
    accumulators, but the rounded inputs amplify the expanded-form
    distance differently between the matmul orders of the two backends."""
    return (2e-3, 1e-4) if dtype == jnp.float32 else (5e-2, 1e-4)


def _data(n, d, k, dtype, seed):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(n, d)), dtype)
    w = jnp.asarray(rng.random(n), jnp.float32)
    w = w.at[: n // 5].set(0.0)                     # some padding rows
    c = jnp.asarray(rng.normal(size=(k, d)), dtype)
    valid = jnp.asarray(rng.random(k) > 0.3).at[0].set(True)
    return x, w, c, valid


@pytest.mark.parametrize("name,n,d,k", POINT_SHAPES, ids=IDS)
@pytest.mark.parametrize("dtype", DTYPES, ids=["f32", "bf16", "f16"])
def test_min_dist_conforms(name, n, d, k, dtype):
    x, _, c, valid = _data(n, d, k, dtype, seed=n + d + k)
    tol, _ = _tols(dtype)
    for cv in (None, valid):
        d2_r, _ = ref.min_dist_ref(x, c, cv)
        d2_o, idx_o = ops.min_dist(x, c, cv)
        np.testing.assert_allclose(d2_o, d2_r, rtol=tol, atol=tol)
        # argmin ties may break differently; the chosen center must be
        # valid and realize the reported distance
        if cv is not None:
            assert bool(jnp.all(valid[idx_o]))
        d2_at = jnp.sum((x.astype(jnp.float32)
                         - c.astype(jnp.float32)[idx_o]) ** 2, -1)
        np.testing.assert_allclose(d2_at, d2_r, rtol=tol, atol=tol)


@pytest.mark.parametrize("name,n,d,k", POINT_SHAPES, ids=IDS)
@pytest.mark.parametrize("dtype", DTYPES, ids=["f32", "bf16", "f16"])
def test_lloyd_reduce_conforms(name, n, d, k, dtype):
    x, w, _, _ = _data(n, d, k, dtype, seed=2 * n + d + k)
    rng = np.random.default_rng(k)
    assign = jnp.asarray(rng.integers(0, k, n), jnp.int32)
    tol, tight = _tols(dtype)
    s_r, c_r = ref.lloyd_reduce_ref(x, w, assign, k)
    s_o, c_o = ops.lloyd_reduce(x, w, assign, k)
    np.testing.assert_allclose(s_o, s_r, rtol=tol, atol=tol)
    np.testing.assert_allclose(c_o, c_r, rtol=tight, atol=tight)


@pytest.mark.parametrize("name,n,d,k", POINT_SHAPES, ids=IDS)
@pytest.mark.parametrize("dtype", DTYPES, ids=["f32", "bf16", "f16"])
def test_fused_assign_reduce_conforms(name, n, d, k, dtype):
    x, w, c, valid = _data(n, d, k, dtype, seed=3 * n + d + k)
    tol, tight = _tols(dtype)
    for cv in (None, valid):
        s_r, c_r, cost_r = ref.fused_assign_reduce_ref(x, w, c, cv)
        s_o, c_o, cost_o = ops.fused_assign_reduce(x, w, c, cv)
        np.testing.assert_allclose(s_o, s_r, rtol=tol, atol=tol)
        np.testing.assert_allclose(c_o, c_r, rtol=tight, atol=tight)
        np.testing.assert_allclose(cost_o, cost_r, rtol=tol, atol=tol)
        if cv is not None:                # invalid centers receive no mass
            assert float(jnp.sum(jnp.where(cv, 0.0, c_o))) == 0.0


@pytest.mark.parametrize("name,m,p,d,k", MP_SHAPES, ids=MP_IDS)
@pytest.mark.parametrize("dtype", DTYPES, ids=["f32", "bf16", "f16"])
def test_remove_below_conforms(name, m, p, d, k, dtype):
    rng = np.random.default_rng(m + p + d + k)
    x = jnp.asarray(rng.normal(size=(m, p, d)), dtype)
    c = jnp.asarray(rng.normal(size=(k, d)), dtype)
    alive = jnp.asarray(rng.random((m, p)) > 0.25)
    d2, _ = ref.min_dist_ref(x.reshape(m * p, d), c)
    # thresholds strictly between data d2 values: the backends sum the
    # distance terms in different orders, so a v equal to a point's exact
    # d2 could flip its keep bit by one ulp
    d2s = jnp.sort(d2)
    mid = 0.5 * (d2s[m * p // 2] + d2s[m * p // 2 + 1])
    for v in [jnp.float32(0.0), mid, jnp.max(d2) + 1.0]:
        a_r, l_r = ref.remove_below_ref(x, c, alive, v)
        a_o, l_o = ops.remove_below(x, c, alive, v)
        np.testing.assert_array_equal(np.asarray(a_o), np.asarray(a_r))
        np.testing.assert_array_equal(np.asarray(l_o), np.asarray(l_r))


@pytest.mark.parametrize("name,n,d,k", POINT_SHAPES, ids=IDS)
@pytest.mark.parametrize("dtype", DTYPES, ids=["f32", "bf16", "f16"])
def test_update_min_dist_conforms(name, n, d, k, dtype):
    kc = min(k, 37)                       # the new-center block is small
    x, w, c, valid = _data(n, d, kc, dtype, seed=4 * n + d + k)
    rng = np.random.default_rng(5 * n + d)
    d2 = jnp.asarray(rng.random(n) * float(d), jnp.float32)
    tol, tight = _tols(dtype)
    for cv in (None, valid[:kc]):
        d2_r, m_r = ref.update_min_dist_ref(x, w, c, d2, cv)
        d2_o, m_o = ops.update_min_dist(x, w, c, d2, cv)
        np.testing.assert_allclose(d2_o, d2_r, rtol=tol, atol=tol)
        np.testing.assert_allclose(m_o, m_r, rtol=tol)
        # monotone: the update never raises the running min-d2
        assert bool(jnp.all(d2_o <= d2 + 1e-6))


@pytest.mark.parametrize("name,n,d,k", POINT_SHAPES, ids=IDS)
@pytest.mark.parametrize("dtype", DTYPES, ids=["f32", "bf16", "f16"])
def test_sensitivity_scores_conforms(name, n, d, k, dtype):
    """Coreset sensitivity pass: scores/mass/cost against the oracle over
    the full boundary grid (k > _MAX_PALLAS_K dispatches to the tiled
    min_dist sweep + XLA tail, d > _MAX_PALLAS_D to the oracle)."""
    x, w, c, valid = _data(n, d, k, dtype, seed=6 * n + d + k)
    tol, tight = _tols(dtype)
    for cv in (None, valid):
        s_r, _, m_r, cost_r = ref.sensitivity_scores_ref(x, w, c, cv)
        s_o, a_o, m_o, cost_o = ops.sensitivity_scores(x, w, c, cv)
        np.testing.assert_allclose(s_o, s_r, rtol=tol, atol=tol)
        np.testing.assert_allclose(m_o, m_r, rtol=tight, atol=tight)
        np.testing.assert_allclose(cost_o, cost_r, rtol=tol, atol=tol)
        # mass conservation: every unit of weight lands on some center
        np.testing.assert_allclose(jnp.sum(m_o), jnp.sum(w),
                                   rtol=tol, atol=tol)
        # argmin ties may break differently; the chosen center must be
        # valid and realize the reported score
        if cv is not None:
            assert bool(jnp.all(valid[a_o]))
        d2_at = jnp.sum((x.astype(jnp.float32)
                         - c.astype(jnp.float32)[a_o]) ** 2, -1)
        np.testing.assert_allclose(np.asarray(w) * np.asarray(d2_at), s_r,
                                   rtol=tol, atol=tol)


@pytest.mark.parametrize("name,n,d,k", POINT_SHAPES, ids=IDS)
@pytest.mark.parametrize("dtype", DTYPES, ids=["f32", "bf16", "f16"])
def test_truncated_cost_conforms(name, n, d, k, dtype):
    """Robust-tier truncated-cost split against the oracle over the full
    boundary grid. Thresholds sit strictly between sorted d2 values (the
    backends sum distance terms in different orders, so a v equal to a
    point's exact d2 could flip its side by one ulp) plus the two
    degenerate extremes (everything tail / everything kept)."""
    x, w, c, valid = _data(n, d, k, dtype, seed=7 * n + d + k)
    tol, _ = _tols(dtype)
    for cv in (None, valid):
        d2, _ = ref.min_dist_ref(x, c, cv)
        d2s = jnp.sort(d2)
        mid = 0.5 * (d2s[n // 2] + d2s[min(n // 2 + 1, n - 1)] + 1e-6)
        for v in [jnp.float32(-1.0), mid, jnp.max(d2) * 1.01 + 1.0]:
            kc_r, tm_r, tc_r = ref.truncated_cost_ref(x, w, c, v, cv)
            kc_o, tm_o, tc_o = ops.truncated_cost(x, w, c, v, cv)
            np.testing.assert_allclose(kc_o, kc_r, rtol=tol, atol=tol)
            np.testing.assert_allclose(tm_o, tm_r, rtol=tol, atol=tol)
            np.testing.assert_allclose(tc_o, tc_r, rtol=tol, atol=tol)
        # conservation at any v: kept + tail cost == total weighted cost
        total = jnp.sum(jnp.where(w > 0, w * d2, 0.0))
        kc_o, _, tc_o = ops.truncated_cost(x, w, c, mid, cv)
        np.testing.assert_allclose(kc_o + tc_o, total, rtol=tol, atol=tol)


def test_update_min_dist_large_block():
    """A new-center block over _MAX_PALLAS_K (k-means‖ seeding's ~6·k-row
    candidate buffer at large k_plus) runs as sliced resident sweeps on
    the Pallas backend — min is associative, so it must match the
    one-shot oracle exactly to tolerance, mass included."""
    x, w, c, valid = _data(40, 5, ops._MAX_PALLAS_K + 8, jnp.float32,
                           seed=0)
    d2 = jnp.full((40,), 1e6, jnp.float32)
    for cv in (None, valid):
        d2_r, m_r = ref.update_min_dist_ref(x, w, c, d2, cv)
        d2_o, m_o = ops.update_min_dist(x, w, c, d2, cv)
        np.testing.assert_allclose(d2_o, d2_r, rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(m_o, m_r, rtol=1e-4)


# ---- degenerate cases --------------------------------------------------

# one resident-k and one chunked-k instance each
DEGENERATE_SHAPES = [("resident", 90, 11, 40), ("chunked", 90, 11, 1300)]
DEG_IDS = [s[0] for s in DEGENERATE_SHAPES]


@pytest.mark.parametrize("name,n,d,k", DEGENERATE_SHAPES, ids=DEG_IDS)
def test_all_invalid_centers(name, n, d, k):
    """Zero valid centers: distances are 'effectively infinite' (>= the
    backend sentinel), removal keeps the mask, the seeding update is a
    no-op. Assignments are meaningless and deliberately unchecked."""
    x, w, c, _ = _data(n, d, k, jnp.float32, seed=7)
    none_valid = jnp.zeros((k,), bool)

    d2_o, _ = ops.min_dist(x, c, none_valid)
    assert bool(jnp.all(d2_o >= 1e37))

    _, counts, _ = ops.fused_assign_reduce(x, w, c, none_valid)
    # every point is still counted somewhere (padding semantics) but no
    # VALID center may receive mass — there are none, so total mass
    # equals the total weight wherever it landed
    np.testing.assert_allclose(jnp.sum(counts), jnp.sum(w), rtol=1e-5)

    xm = x.reshape(2, n // 2, d)
    alive = jnp.asarray(np.random.default_rng(8).random((2, n // 2)) > 0.4)
    a_o, l_o = ops.remove_below(xm, c, alive, jnp.float32(1e6), none_valid)
    np.testing.assert_array_equal(np.asarray(a_o), np.asarray(alive))
    np.testing.assert_array_equal(np.asarray(l_o),
                                  np.asarray(jnp.sum(alive, axis=1)))

    d2 = jnp.asarray(np.random.default_rng(9).random(n), jnp.float32)
    d2_o, mass_o = ops.update_min_dist(x, w, c[:5], d2,
                                       jnp.zeros((5,), bool))
    np.testing.assert_array_equal(np.asarray(d2_o), np.asarray(d2))
    np.testing.assert_allclose(mass_o, jnp.sum(w * d2), rtol=1e-5)


@pytest.mark.parametrize("name,n,d,k", DEGENERATE_SHAPES, ids=DEG_IDS)
def test_all_zero_weights(name, n, d, k):
    """All-zero weights: reductions and masses are exactly zero."""
    x, _, c, _ = _data(n, d, k, jnp.float32, seed=10)
    w0 = jnp.zeros((n,), jnp.float32)
    sums, counts, cost = ops.fused_assign_reduce(x, w0, c)
    assert float(jnp.max(jnp.abs(sums))) == 0.0
    assert float(jnp.max(jnp.abs(counts))) == 0.0
    assert float(cost) == 0.0
    rng = np.random.default_rng(11)
    assign = jnp.asarray(rng.integers(0, k, n), jnp.int32)
    s, cnt = ops.lloyd_reduce(x, w0, assign, k)
    assert float(jnp.max(jnp.abs(s))) == 0.0 and float(jnp.max(cnt)) == 0.0
    d2 = jnp.asarray(rng.random(n), jnp.float32)
    _, mass = ops.update_min_dist(x, w0, c[:3], d2)
    assert float(mass) == 0.0
    scores, _, smass, cost = ops.sensitivity_scores(x, w0, c)
    assert float(jnp.max(jnp.abs(scores))) == 0.0
    assert float(jnp.max(jnp.abs(smass))) == 0.0
    assert float(cost) == 0.0
    kc, tm, tc = ops.truncated_cost(x, w0, c, jnp.float32(1.0))
    assert float(kc) == 0.0 and float(tm) == 0.0 and float(tc) == 0.0


# ---- multi-panel walks / single-walk variants --------------------------

# (name, n, d, k): walks over several point panels (bn=128 forced ->
# ceil(n/128) panels), so the per-panel row blocks and the walk-resident
# accumulators are exercised across panel boundaries
MULTI_PANEL_SHAPES = [
    ("multi_panel", 2600, 16, 32),        # 21 panels, odd tail
    ("two_panels", 256, 8, 5),            # exactly 2 panels
    ("one_panel", 100, 8, 5),             # degenerate: one padded panel
]
PANEL_IDS = [s[0] for s in MULTI_PANEL_SHAPES]


@pytest.mark.parametrize("name,n,d,k", MULTI_PANEL_SHAPES, ids=PANEL_IDS)
@pytest.mark.parametrize("dtype", DTYPES, ids=["f32", "bf16", "f16"])
def test_fused_assign_reduce_multi_panel_conforms(name, n, d, k, dtype):
    """The resident-center Lloyd sweep against the oracle over a walk of
    many 128-point panels (the accumulators persist across the grid)."""
    from repro.kernels.fused_lloyd import fused_assign_reduce_pallas
    x, w, c, valid = _data(n, d, k, dtype, seed=8 * n + d + k)
    tol, tight = _tols(dtype)
    for cv in (None, valid):
        s_r, c_r, cost_r = ref.fused_assign_reduce_ref(x, w, c, cv)
        s_o, c_o, cost_o = fused_assign_reduce_pallas(
            x, w, c, cv, interpret=True, bn=128)
        np.testing.assert_allclose(s_o, s_r, rtol=tol, atol=tol)
        np.testing.assert_allclose(c_o, c_r, rtol=tight, atol=tight)
        np.testing.assert_allclose(cost_o, cost_r, rtol=tol, atol=tol)


@pytest.mark.parametrize("name,n,d,k", MULTI_PANEL_SHAPES, ids=PANEL_IDS)
@pytest.mark.parametrize("dtype", DTYPES, ids=["f32", "bf16", "f16"])
def test_update_min_dist_multi_panel_conforms(name, n, d, k, dtype):
    """The seeding sweep writes a (1, n) min-d2 row panel by panel; every
    panel's slice of the output must line up with the oracle's."""
    from repro.kernels.fused_lloyd import update_min_dist_pallas
    kc = min(k, 37)
    x, w, c, valid = _data(n, d, kc, dtype, seed=9 * n + d + k)
    rng = np.random.default_rng(n + d)
    d2 = jnp.asarray(rng.random(n) * float(d), jnp.float32)
    tol, _ = _tols(dtype)
    for cv in (None, valid[:kc]):
        d2_r, m_r = ref.update_min_dist_ref(x, w, c, d2, cv)
        d2_o, m_o = update_min_dist_pallas(
            x, w, c, d2, cv, interpret=True, bn=128)
        np.testing.assert_allclose(d2_o, d2_r, rtol=tol, atol=tol)
        np.testing.assert_allclose(m_o, m_r, rtol=tol)


def test_multi_panel_removal_matches_ref():
    """The removal sweep over several machines AND several panels each:
    the (m, 1, p) mask rows and (m, 1, 1) live counts index by machine."""
    from repro.kernels.fused_lloyd import remove_below_pallas
    rng = np.random.default_rng(12)
    x = jnp.asarray(rng.normal(size=(3, 700, 8)), jnp.float32)
    c = jnp.asarray(rng.normal(size=(5, 8)), jnp.float32)
    alive = jnp.asarray(rng.random((3, 700)) > 0.2)
    d2, _ = ref.min_dist_ref(x.reshape(-1, 8), c)
    v = jnp.median(d2) + 1e-4
    a_r, l_r = ref.remove_below_ref(x, c, alive, v)
    a_o, l_o = remove_below_pallas(x, c, alive, v, interpret=True, bn=128)
    np.testing.assert_array_equal(np.asarray(a_o), np.asarray(a_r))
    np.testing.assert_array_equal(np.asarray(l_o), np.asarray(l_r))


@pytest.mark.parametrize("dtype", DTYPES, ids=["f32", "bf16", "f16"])
def test_chunked_single_walk_and_fallback_agree(dtype):
    """The single-walk chunked-K kernel (walk-resident accumulators,
    per-chunk scatter at the last chunk) and the legacy two-walk fallback
    (forced via a zero accumulator budget) must both match the oracle —
    the byte model in bench_kernels assumes the one-walk path."""
    from repro.kernels.fused_lloyd import fused_assign_reduce_chunked_pallas
    x, w, c, valid = _data(300, 10, 1500, dtype, seed=14)
    tol, tight = _tols(dtype)
    for cv in (None, valid):
        s_r, c_r, cost_r = ref.fused_assign_reduce_ref(x, w, c, cv)
        for budget in (None, 1):          # default one-walk, forced two-walk
            kw = {} if budget is None else {"acc_budget": budget}
            s_o, c_o, cost_o = fused_assign_reduce_chunked_pallas(
                x, w, c, cv, interpret=True, **kw)
            np.testing.assert_allclose(s_o, s_r, rtol=tol, atol=tol)
            np.testing.assert_allclose(c_o, c_r, rtol=tight, atol=tight)
            np.testing.assert_allclose(cost_o, cost_r, rtol=tol, atol=tol)


def test_scanned_seeding_conforms():
    """The lax.scan D²-seeding path through whichever backend the env
    selects (make test-kernels runs this under ref AND pallas): every
    center is a data row, the seeding is deterministic per key, and the
    scan traces its step body a constant number of times regardless of k
    (the compile-once contract of the seeding rewrite)."""
    import jax
    from repro.core import kmeans

    rng = np.random.default_rng(15)
    x = jnp.asarray(rng.normal(size=(500, 6)), jnp.float32)
    w = jnp.ones((500,), jnp.float32)

    base = dict(kmeans.TRACE_COUNTS)
    c1 = kmeans.kmeans_plusplus(jax.random.PRNGKey(0), x, w, 5)
    t_small = kmeans.TRACE_COUNTS["kmeans_plusplus_step"] - base.get(
        "kmeans_plusplus_step", 0)

    # each chosen center must be an actual data row
    d2 = np.min(np.sum((np.asarray(c1)[:, None, :]
                        - np.asarray(x)[None]) ** 2, -1), axis=1)
    np.testing.assert_allclose(d2, 0.0, atol=1e-8)

    # determinism per key
    c2 = kmeans.kmeans_plusplus(jax.random.PRNGKey(0), x, w, 5)
    np.testing.assert_array_equal(np.asarray(c1), np.asarray(c2))

    # trace count does not grow with k (fresh k -> fresh trace, but the
    # scan body is traced the same constant number of times)
    base = dict(kmeans.TRACE_COUNTS)
    kmeans.kmeans_plusplus(jax.random.PRNGKey(1), x, w, 11)
    t_large = kmeans.TRACE_COUNTS["kmeans_plusplus_step"] - base.get(
        "kmeans_plusplus_step", 0)
    assert t_large == t_small <= 3


def test_every_entry_point_covered():
    """Adding an ops.py entry point without conformance coverage fails
    here — extend the grid above and this set together. The public
    surface is INTROSPECTED (callables defined in ops taking backend=),
    so forgetting to update ops.ENTRY_POINTS also fails."""
    import inspect
    public = {name for name, fn in vars(ops).items()
              if callable(fn) and not name.startswith("_")
              and getattr(fn, "__module__", "") == ops.__name__
              and "backend" in inspect.signature(fn).parameters}
    covered = {"min_dist", "lloyd_reduce", "fused_assign_reduce",
               "remove_below", "update_min_dist", "sensitivity_scores",
               "truncated_cost"}
    assert public == set(ops.ENTRY_POINTS) == covered
