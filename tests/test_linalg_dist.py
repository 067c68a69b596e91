"""paddle.linalg.dist — SUMMA-style distributed linear algebra on the
8-device MULTICHIP mesh (ISSUE 12).

Gates: numerical agreement of SUMMA matmul / blocked Cholesky / TSQR
/ Lanczos / subspace iteration with the single-device jnp.linalg
reference, comm/<op>/bytes telemetry matching each algorithm's
analytic collective volume, PTA05x lint behavior on ShardedMatrix
specs (zero findings under PADDLE_SANITIZE=sharding for valid
layouts), the linalg_dispatch chaos site, persistent-compile-cache
integration, and the README doc-drift gate over linalg/."""
import os

import numpy as np
import pytest

import paddle_tpu as paddle  # noqa: F401 — registers ops/backends
from paddle_tpu.core import monitor as cmon
from paddle_tpu.distributed import build_mesh, get_mesh, set_mesh
from paddle_tpu.linalg import dist as dla

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

RNG = np.random.default_rng(12345)


def _f32(*shape):
    return RNG.standard_normal(shape).astype(np.float32)


def _spd(n):
    m = RNG.standard_normal((n, n))
    return (m @ m.T + n * np.eye(n)).astype(np.float32)


@pytest.fixture
def mesh24():
    prev = get_mesh()
    mesh = build_mesh({"dp": 2, "mp": 4})
    set_mesh(mesh)
    yield mesh
    set_mesh(prev)
    dla.clear_program_cache()


@pytest.fixture
def mesh42():
    prev = get_mesh()
    mesh = build_mesh({"dp": 4, "mp": 2})
    set_mesh(mesh)
    yield mesh
    set_mesh(prev)
    dla.clear_program_cache()


@pytest.fixture
def mesh1d():
    prev = get_mesh()
    mesh = build_mesh({"dp": 8})
    set_mesh(mesh)
    yield mesh
    set_mesh(prev)
    dla.clear_program_cache()


# ---------------------------------------------------------------------------
# ShardedMatrix layouts + lints
# ---------------------------------------------------------------------------

def test_shard_gather_roundtrip_blocks(mesh24):
    a = _f32(64, 32)
    A = dla.shard(a)
    assert A.shape == (64, 32)
    assert A.block_shape == (32, 8)
    assert A.layout == "blocks"
    assert tuple(A.spec) == ("dp", "mp")
    np.testing.assert_array_equal(A.gather(), a)
    # the global array is genuinely 2D-block-sharded over all devices
    assert len({d for s in A.value.addressable_shards
                for d in [s.device]}) == 8
    assert A.value.addressable_shards[0].data.shape == (32, 8)


def test_shard_gather_roundtrip_rows(mesh24):
    a = _f32(64, 4)
    A = dla.shard(a, layout="rows")
    assert A.block_shape == (8, 4)
    spec = tuple(A.spec)
    assert spec[0] == ("dp", "mp") and spec[1] is None
    np.testing.assert_array_equal(A.gather(), a)


def test_shard_rejects_non_2d_and_indivisible(mesh24):
    with pytest.raises(ValueError, match="2D"):
        dla.shard(_f32(4, 4, 4))
    with pytest.raises(ValueError, match="PTA051"):
        dla.shard(_f32(63, 32))  # rows not divisible by dp=2
    with pytest.raises(ValueError, match="PTA051"):
        dla.shard(_f32(64, 30))  # cols not divisible by mp=4
    with pytest.raises(ValueError, match="PTA051"):
        dla.shard(_f32(62, 4), layout="rows")  # 62 % 8 != 0


def test_grid_resolution_and_overrides(mesh24):
    g = dla.grid()
    assert (g.rx, g.cx, g.px, g.py) == ("dp", "mp", 2, 4)
    g = dla.grid(row_axis="mp", col_axis="dp")
    assert (g.px, g.py) == (4, 2)
    with pytest.raises(ValueError, match="not a mesh axis"):
        dla.grid(row_axis="nope")
    with pytest.raises(ValueError, match="distinct"):
        dla.grid(row_axis="dp", col_axis="dp")
    os.environ["PADDLE_LINALG_AXES"] = "mp,dp"
    try:
        g = dla.grid()
        assert (g.rx, g.cx) == ("mp", "dp")
    finally:
        del os.environ["PADDLE_LINALG_AXES"]


def test_lint_spec_records_findings_only_when_armed(mesh24):
    """PTA05x runs on every ShardedMatrix spec before compile: errors
    always raise; the analysis counters only move when the sanitizer
    (or PADDLE_ANALYSIS) is armed — the disarmed path must stay
    counter-clean (bench provenance contract)."""
    from paddle_tpu.monitor import sanitize as san

    cmon.stat_reset("analysis/PTA051/findings")
    with pytest.raises(ValueError):
        dla.shard(_f32(63, 32))
    assert cmon.stat_get("analysis/PTA051/findings") == 0
    san.configure("sharding")
    try:
        with pytest.raises(ValueError):
            dla.shard(_f32(63, 32))
        assert cmon.stat_get("analysis/PTA051/findings") >= 1
    finally:
        san.disarm()
        cmon.stat_reset("analysis/PTA051/findings")


# ---------------------------------------------------------------------------
# SUMMA matmul
# ---------------------------------------------------------------------------

def _matmul_case(M, K, N, block_size=None):
    a, b = _f32(M, K), _f32(K, N)
    C = dla.matmul(dla.shard(a), dla.shard(b), block_size=block_size)
    ref = a @ b
    np.testing.assert_allclose(C.gather(), ref, rtol=2e-4, atol=2e-4)
    return C


def test_summa_matches_reference_2x4(mesh24):
    C = _matmul_case(64, 96, 48)
    assert C.block_shape == (32, 12)


def test_summa_matches_reference_4x2(mesh42):
    _matmul_case(32, 64, 80)


def test_summa_matches_reference_1d(mesh1d):
    _matmul_case(64, 64, 32)


def test_summa_block_sizes_agree(mesh24):
    a, b = _f32(32, 96, ), _f32(96, 32)
    A, B = dla.shard(a), dla.shard(b)
    outs = [dla.matmul(A, B, block_size=nb).gather()
            for nb in (4, 12, 24)]
    for o in outs[1:]:
        np.testing.assert_allclose(o, outs[0], rtol=2e-4, atol=2e-4)
    with pytest.raises(ValueError, match="block_size"):
        dla.matmul(A, B, block_size=5)


def test_summa_shape_and_layout_validation(mesh24):
    A = dla.shard(_f32(64, 32))
    with pytest.raises(ValueError, match="inner dims"):
        dla.matmul(A, dla.shard(_f32(64, 32)))
    with pytest.raises(TypeError, match="ShardedMatrix"):
        dla.matmul(A, _f32(32, 8))
    with pytest.raises(ValueError, match="layout"):
        dla.matmul(A, dla.shard(_f32(32, 8), layout="rows"))


def test_summa_comm_bytes_match_analytic_volume(mesh24):
    """The acceptance gate: comm/broadcast/bytes must price exactly
    the SUMMA panel traffic — T panels x (A panel (M/px, nb) + B
    panel (nb, N/py)) f32 elements, counted at trace time."""
    M, K, N, nb = 64, 32, 64, 8
    a, b = _f32(M, K), _f32(K, N)
    A, B = dla.shard(a), dla.shard(b)
    grid = A.grid
    dla.clear_program_cache()
    before = cmon.stat_get("comm/broadcast/bytes")
    calls_before = cmon.stat_get("comm/broadcast/calls")
    dla.matmul(A, B, block_size=nb)
    t = K // nb
    expect = t * (M // grid.px * nb + nb * N // grid.py) * 4
    assert cmon.stat_get("comm/broadcast/bytes") - before == expect
    assert cmon.stat_get("comm/broadcast/calls") - calls_before == 2 * t


def test_summa_counters_and_flight(mesh24):
    from paddle_tpu.monitor import flight

    a, b = _f32(16, 16), _f32(16, 16)
    A, B = dla.shard(a), dla.shard(b)
    before = cmon.stat_get("linalg/matmuls")
    bytes_before = cmon.stat_get("linalg/bytes")
    dla.matmul(A, B)
    assert cmon.stat_get("linalg/matmuls") == before + 1
    assert cmon.stat_get("linalg/bytes") > bytes_before
    kinds = [e["kind"] for e in flight.tail()]
    assert "linalg_begin" in kinds and "linalg_end" in kinds


# ---------------------------------------------------------------------------
# block-size selection
# ---------------------------------------------------------------------------

def test_block_candidates_and_env_pin(mesh24):
    A, B = dla.shard(_f32(32, 96)), dla.shard(_f32(96, 32))
    g = A.grid
    cands = dla.block_candidates(96, g)
    # gcd(96/2, 96/4) = 24
    assert cands[0] == 24 and all(24 % c == 0 for c in cands)
    os.environ["PADDLE_LINALG_BLOCK"] = "12"
    try:
        assert dla.choose_block_size(A, B) == 12
        os.environ["PADDLE_LINALG_BLOCK"] = "7"
        with pytest.raises(ValueError, match="PADDLE_LINALG_BLOCK"):
            dla.choose_block_size(A, B)
    finally:
        del os.environ["PADDLE_LINALG_BLOCK"]
    assert dla.choose_block_size(A, B) == 24  # largest capped divisor


def test_block_autotune_rides_cost_model(mesh24):
    """PADDLE_LINALG_AUTOTUNE=1 profiles candidate programs through
    cost_model.CostModel and caches the pick per shape family."""
    from paddle_tpu.linalg.dist import summa

    A, B = dla.shard(_f32(16, 32)), dla.shard(_f32(32, 16))
    summa._chosen.clear()
    os.environ["PADDLE_LINALG_AUTOTUNE"] = "1"
    try:
        nb = dla.choose_block_size(A, B)
        assert nb in dla.block_candidates(32, A.grid)
        assert summa._chosen  # cached for the rerun
        assert dla.choose_block_size(A, B) == nb
        out = dla.matmul(A, B, block_size=nb)
        np.testing.assert_allclose(
            out.gather(), A.gather() @ B.gather(),
            rtol=2e-4, atol=2e-4)
    finally:
        del os.environ["PADDLE_LINALG_AUTOTUNE"]
        summa._chosen.clear()


# ---------------------------------------------------------------------------
# factorizations
# ---------------------------------------------------------------------------

def test_cholesky_matches_reference(mesh24):
    spd = _spd(64)
    L = dla.cholesky(dla.shard(spd))
    ref = np.linalg.cholesky(spd)
    np.testing.assert_allclose(L.gather(), ref, rtol=1e-3, atol=1e-3)
    # strictly lower-triangular blocks everywhere above the diagonal
    assert np.allclose(L.gather(), np.tril(L.gather()))


def test_cholesky_block_sizes_and_4x2(mesh42):
    spd = _spd(64)
    ref = np.linalg.cholesky(spd)
    for nb in (8, 16):
        L = dla.cholesky(dla.shard(spd), block_size=nb)
        np.testing.assert_allclose(L.gather(), ref, rtol=1e-3,
                                   atol=1e-3)
    with pytest.raises(ValueError, match="block_size"):
        dla.cholesky(dla.shard(spd), block_size=5)
    with pytest.raises(ValueError, match="square"):
        dla.cholesky(dla.shard(_f32(64, 32)))


def test_cholesky_comm_bytes_match_analytic_volume(mesh24):
    """Per panel: one (nb,nb) 2D broadcast of the diagonal block, one
    (N/px, nb) row broadcast of the panel, one (N/px, nb) all_gather
    up the column tree. all_gather prices its FULL payload — the
    group_size gathered copies, px * the per-rank (N/px, nb) panel =
    the whole (N, nb) column per panel (the ISSUE-14 list-arg payload
    fix; broadcast stays the per-rank tensor)."""
    N, nb = 64, 16
    spd = _spd(N)
    A = dla.shard(spd)
    g = A.grid
    dla.clear_program_cache()
    b0 = cmon.stat_get("comm/broadcast/bytes")
    g0 = cmon.stat_get("comm/all_gather/bytes")
    dla.cholesky(A, block_size=nb)
    t = N // nb
    rb = N // g.px
    assert cmon.stat_get("comm/broadcast/bytes") - b0 == \
        t * (nb * nb + rb * nb) * 4
    assert cmon.stat_get("comm/all_gather/bytes") - g0 == \
        t * g.px * rb * nb * 4


def test_tsqr_matches_reference(mesh24):
    a = _f32(256, 8)
    Q, R = dla.qr(dla.shard(a, layout="rows"))
    qg = Q.gather()
    np.testing.assert_allclose(qg @ R, a, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(qg.T @ qg, np.eye(8), atol=1e-4)
    assert np.allclose(R, np.triu(R))
    # against the single-device reference, both sign-normalized to
    # diag(R) >= 0
    qr_ref, r_ref = np.linalg.qr(a)
    s = np.sign(np.diag(r_ref))
    s[s == 0] = 1
    np.testing.assert_allclose(R, r_ref * s[:, None], rtol=1e-3,
                               atol=1e-3)
    np.testing.assert_allclose(qg, qr_ref * s[None, :], rtol=1e-3,
                               atol=1e-3)


def test_tsqr_validation_and_counters(mesh24):
    with pytest.raises(ValueError, match="rows"):
        dla.qr(dla.shard(_f32(64, 8)))
    with pytest.raises(ValueError, match="at least as tall"):
        dla.qr(dla.shard(_f32(64, 16), layout="rows"))  # 8 rows < 16
    before = cmon.stat_get("linalg/factorizations")
    dla.qr(dla.shard(_f32(64, 4), layout="rows"))
    assert cmon.stat_get("linalg/factorizations") == before + 1


# ---------------------------------------------------------------------------
# eigensolvers
# ---------------------------------------------------------------------------

def test_matvec_matches_reference(mesh24):
    a, v = _spd(64), _f32(64)
    A = dla.shard(a)
    w = np.asarray(dla.matvec(A, v))
    np.testing.assert_allclose(w, a @ v, rtol=2e-4, atol=2e-4)
    vk = _f32(64, 3)
    np.testing.assert_allclose(np.asarray(dla.matvec(A, vk)), a @ vk,
                               rtol=2e-4, atol=2e-4)
    with pytest.raises(ValueError, match="length"):
        dla.matvec(A, _f32(32))


def test_lanczos_extreme_eigenvalues(mesh24):
    sym = _spd(64)
    ref = np.linalg.eigvalsh(sym)
    top = dla.lanczos(dla.shard(sym), k=2, iters=40)
    np.testing.assert_allclose(top, ref[::-1][:2], rtol=1e-3)
    bot = dla.lanczos(dla.shard(sym), k=1, iters=40,
                      which="smallest")
    np.testing.assert_allclose(bot, ref[:1], rtol=1e-2)
    with pytest.raises(ValueError, match="which"):
        dla.lanczos(dla.shard(sym), which="middle")


def test_eigsh_subspace_iteration(mesh24):
    sym = _spd(64)
    wr, vr = np.linalg.eigh(sym)
    w, V = dla.eigsh(dla.shard(sym), k=3, iters=50, seed=3)
    np.testing.assert_allclose(w, wr[::-1][:3], rtol=1e-3)
    # eigenvector residual ||A v - w v|| small, sign-agnostic
    res = sym @ V - V * w[None, :]
    assert np.abs(res).max() < 5e-2
    before = cmon.stat_get("linalg/eigensolves")
    dla.eigsh(dla.shard(sym), k=2, iters=10)
    assert cmon.stat_get("linalg/eigensolves") == before + 1


# ---------------------------------------------------------------------------
# production spine: sanitizer, chaos, compile cache
# ---------------------------------------------------------------------------

def test_algorithms_sanitize_clean(mesh24):
    """Acceptance: zero sanitizer findings under
    PADDLE_SANITIZE=sharding while every algorithm family runs."""
    from paddle_tpu.monitor import sanitize as san

    san.configure("sharding")
    try:
        cmon.registry.reset_all()
        spd = _spd(32)
        A = dla.shard(spd)
        dla.matmul(A, A)
        dla.cholesky(A)
        dla.qr(dla.shard(_f32(64, 4), layout="rows"))
        dla.lanczos(A, k=1, iters=8)
        findings = {k: v for k, v in cmon.registry.snapshot().items()
                    if k.startswith("analysis/PTA05")}
        assert not any(findings.values()), findings
    finally:
        san.disarm()


def test_chaos_linalg_dispatch_site(mesh24):
    from paddle_tpu.monitor import chaos

    A = dla.shard(_f32(16, 16))
    with chaos.inject("linalg_dispatch", "raise") as rule:
        with pytest.raises(chaos.ChaosInjected):
            dla.matmul(A, A)
        assert rule.triggers == 1
    # disarmed again: the same cached program dispatches clean
    out = dla.matmul(A, A)
    np.testing.assert_allclose(out.gather(),
                               A.gather() @ A.gather(),
                               rtol=2e-4, atol=2e-4)


def test_program_cache_reuses_executables(mesh24):
    a, b = _f32(16, 32), _f32(32, 16)
    A, B = dla.shard(a), dla.shard(b)
    dla.clear_program_cache()
    compiles0 = cmon.stat_get("linalg/compiles")
    dla.matmul(A, B)
    assert cmon.stat_get("linalg/compiles") == compiles0 + 1
    hits0 = cmon.stat_get("linalg/program_cache/hits")
    dla.matmul(A, B)
    assert cmon.stat_get("linalg/compiles") == compiles0 + 1
    assert cmon.stat_get("linalg/program_cache/hits") == hits0 + 1


# ---------------------------------------------------------------------------
# API surface + doc drift
# ---------------------------------------------------------------------------

def test_linalg_package_surface_unchanged():
    """The package promotion must keep the ops.linalg surface: every
    op reachable at paddle.linalg.<op>, and the shadowed distance op
    still available as paddle.dist / linalg.pdist_op."""
    import paddle_tpu.linalg as L
    from paddle_tpu.ops import linalg as ops_linalg

    for name in ops_linalg.__all__:
        if name == "dist":
            continue  # the subpackage wins this name (ISSUE 12)
        assert getattr(L, name) is getattr(ops_linalg, name), name
    assert L.pdist_op is ops_linalg.dist
    assert callable(paddle.dist)
    import types

    assert isinstance(L.dist, types.ModuleType)
    assert L.dist is dla


def test_readme_documents_linalg_env_vars():
    """Doc-drift gate over linalg/: every PADDLE_* env var the
    package reads must appear in the README (the test_flight.py
    contract, extended over the new subsystem)."""
    import re

    pkg = os.path.join(REPO, "paddle_tpu", "linalg")
    vars_used = set()
    for root, _, files in os.walk(pkg):
        for f in files:
            if not f.endswith(".py"):
                continue
            with open(os.path.join(root, f)) as fh:
                vars_used |= set(re.findall(r"PADDLE_[A-Z0-9_]+",
                                            fh.read()))
    assert vars_used, "expected PADDLE_LINALG_* knobs in linalg/"
    with open(os.path.join(REPO, "README.md")) as f:
        doc = f.read()
    missing = sorted(v for v in vars_used if v not in doc)
    assert not missing, \
        f"linalg env vars missing from README: {missing}"
    for needle in ("Distributed linear algebra", "ShardedMatrix",
                   "linalg_dispatch", "SUMMA", "TSQR"):
        assert needle in doc, f"{needle!r} missing from README"
