"""Per-kernel shape/dtype sweeps: Pallas (interpret mode) vs pure-jnp oracle."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import _backend
from repro.kernels.decision_fused import decision_fused as df
from repro.kernels.decision_fused import ops as df_ops
from repro.kernels.decision_fused import ref as df_ref
from repro.kernels.flash_attention import flash_attention as fa
from repro.kernels.flash_attention import ops as fa_ops
from repro.kernels.flash_attention import ref as fa_ref
from repro.kernels.fleet_scan import fleet_scan, ops as fleet_ops
from repro.kernels.fleet_scan import ref as fleet_ref
from repro.kernels.move_score import move_score, ops as move_ops
from repro.kernels.move_score import ref as move_ref
from repro.kernels.pruning import pruning, ref as prune_ref
from repro.kernels.zorder import ref as z_ref, zorder


# ---------------------------------------------------------------------------
# pruning kernel
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("Q,P,C", [(8, 8, 4), (64, 32, 12), (130, 60, 7),
                                   (256, 128, 58), (17, 5, 1)])
def test_pruning_matches_ref(Q, P, C):
    rng = np.random.default_rng(Q * 1000 + P)
    p_min = rng.uniform(0, 1, (P, C)).astype(np.float32)
    p_max = p_min + rng.uniform(0, 0.5, (P, C)).astype(np.float32)
    q_lo = rng.uniform(0, 1, (Q, C)).astype(np.float32)
    q_hi = q_lo + rng.uniform(0, 0.5, (Q, C)).astype(np.float32)
    got = pruning.scan_matrix_pallas(q_lo, q_hi, p_min, p_max, interpret=True)
    want = prune_ref.scan_matrix(q_lo, q_hi, p_min, p_max)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("Q,P,C,bq,bp,col_chunk", [
    (130, 60, 7, 128, 128, 8),    # Q and P ragged vs the block size
    (33, 17, 5, 16, 16, 2),       # ragged everywhere, C % col_chunk != 0
    (64, 32, 9, 32, 32, 4),       # C not a multiple of col_chunk
    (7, 3, 1, 8, 8, 8),           # tiny: blocks clamp to the problem size
    (128, 128, 8, 128, 128, 8),   # exact multiples (no padding at all)
])
def test_pruning_ragged_padding_parity(Q, P, C, bq, bp, col_chunk):
    """Kernel == numpy reference on every ragged Q/P/C padding edge, with
    interpret auto-selected (None -> interpreter on CPU-only hosts)."""
    rng = np.random.default_rng(Q * 7919 + P * 31 + C)
    p_min = rng.uniform(0, 1, (P, C)).astype(np.float32)
    p_max = p_min + rng.uniform(0, 0.5, (P, C)).astype(np.float32)
    q_lo = rng.uniform(0, 1, (Q, C)).astype(np.float32)
    q_hi = q_lo + rng.uniform(0, 0.5, (Q, C)).astype(np.float32)
    got = pruning.scan_matrix_pallas(q_lo, q_hi, p_min, p_max, bq=bq, bp=bp,
                                     col_chunk=col_chunk, interpret=None)
    want = prune_ref.scan_matrix(q_lo, q_hi, p_min, p_max)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want))


def test_pruning_interpret_autodetect_matches_backend():
    """interpret=None resolves to the interpreter exactly when JAX has no
    accelerator backend."""
    from repro.engine import scan_matrix as engine_scan_matrix
    rng = np.random.default_rng(0)
    p_min = rng.uniform(0, 1, (12, 4)).astype(np.float32)
    p_max = p_min + 0.2
    q_lo = rng.uniform(0, 1, (9, 4)).astype(np.float32)
    q_hi = q_lo + 0.3
    want = np.asarray(prune_ref.scan_matrix(q_lo, q_hi, p_min, p_max))
    # the engine's unified entry point routes through the same auto-detection
    got = engine_scan_matrix(q_lo, q_hi, p_min, p_max, backend="pallas")
    assert np.array_equal(got, want > 0.5)


@pytest.mark.parametrize("bq,bp,col_chunk", [(32, 32, 4), (128, 64, 8),
                                             (16, 128, 3)])
def test_pruning_block_sweep(bq, bp, col_chunk):
    rng = np.random.default_rng(0)
    Q, P, C = 96, 80, 10
    p_min = rng.uniform(0, 1, (P, C)).astype(np.float32)
    p_max = p_min + 0.2
    q_lo = rng.uniform(0, 1, (Q, C)).astype(np.float32)
    q_hi = q_lo + 0.3
    got = pruning.scan_matrix_pallas(q_lo, q_hi, p_min, p_max, bq=bq, bp=bp,
                                     col_chunk=col_chunk, interpret=True)
    want = prune_ref.scan_matrix(q_lo, q_hi, p_min, p_max)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want))


def test_pruning_agrees_with_core_cost_model():
    """Kernel semantics == the simulator's numpy cost model."""
    from repro.core import layouts as core_layouts
    rng = np.random.default_rng(3)
    P, C, Q = 24, 6, 40
    p_min = rng.uniform(0, 100, (P, C))
    p_max = p_min + rng.uniform(0, 30, (P, C))
    rows = rng.integers(100, 1000, P).astype(np.float64)
    meta = core_layouts.PartitionMetadata(mins=p_min, maxs=p_max, rows=rows)
    q_lo = rng.uniform(0, 100, (Q, C))
    q_hi = q_lo + rng.uniform(0, 50, (Q, C))
    want = core_layouts.partitions_scanned(meta, q_lo, q_hi)
    got = pruning.scan_matrix_pallas(q_lo.astype(np.float32),
                                     q_hi.astype(np.float32),
                                     p_min.astype(np.float32),
                                     p_max.astype(np.float32),
                                     interpret=True)
    np.testing.assert_array_equal(np.asarray(got) > 0.5, want)


# ---------------------------------------------------------------------------
# fleet_scan kernel (fused multi-tenant scan matrix)
# ---------------------------------------------------------------------------

def _fleet_case(T, N, C, seed):
    rng = np.random.default_rng(seed)
    p_min = rng.uniform(0, 1, (T, N, C)).astype(np.float32)
    p_max = p_min + rng.uniform(0, 0.5, (T, N, C)).astype(np.float32)
    q_lo = rng.uniform(0, 1, (T, C)).astype(np.float32)
    q_hi = q_lo + rng.uniform(0, 0.5, (T, C)).astype(np.float32)
    return q_lo, q_hi, p_min, p_max


@pytest.mark.parametrize("T,N,C", [(1, 8, 4), (4, 64, 8), (32, 56, 6),
                                   (17, 130, 7), (3, 5, 1)])
def test_fleet_scan_matches_ref(T, N, C):
    q_lo, q_hi, p_min, p_max = _fleet_case(T, N, C, T * 1000 + N)
    got = fleet_scan.scan_fleet_pallas(q_lo, q_hi, p_min, p_max,
                                       interpret=True)
    want = fleet_ref.scan_fleet(q_lo, q_hi, p_min, p_max)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("T,N,C,bt,bn,col_chunk", [
    (17, 130, 7, 8, 128, 8),    # T and N ragged vs the block sizes
    (5, 33, 5, 4, 16, 2),       # ragged everywhere, C % col_chunk != 0
    (8, 64, 9, 8, 32, 4),       # C not a multiple of col_chunk
    (1, 3, 1, 8, 8, 8),         # tiny: blocks clamp to the problem size
    (8, 128, 8, 8, 128, 8),     # exact multiples (no padding at all)
])
def test_fleet_scan_ragged_padding_parity(T, N, C, bt, bn, col_chunk):
    """Kernel == jnp oracle on every ragged T/N/C padding edge, with
    interpret auto-selected (None -> interpreter on CPU-only hosts)."""
    q_lo, q_hi, p_min, p_max = _fleet_case(T, N, C, T * 7919 + N * 31 + C)
    got = fleet_scan.scan_fleet_pallas(q_lo, q_hi, p_min, p_max, bt=bt,
                                       bn=bn, col_chunk=col_chunk,
                                       interpret=None)
    want = fleet_ref.scan_fleet(q_lo, q_hi, p_min, p_max)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_fleet_scan_per_tenant_rows_match_pruning_kernel():
    """Each tenant lane of the fused kernel equals the single-table
    pruning kernel run on that tenant's own bounds and query."""
    T, N, C = 6, 40, 5
    q_lo, q_hi, p_min, p_max = _fleet_case(T, N, C, 99)
    fused = np.asarray(fleet_scan.scan_fleet_pallas(q_lo, q_hi, p_min,
                                                    p_max, interpret=True))
    for t in range(T):
        single = pruning.scan_matrix_pallas(q_lo[t:t + 1], q_hi[t:t + 1],
                                            p_min[t], p_max[t],
                                            interpret=True)
        np.testing.assert_array_equal(fused[t], np.asarray(single)[0])


def test_fleet_scan_matches_engine_exact_path():
    """Kernel semantics == the engine's exact float64 fleet overlap (on
    float32-representable bounds), across the (C, T, S, P) layout."""
    from repro.engine import compute as engine_compute
    rng = np.random.default_rng(12)
    T, S, P, C = 4, 3, 8, 4
    mins = rng.uniform(0, 1, (T, S, P, C)).astype(np.float32).astype(
        np.float64)
    maxs = mins + rng.uniform(0, 0.5, (T, S, P, C)).astype(
        np.float32).astype(np.float64)
    q_lo = rng.uniform(0, 1, (T, C)).astype(np.float32).astype(np.float64)
    q_hi = q_lo + 0.25
    minsT = np.ascontiguousarray(np.moveaxis(mins, 3, 0))
    maxsT = np.ascontiguousarray(np.moveaxis(maxs, 3, 0))
    want = engine_compute.fleet_masked_overlap(minsT, maxsT, q_lo, q_hi)
    got = engine_compute.fleet_scan_matrix(
        q_lo, q_hi, mins.reshape(T, S * P, C), maxs.reshape(T, S * P, C),
        backend="pallas").reshape(T, S, P)
    np.testing.assert_array_equal(got, want)


def test_fleet_scan_fractions_weights_rows():
    rng = np.random.default_rng(13)
    T, N, C = 3, 16, 4
    q_lo, q_hi, p_min, p_max = _fleet_case(T, N, C, 13)
    rows = rng.integers(1, 100, (T, N)).astype(np.float32)
    frac = np.asarray(fleet_ops.fleet_scan_fractions(
        jnp.asarray(q_lo), jnp.asarray(q_hi), jnp.asarray(p_min),
        jnp.asarray(p_max), jnp.asarray(rows)))
    scan = np.asarray(fleet_ref.scan_fleet(q_lo, q_hi, p_min, p_max))
    want = (scan * rows).sum(1) / np.maximum(rows.sum(1), 1.0)
    np.testing.assert_allclose(frac, want, rtol=1e-6)
    assert np.all(frac >= 0) and np.all(frac <= 1)


def test_fleet_ops_wrapper_dispatches():
    q_lo, q_hi, p_min, p_max = _fleet_case(2, 8, 3, 7)
    via_kernel = fleet_ops.scan_fleet(q_lo, q_hi, p_min, p_max,
                                      use_kernel=True, interpret=True)
    via_oracle = fleet_ops.scan_fleet(q_lo, q_hi, p_min, p_max,
                                      use_kernel=False)
    np.testing.assert_array_equal(np.asarray(via_kernel),
                                  np.asarray(via_oracle))


# ---------------------------------------------------------------------------
# move_score kernel (per-partition scan frequencies for the reorg planner)
# ---------------------------------------------------------------------------

def _move_case(Q, S, P, C, seed):
    rng = np.random.default_rng(seed)
    p_min = rng.uniform(0, 1, (S, P, C)).astype(np.float32)
    p_max = p_min + rng.uniform(0, 0.5, (S, P, C)).astype(np.float32)
    q_lo = rng.uniform(0, 1, (Q, C)).astype(np.float32)
    q_hi = q_lo + rng.uniform(0, 0.5, (Q, C)).astype(np.float32)
    return q_lo, q_hi, p_min, p_max


@pytest.mark.parametrize("Q,S,P,C", [(8, 2, 16, 4), (32, 2, 64, 8),
                                     (13, 3, 37, 5), (1, 2, 5, 1),
                                     (64, 4, 130, 7)])
def test_move_score_matches_ref(Q, S, P, C):
    q_lo, q_hi, p_min, p_max = _move_case(Q, S, P, C, Q * 1000 + P)
    got = move_score.move_scores_pallas(q_lo, q_hi, p_min, p_max,
                                        interpret=True)
    want = move_ref.move_scores(q_lo, q_hi, p_min, p_max)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-7)


@pytest.mark.parametrize("Q,S,P,C,bp,col_chunk", [
    (16, 2, 130, 7, 128, 8),    # P ragged vs the block size
    (9, 3, 33, 5, 16, 2),       # ragged everywhere, C % col_chunk != 0
    (24, 2, 64, 9, 32, 4),      # C not a multiple of col_chunk
    (3, 1, 3, 1, 8, 8),         # tiny: blocks clamp to the problem size
    (16, 2, 128, 8, 128, 8),    # exact multiples (no padding at all)
])
def test_move_score_ragged_padding_parity(Q, S, P, C, bp, col_chunk):
    """Kernel == jnp oracle on every ragged P/C padding edge, with
    interpret auto-selected (None -> interpreter on CPU-only hosts)."""
    q_lo, q_hi, p_min, p_max = _move_case(Q, S, P, C, Q * 7919 + P * 31 + C)
    got = move_score.move_scores_pallas(q_lo, q_hi, p_min, p_max, bp=bp,
                                        col_chunk=col_chunk, interpret=None)
    want = move_ref.move_scores(q_lo, q_hi, p_min, p_max)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-7)


def test_move_score_agrees_with_planner_numpy_path():
    """Kernel frequencies == the planner's exact numpy scan frequencies
    (on float32-representable bounds)."""
    from repro.core import layouts as core_layouts
    from repro.engine.reorg.planner import scan_frequencies
    rng = np.random.default_rng(21)
    P, C, Q = 24, 4, 30
    metas = []
    for _ in range(2):
        mins = rng.uniform(0, 100, (P, C)).astype(np.float32).astype(
            np.float64)
        maxs = mins + rng.uniform(0, 30, (P, C)).astype(np.float32).astype(
            np.float64)
        rows = rng.integers(10, 100, P).astype(np.float64)
        metas.append(core_layouts.PartitionMetadata(mins=mins, maxs=maxs,
                                                    rows=rows))
    q_lo = rng.uniform(0, 100, (Q, C)).astype(np.float32).astype(np.float64)
    q_hi = q_lo + 20.0
    exact = scan_frequencies(metas, q_lo, q_hi, compute="numpy")
    kernel = scan_frequencies(metas, q_lo, q_hi, compute="pallas")
    for a, b in zip(exact, kernel):
        np.testing.assert_allclose(b, a, rtol=1e-6, atol=1e-7)


def test_move_ops_wrapper_dispatches():
    q_lo, q_hi, p_min, p_max = _move_case(12, 2, 20, 3, 7)
    via_kernel = move_ops.move_scan_frequencies(q_lo, q_hi, p_min, p_max,
                                                use_kernel=True,
                                                interpret=True)
    via_oracle = move_ops.move_scan_frequencies(q_lo, q_hi, p_min, p_max,
                                                use_kernel=False)
    np.testing.assert_allclose(np.asarray(via_kernel),
                               np.asarray(via_oracle), rtol=1e-6)


# ---------------------------------------------------------------------------
# zorder kernel
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("N,m,bits", [(100, 3, 10), (1024, 2, 16),
                                      (4097, 3, 8), (64, 1, 16), (33, 4, 8)])
def test_zorder_matches_ref(N, m, bits):
    rng = np.random.default_rng(N)
    vals = rng.uniform(-5, 5, (N, m)).astype(np.float32)
    lo = vals.min(0)
    hi = vals.max(0)
    got = zorder.zorder_keys_pallas(vals, lo, hi, bits=bits, interpret=True)
    want = z_ref.zorder_keys(jnp.asarray(vals), jnp.asarray(lo),
                             jnp.asarray(hi), bits=bits)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_zorder_matches_core_numpy():
    """Kernel keys sort rows identically to the simulator's numpy Z-order."""
    from repro.core import zorder as core_z
    rng = np.random.default_rng(7)
    vals = rng.uniform(0, 100, (512, 3))
    lo, hi = vals.min(0), vals.max(0)
    codes = core_z.quantize_columns(vals, lo, hi)
    want = core_z.interleave_bits(codes)
    got = zorder.zorder_keys_pallas(vals.astype(np.float32),
                                    lo.astype(np.float32),
                                    hi.astype(np.float32),
                                    bits=10, interpret=True)
    # Different bit depths (16 vs 10) -> compare induced orderings coarsely:
    # keys must be monotone under the same sort for a decimated prefix.
    order_ref = np.argsort(np.asarray(want), kind="stable")
    order_got = np.argsort(np.asarray(got), kind="stable")
    # identical leading-bit structure => high rank correlation
    from scipy import stats  # noqa: F401  (optional)
    ranks_ref = np.empty(512); ranks_ref[order_ref] = np.arange(512)
    ranks_got = np.empty(512); ranks_got[order_got] = np.arange(512)
    corr = np.corrcoef(ranks_ref, ranks_got)[0, 1]
    assert corr > 0.98, corr


# ---------------------------------------------------------------------------
# flash attention kernel
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("T,S,dh,causal", [
    (128, 128, 64, True), (256, 256, 64, True), (64, 64, 128, True),
    (128, 128, 64, False), (96, 96, 64, True),   # non-multiple of block
])
def test_flash_attention_matches_ref(T, S, dh, causal):
    key = jax.random.PRNGKey(T + S)
    BH = 4
    q = jax.random.normal(key, (BH, T, dh), jnp.float32)
    k = jax.random.normal(jax.random.fold_in(key, 1), (BH, S, dh),
                          jnp.float32)
    v = jax.random.normal(jax.random.fold_in(key, 2), (BH, S, dh),
                          jnp.float32)
    got = fa.flash_attention_pallas(q, k, v, causal=causal, bq=64, bk=64,
                                    interpret=True)
    want = fa_ref.attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("dtype,rtol", [(jnp.float32, 2e-3),
                                        (jnp.bfloat16, 2e-2)])
def test_flash_attention_dtypes(dtype, rtol):
    key = jax.random.PRNGKey(0)
    q = jax.random.normal(key, (2, 128, 64), dtype)
    k = jax.random.normal(jax.random.fold_in(key, 1), (2, 128, 64), dtype)
    v = jax.random.normal(jax.random.fold_in(key, 2), (2, 128, 64), dtype)
    got = fa.flash_attention_pallas(q, k, v, causal=True, bq=64, bk=64,
                                    interpret=True)
    want = fa_ref.attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=rtol, atol=rtol)


def test_flash_attention_gqa_wrapper_matches_model_layer():
    """ops.attention (GQA expand + kernel) == models.layers.flash_attention."""
    from repro.models import layers as L
    key = jax.random.PRNGKey(5)
    B, T, Hq, Hkv, dh = 2, 128, 8, 2, 32
    q = jax.random.normal(key, (B, T, Hq, dh), jnp.float32)
    k = jax.random.normal(jax.random.fold_in(key, 1), (B, T, Hkv, dh),
                          jnp.float32)
    v = jax.random.normal(jax.random.fold_in(key, 2), (B, T, Hkv, dh),
                          jnp.float32)
    got = fa_ops.attention(q, k, v, causal=True, use_kernel=True, bq=64,
                           bk=64)
    want = L.flash_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-3, atol=2e-3)


def test_flash_attention_prefix_lm():
    key = jax.random.PRNGKey(9)
    q = jax.random.normal(key, (2, 128, 32), jnp.float32)
    k = jax.random.normal(jax.random.fold_in(key, 1), (2, 128, 32),
                          jnp.float32)
    v = jax.random.normal(jax.random.fold_in(key, 2), (2, 128, 32),
                          jnp.float32)
    got = fa.flash_attention_pallas(q, k, v, causal=True, prefix_len=32,
                                    bq=64, bk=64, interpret=True)
    want = fa_ref.attention(q, k, v, causal=True, prefix_len=32)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-3, atol=2e-3)


# ---------------------------------------------------------------------------
# decision_fused megakernel (scan + serve-shadow cost + move freq, one pass)
# ---------------------------------------------------------------------------

def _fused_case(B, T, S, P, C, W, seed):
    rng = np.random.default_rng(seed)
    p_min = rng.uniform(0, 1, (T, S, P, C)).astype(np.float32)
    p_max = p_min + rng.uniform(0, 0.5, (T, S, P, C)).astype(np.float32)
    q_lo = rng.uniform(0, 1, (B, T, C)).astype(np.float32)
    q_hi = q_lo + rng.uniform(0, 0.5, (B, T, C)).astype(np.float32)
    rows = rng.integers(1, 1000, (T, S, P)).astype(np.float32)
    inv = (1.0 / np.maximum(rows.sum(-1), 1.0)).astype(np.float32)
    w_lo = rng.uniform(0, 1, (W, C)).astype(np.float32)
    w_hi = w_lo + rng.uniform(0, 0.5, (W, C)).astype(np.float32)
    return q_lo, q_hi, p_min, p_max, rows, inv, w_lo, w_hi


def _col_major(q_lo, q_hi, p_min, p_max, *rest):
    """The kernel's operand order with the (T, S, P, C) plane as
    (C, T, S, P); the oracle keeps the row-major form."""
    return (q_lo, q_hi, np.moveaxis(p_min, -1, 0),
            np.moveaxis(p_max, -1, 0), *rest)


def _assert_fused_triple(got, want):
    g_scan, g_cost, g_freq = got
    w_scan, w_cost, w_freq = want
    np.testing.assert_array_equal(np.asarray(g_scan), np.asarray(w_scan))
    np.testing.assert_allclose(np.asarray(g_cost), np.asarray(w_cost),
                               rtol=1e-6, atol=1e-7)
    np.testing.assert_allclose(np.asarray(g_freq), np.asarray(w_freq),
                               rtol=1e-6, atol=1e-7)


@pytest.mark.parametrize("B,T,S,P,C,W", [
    (1, 1, 1, 1, 1, 1), (2, 3, 2, 8, 4, 4), (4, 8, 3, 16, 6, 8),
    (3, 5, 4, 33, 5, 7), (2, 4, 2, 128, 8, 16),
])
def test_fused_decision_matches_ref(B, T, S, P, C, W):
    ops = _fused_case(B, T, S, P, C, W, B * 1000 + T * 100 + P)
    got = df.fused_decision_pallas(*_col_major(*ops), interpret=True)
    want = df_ref.fused_decision(*[jnp.asarray(a) for a in ops])
    _assert_fused_triple(got, want)


@pytest.mark.parametrize("B,T,S,P,C,W,bb,bp", [
    (2, 17, 2, 130, 7, 4, 4, 128),      # P ragged vs the partition block
    (3, 5, 3, 33, 5, 6, 2, 128),        # B ragged vs the frame block
    (5, 8, 2, 300, 9, 8, 2, 256),       # B and P both ragged
    (1, 1, 1, 3, 1, 1, 4, 128),         # tiny: blocks clamp to the problem
    (4, 8, 2, 256, 8, 4, 2, 128),       # exact multiples (no padding)
])
def test_fused_decision_ragged_padding_parity(B, T, S, P, C, W, bb, bp):
    """Megakernel == jnp oracle on every ragged B/P padding edge, with
    interpret auto-selected (None -> interpreter on CPU-only hosts)."""
    ops = _fused_case(B, T, S, P, C, W, T * 7919 + P * 31 + C)
    got = df.fused_decision_pallas(*_col_major(*ops), bb=bb, bp=bp,
                                   interpret=None)
    want = df_ref.fused_decision(*[jnp.asarray(a) for a in ops])
    _assert_fused_triple(got, want)


def test_fused_decision_partial_outputs():
    """Outputs not requested come back None; the requested ones are
    unchanged by which siblings ride along."""
    q_lo, q_hi, p_min, p_max, rows, inv, w_lo, w_hi = _col_major(
        *_fused_case(2, 4, 2, 20, 4, 6, 55))
    full = df.fused_decision_pallas(q_lo, q_hi, p_min, p_max, rows, inv,
                                    w_lo, w_hi, interpret=True)
    scan_only = df.fused_decision_pallas(q_lo, q_hi, p_min, p_max,
                                         interpret=True)
    assert scan_only[1] is None and scan_only[2] is None
    np.testing.assert_array_equal(np.asarray(scan_only[0]),
                                  np.asarray(full[0]))
    cost_only = df.fused_decision_pallas(q_lo, q_hi, p_min, p_max, rows,
                                         inv, emit_scan=False,
                                         interpret=True)
    assert cost_only[0] is None and cost_only[2] is None
    np.testing.assert_array_equal(np.asarray(cost_only[1]),
                                  np.asarray(full[1]))
    freq_only = df.fused_decision_pallas(q_lo, q_hi, p_min, p_max,
                                         w_lo=w_lo, w_hi=w_hi,
                                         emit_scan=False, interpret=True)
    assert freq_only[0] is None and freq_only[1] is None
    np.testing.assert_array_equal(np.asarray(freq_only[2]),
                                  np.asarray(full[2]))
    with pytest.raises(ValueError, match="nothing to emit"):
        df.fused_decision_pallas(q_lo, q_hi, p_min, p_max, emit_scan=False,
                                 interpret=True)


def test_fused_decision_rejects_unaligned_partition_block():
    """The partition block rides the 128-wide lane axis on the chip."""
    ops = _col_major(*_fused_case(1, 1, 1, 300, 2, 1, 3))
    with pytest.raises(ValueError, match="multiple of 128"):
        df.fused_decision_pallas(*ops[:4], bp=100, interpret=True)


def test_fused_decision_matches_three_separate_kernels():
    """The megakernel's three outputs == the three kernels it fuses,
    bit for bit on the 0/1 scan and to float tolerance on the reductions."""
    B, T, S, P, C, W = 3, 6, 2, 40, 5, 8
    q_lo, q_hi, p_min, p_max, rows, inv, w_lo, w_hi = _fused_case(
        B, T, S, P, C, W, 99)
    scan, cost, freq = df.fused_decision_pallas(
        *_col_major(q_lo, q_hi, p_min, p_max, rows, inv, w_lo, w_hi),
        interpret=True)
    scan = np.asarray(scan)
    # scan: one fleet_scan launch per frame over the (T, S*P, C) plane
    pm2 = p_min.reshape(T, S * P, C)
    px2 = p_max.reshape(T, S * P, C)
    for b in range(B):
        sep = fleet_scan.scan_fleet_pallas(q_lo[b], q_hi[b], pm2, px2,
                                           interpret=True)
        np.testing.assert_array_equal(
            scan[b], np.asarray(sep).reshape(T, S, P))
    # scan again: one pruning launch per (frame, tenant, state) table
    for t in range(T):
        for s in range(S):
            single = pruning.scan_matrix_pallas(
                q_lo[:, t], q_hi[:, t], p_min[t, s], p_max[t, s],
                interpret=True)
            np.testing.assert_array_equal(scan[:, t, s], np.asarray(single))
    # cost: the scanned-row fraction the scan implies
    want_cost = (scan * rows[None]).sum(-1) * inv[None]
    np.testing.assert_allclose(np.asarray(cost), want_cost, rtol=1e-6,
                               atol=1e-7)
    # freq: one move_score launch per tenant over the shared window
    for t in range(T):
        sep = move_score.move_scores_pallas(w_lo, w_hi, p_min[t], p_max[t],
                                            interpret=True)
        np.testing.assert_allclose(np.asarray(freq)[t], np.asarray(sep),
                                   rtol=1e-6, atol=1e-7)


def test_fused_ops_wrapper_dispatches():
    ops = _fused_case(2, 3, 2, 12, 4, 5, 7)
    via_kernel = df_ops.fused_decision(*ops, use_kernel=True,
                                       interpret=True)
    via_oracle = df_ops.fused_decision(*ops, use_kernel=False)
    _assert_fused_triple(via_kernel, via_oracle)


# ---------------------------------------------------------------------------
# shared interpret auto-detection (_backend.resolve_interpret)
# ---------------------------------------------------------------------------

def test_resolve_interpret_explicit_passthrough():
    assert _backend.resolve_interpret(True) is True
    assert _backend.resolve_interpret(False) is False


def test_resolve_interpret_follows_detected_backend(monkeypatch):
    """interpret=None compiles on accelerators and interprets on CPU-only
    hosts — the seam every kernel shares."""
    monkeypatch.setattr(_backend, "default_backend", lambda: "tpu")
    assert _backend.resolve_interpret(None) is False
    monkeypatch.setattr(_backend, "default_backend", lambda: "gpu")
    assert _backend.resolve_interpret(None) is False
    monkeypatch.setattr(_backend, "default_backend", lambda: "cpu")
    assert _backend.resolve_interpret(None) is True


def test_initialized_platform_reports_without_initializing():
    """The process-holds-a-device probe names the live backend's platform
    once JAX has one (this test session already ran kernels)."""
    jnp.zeros(1).block_until_ready()
    assert _backend.initialized_platform() == jax.default_backend()


def test_all_kernels_share_backend_seam(monkeypatch):
    """Monkeypatching the one detected-backend seam changes auto-detect
    for every kernel module (no copy-pasted detection left behind)."""
    calls = []

    def spy():
        calls.append(1)
        return "cpu"

    monkeypatch.setattr(_backend, "default_backend", spy)
    q_lo, q_hi, p_min, p_max = _fleet_case(2, 8, 3, 3)
    fleet_scan.scan_fleet_pallas(q_lo, q_hi, p_min, p_max, interpret=None)
    move_score.move_scores_pallas(q_lo, q_hi, p_min, p_max, interpret=None)
    pruning.scan_matrix_pallas(q_lo, q_hi, p_min[0], p_max[0],
                               interpret=None)
    df.fused_decision_pallas(q_lo[None], q_hi[None],
                             np.moveaxis(p_min, -1, 0)[:, :, None],
                             np.moveaxis(p_max, -1, 0)[:, :, None],
                             interpret=None)
    assert len(calls) >= 4
