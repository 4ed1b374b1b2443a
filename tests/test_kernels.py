"""Kernel-layer validation (tier-1, socket-free).

Three layers are pinned against each other (DESIGN.md §12):

  * the Pallas kernels (interpret mode on CPU — bit-accurate vs the TPU
    semantics) vs the pure-jnp oracles in repro.kernels.ref;
  * the fused XLA round-hot-path programs (`hessian_syrk_xla` /
    `hessian_syrk_packed` / the masked selection forms) vs the reference
    jnp formulations — bit-identical where the contract says so;
  * the selection contract itself: f32 rank keys, lowest-index tie-break,
    identical sets from the sorted and threshold-mask formulations,
    including adversarial f64-distinct/f32-equal near-ties.

Only the hypothesis property test needs hypothesis; everything else runs
under the plain tier-1 suite.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.compressors import select as csel
from repro.compressors.core import randseqk, topk
from repro.kernels import ops
from repro.kernels.compressor_select import (
    select_randseqk_pallas,
    select_topk_pallas,
)
from repro.kernels.ref import flash_attention_ref, hessian_syrk_ref

try:
    from hypothesis import given, settings, strategies as st

    HAVE_HYPOTHESIS = True
except ImportError:  # hypothesis is a dev extra; only the property test needs it
    HAVE_HYPOTHESIS = False


# ---------------------------------------------------------------------------
# hessian_syrk (Pallas wrapper, interpret mode)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n,d", [(8, 8), (64, 48), (348, 301), (130, 257), (1, 5)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.float64])
def test_hessian_syrk_sweep(n, d, dtype):
    key = jax.random.PRNGKey(n * 1000 + d)
    z = jax.random.normal(key, (n, d), dtype=dtype)
    h = jax.random.uniform(jax.random.fold_in(key, 1), (n,), dtype=dtype)
    got = ops.hessian_syrk(z, h)
    want = hessian_syrk_ref(z, h)
    tol = 2e-4 if dtype == jnp.float32 else 1e-10
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=tol, rtol=tol)


def test_hessian_syrk_symmetric_output():
    z = jax.random.normal(jax.random.PRNGKey(0), (100, 37), dtype=jnp.float64)
    h = jnp.ones(100) / 100
    out = np.asarray(ops.hessian_syrk(z, h))
    np.testing.assert_allclose(out, out.T, atol=1e-13)


if HAVE_HYPOTHESIS:

    @settings(max_examples=10, deadline=None)
    @given(
        n=st.integers(min_value=1, max_value=200),
        d=st.integers(min_value=1, max_value=160),
        seed=st.integers(0, 999),
    )
    def test_hessian_syrk_property(n, d, seed):
        key = jax.random.PRNGKey(seed)
        z = jax.random.normal(key, (n, d), dtype=jnp.float64)
        h = jax.random.uniform(jax.random.fold_in(key, 1), (n,), dtype=jnp.float64)
        np.testing.assert_allclose(
            np.asarray(ops.hessian_syrk(z, h)),
            np.asarray(hessian_syrk_ref(z, h)),
            atol=1e-10,
        )

else:

    @pytest.mark.skip(reason="property tests need hypothesis (requirements-dev.txt)")
    def test_hessian_syrk_property():
        pass


def test_hessian_syrk_blocks():
    """Different BlockSpec tilings agree."""
    z = jax.random.normal(jax.random.PRNGKey(3), (96, 80), dtype=jnp.float32)
    h = jnp.ones(96) * 0.5
    a = ops.hessian_syrk(z, h, block_d=128, block_n=128)
    b = ops.hessian_syrk(z, h, block_d=32, block_n=64)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)


# ---------------------------------------------------------------------------
# hessian_syrk_xla / hessian_syrk_packed — the fused round's CPU hot path
# ---------------------------------------------------------------------------

XLA_SHAPES = [(8, 8), (64, 48), (348, 301), (130, 257), (1, 5), (40, 129), (200, 128)]


@pytest.mark.parametrize("n,d", XLA_SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.float64])
def test_hessian_syrk_xla_parity(n, d, dtype):
    key = jax.random.PRNGKey(n * 1000 + d)
    z = jax.random.normal(key, (n, d), dtype=dtype)
    h = jax.random.uniform(jax.random.fold_in(key, 1), (n,), dtype=dtype)
    got = np.asarray(jax.jit(ops.hessian_syrk_xla)(z, h))
    # both sides jitted: the bit-exactness contract is between the compiled
    # round programs (an eager op-by-op reference differs bitwise in f32)
    want = np.asarray(jax.jit(hessian_syrk_ref)(z, h))
    if d <= 128:
        # single tile: the fused program IS the reference expression
        # (including its f32 gemm asymmetry of a few ulp — no extra claim)
        np.testing.assert_array_equal(got, want)
    else:
        tol = 2e-3 if dtype == jnp.float32 else 1e-12
        np.testing.assert_allclose(got, want, atol=tol, rtol=tol)
        # off-diagonal blocks are mirrored exactly; diagonal blocks hold two
        # independently-computed triangles (ulp-level asymmetry, like the
        # reference gemm) — the round consumes pack_triu(·), never the lower
        np.testing.assert_allclose(got, got.T, atol=tol, rtol=tol)


def test_hessian_syrk_xla_zero_weight_rows():
    """Zero-weight rows (padded samples) are exact no-ops for the strips."""
    key = jax.random.PRNGKey(7)
    z = jax.random.normal(key, (50, 200), dtype=jnp.float64)
    h = jax.random.uniform(jax.random.fold_in(key, 1), (50,), dtype=jnp.float64)
    h = h.at[30:].set(0.0)
    got = np.asarray(jax.jit(ops.hessian_syrk_xla)(z, h))
    want = np.asarray(jax.jit(ops.hessian_syrk_xla)(z[:30], h[:30]))
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("n,d", XLA_SHAPES)
def test_hessian_syrk_packed_bit_identical_to_full(n, d):
    """pack_triu straight off the strips == pack_triu of the mirrored matrix."""
    from repro.linalg import pack_triu

    key = jax.random.PRNGKey(n + d)
    z = jax.random.normal(key, (n, d), dtype=jnp.float64)
    h = jax.random.uniform(jax.random.fold_in(key, 1), (n,), dtype=jnp.float64)
    got = np.asarray(jax.jit(lambda z, h: ops.hessian_syrk_packed(z, h))(z, h))
    want = np.asarray(jax.jit(lambda z, h: pack_triu(ops.hessian_fused(z, h)))(z, h))
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("hessian", ["jnp", "fused", "pallas"])
def test_logreg_oracles_packed_matches_full(hessian):
    """The packed client oracle == pack_triu of the full oracle, bitwise."""
    from repro.linalg import pack_triu
    from repro.objectives.logreg import logreg_oracles, logreg_oracles_packed

    for n, d in [(30, 24), (60, 150)]:
        key = jax.random.PRNGKey(d)
        z = jax.random.normal(key, (n, d), dtype=jnp.float64)
        x = jax.random.normal(jax.random.fold_in(key, 1), (d,), dtype=jnp.float64)
        f1, g1, hp = jax.jit(
            lambda z, x: logreg_oracles_packed(z, x, 1e-3, hessian=hessian)
        )(z, x)
        f2, g2, hess = jax.jit(
            lambda z, x: logreg_oracles(z, x, 1e-3, hessian=hessian)
        )(z, x)
        assert float(f1) == float(f2)
        np.testing.assert_array_equal(np.asarray(g1), np.asarray(g2))
        np.testing.assert_array_equal(np.asarray(hp), np.asarray(pack_triu(hess)))


# ---------------------------------------------------------------------------
# the selection contract (DESIGN.md §12)
# ---------------------------------------------------------------------------

def _near_tie_vector(t: int, seed: int) -> jax.Array:
    """f64 entries that are pairwise distinct but collide when rounded to f32
    — the adversarial case for mixed-width ranking (the satellite-2 bug)."""
    rng = np.random.default_rng(seed)
    base = rng.standard_normal(t // 4).astype(np.float32).astype(np.float64)
    # four f64-distinct perturbations of each f32 value, all rounding back
    # to the same f32 key
    eps = np.array([0.0, 1e-12, 2.5e-12, -1e-12])
    u = (base[:, None] * (1.0 + eps[None, :])).reshape(-1)
    exact = np.asarray(
        jnp.abs(jnp.asarray(u)).astype(jnp.float32), dtype=np.float32
    )
    collide = len(np.unique(exact)) < len(np.unique(np.abs(u)))
    assert collide, "fixture must contain f32 key collisions"
    return jnp.asarray(rng.permutation(u))


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_selection_contract_near_ties(seed):
    """Sorted top_k, the threshold mask, and the Pallas kernel select the
    SAME index set on adversarial near-ties, with lowest-index tie-break."""
    t, k = 512, 100
    u = _near_tie_vector(t, seed)
    keys = np.asarray(csel.rank_keys(u))

    idx_sorted = np.sort(np.asarray(csel.topk_indices(u, k)))
    mask = np.asarray(csel.threshold_keep_mask(csel.rank_keys(u), k))
    idx_mask = np.flatnonzero(mask)
    u_pal, sent_pal = select_topk_pallas(u, k, interpret=True)
    idx_pal = np.flatnonzero(np.asarray(u_pal))

    np.testing.assert_array_equal(idx_sorted, idx_mask)
    np.testing.assert_array_equal(idx_sorted, idx_pal)
    assert int(sent_pal[0]) == k

    # lowest-index tie-break, verified independently with numpy: stable
    # descending sort of the f32 keys by (−key, index)
    order = np.lexsort((np.arange(t), -keys))
    np.testing.assert_array_equal(idx_sorted, np.sort(order[:k]))


@pytest.mark.parametrize("t,k", [(300, 24), (1000, 64), (257, 1), (130, 130)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.float64])
def test_masked_formulations_bit_identical(t, k, dtype):
    """topk_dense_masked / randseqk_dense_masked == the sorted/rolled forms."""
    key = jax.random.PRNGKey(t * 31 + k)
    u = jax.random.normal(key, (t,), dtype=dtype)
    np.testing.assert_array_equal(
        np.asarray(csel.topk_dense_masked(u, k)),
        np.asarray(csel.topk_dense(u, k)),
    )
    s = jax.random.randint(jax.random.fold_in(key, 1), (), 0, t)
    np.testing.assert_array_equal(
        np.asarray(csel.randseqk_dense_masked(u, k, s)),
        np.asarray(csel.randseqk_dense(u, k, s)),
    )


@pytest.mark.parametrize("t,k", [(300, 24), (1000, 64), (257, 3)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.float64])
def test_select_kernels_interpret_parity(t, k, dtype):
    """The Pallas selection kernels (interpret) are bit-identical to the
    routed compressor primitives, T a non-multiple of 128 included."""
    key = jax.random.PRNGKey(t + k)
    u = jax.random.normal(key, (t,), dtype=dtype)

    want, _ = topk(u, k)
    got, sent = select_topk_pallas(u, k, interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert int(sent[0]) == k

    rk = jax.random.fold_in(key, 1)
    want, _ = randseqk(rk, u, k)
    s = jax.random.randint(rk, (), 0, t)
    got, sent = select_randseqk_pallas(u, k, s, interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert int(sent[0]) == k


def test_toplek_uniform_replays_bernoulli():
    """The hoisted uniform draw consumes the PRNG stream exactly as
    jax.random.bernoulli(key, p) — the fused/unfused PRNG-parity pin."""
    for seed in range(50):
        key = jax.random.PRNGKey(seed)
        for dtype in (jnp.float32, jnp.float64):
            p = jnp.asarray(0.37, dtype=dtype)
            unif = csel.toplek_uniform(key, dtype)
            assert bool(unif < p) == bool(jax.random.bernoulli(key, p))


@pytest.mark.parametrize("comp", ["topk", "randk", "randseqk", "toplek",
                                  "natural", "identity"])
def test_fused_round_bit_parity(comp):
    """hessian='fused' replays hessian='jnp' bit-for-bit on tiny: state,
    metrics, and the integer bit accounting — for all six compressors."""
    from repro.core.fednl import FedNLConfig, fednl_init, make_fednl_round
    from repro.data import (
        add_intercept,
        make_synthetic_logreg,
        partition_clients,
        DATASET_SHAPES,
    )

    _, nc, ni = DATASET_SHAPES["tiny"]
    x, y = make_synthetic_logreg("tiny", seed=1)
    z = jnp.asarray(partition_clients(add_intercept(x), y, nc, ni, seed=1))

    results = {}
    for hessian in ("jnp", "fused"):
        cfg = FedNLConfig(compressor=comp, hessian=hessian)
        state = fednl_init(z, cfg, seed=1)
        round_fn = jax.jit(make_fednl_round(z, cfg))
        metrics = []
        for _ in range(3):
            state, m = round_fn(state)
            metrics.append(m)
        results[hessian] = (state, metrics)

    sj, mj = results["jnp"]
    sf, mf = results["fused"]
    np.testing.assert_array_equal(np.asarray(sj.x), np.asarray(sf.x))
    np.testing.assert_array_equal(np.asarray(sj.h_global), np.asarray(sf.h_global))
    np.testing.assert_array_equal(np.asarray(sj.h_local), np.asarray(sf.h_local))
    for a, b in zip(mj, mf):
        assert float(a.grad_norm) == float(b.grad_norm)
        assert int(a.sent_bits) == int(b.sent_bits)
        assert int(a.sent_elems) == int(b.sent_elems)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize(
    "sq,sk,hn,dh,causal,window",
    [
        (128, 128, 2, 64, True, None),
        (256, 256, 4, 64, True, 64),
        (200, 200, 2, 32, True, None),  # padded seq
        (96, 96, 1, 16, False, None),  # bidirectional + padding
        (256, 256, 2, 64, False, 128),
        (64, 256, 1, 32, False, None),  # cross-attention shape
    ],
)
def test_flash_attention_sweep(sq, sk, hn, dh, causal, window):
    ks = jax.random.split(jax.random.PRNGKey(sq + sk + hn), 3)
    q = jax.random.normal(ks[0], (sq, hn, dh), dtype=jnp.float32)
    k = jax.random.normal(ks[1], (sk, hn, dh), dtype=jnp.float32)
    v = jax.random.normal(ks[2], (sk, hn, dh), dtype=jnp.float32)
    got = ops.flash_attention(q, k, v, causal=causal, window=window,
                              block_q=64, block_k=64)
    want = flash_attention_ref(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


def test_flash_attention_bf16():
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (128, 2, 64), dtype=jnp.bfloat16)
    k = jax.random.normal(ks[1], (128, 2, 64), dtype=jnp.bfloat16)
    v = jax.random.normal(ks[2], (128, 2, 64), dtype=jnp.bfloat16)
    got = ops.flash_attention(q, k, v, causal=True, block_q=64, block_k=64)
    want = flash_attention_ref(
        q.astype(jnp.float32), k.astype(jnp.float32), v.astype(jnp.float32),
        causal=True,
    )
    np.testing.assert_allclose(
        np.asarray(got, dtype=np.float32), np.asarray(want), atol=0.05
    )


def test_flash_matches_models_chunked_attention():
    """The Pallas kernel and the models' jnp chunked attention agree."""
    from repro.models.layers import chunked_attention

    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    b, s, h, dh = 2, 256, 4, 32
    q = jax.random.normal(ks[0], (b, s, h, dh), dtype=jnp.float32)
    k = jax.random.normal(ks[1], (b, s, h, dh), dtype=jnp.float32)
    v = jax.random.normal(ks[2], (b, s, h, dh), dtype=jnp.float32)
    jnp_out = chunked_attention(q, k, v, causal=True, window=96, q_chunk=64)
    kern_out = jnp.stack([
        ops.flash_attention(q[i], k[i], v[i], causal=True, window=96,
                            block_q=64, block_k=64)
        for i in range(b)
    ])
    np.testing.assert_allclose(
        np.asarray(kern_out), np.asarray(jnp_out), atol=2e-5
    )


# ---------------------------------------------------------------------------
# the route (DESIGN.md §12): dtype and impl pick the formulation
# ---------------------------------------------------------------------------

def _tiny_round_jaxpr(dtype, hessian="fused", d=24):
    from repro.core.fednl import FedNLConfig, fednl_init, make_fednl_round

    z = jax.random.normal(jax.random.PRNGKey(0), (4, 20, d), dtype=dtype)
    cfg = FedNLConfig(compressor="topk", hessian=hessian)
    state = jax.eval_shape(lambda: fednl_init(z, cfg))
    return str(jax.make_jaxpr(make_fednl_round(z, cfg))(state))


@pytest.mark.parametrize("d", [24, 150])
def test_f64_fused_round_has_no_pallas_call(monkeypatch, d):
    """Even when the round targets a TPU, the f64 fused round traces no
    Pallas call (Mosaic has no f64); an f32 round there does."""
    monkeypatch.setattr(ops, "target_platform", lambda *a: "tpu")
    assert "pallas_call" not in _tiny_round_jaxpr(jnp.float64, d=d)
    assert "pallas_call" in _tiny_round_jaxpr(jnp.float32, d=d)


def test_f64_route_is_the_same_on_every_backend(monkeypatch):
    """The f64 fused round is one program whether it targets a TPU or the
    CPU: the CPU tests cover the formulation the chip runs."""
    monkeypatch.setattr(ops, "target_platform", lambda *a: "tpu")
    on_tpu = _tiny_round_jaxpr(jnp.float64, d=150)
    monkeypatch.setattr(ops, "target_platform", lambda *a: "cpu")
    assert _tiny_round_jaxpr(jnp.float64, d=150) == on_tpu


@pytest.mark.parametrize("kernel", ["syrk", "topk", "randseqk"])
def test_compiled_pallas_at_f64_names_the_dtype(kernel):
    """A Pallas wrapper called at f64 with interpret=False raises a
    TypeError naming the dtype, not a Mosaic lowering error."""
    u = jnp.ones((300,), jnp.float64)
    calls = {
        "syrk": lambda: ops.hessian_syrk(jnp.ones((8, 8), jnp.float64),
                                         jnp.ones((8,), jnp.float64),
                                         interpret=False),
        "topk": lambda: select_topk_pallas(u, 4, interpret=False),
        "randseqk": lambda: select_randseqk_pallas(u, 4, jnp.int32(3),
                                                   interpret=False),
    }
    with pytest.raises(TypeError, match="float64"):
        calls[kernel]()


def test_explicit_pallas_on_tpu_never_interprets(monkeypatch):
    """hessian='pallas' on a TPU target compiles (no interpret mode): at f64
    that is the dtype error, raised while the round is traced."""
    monkeypatch.setattr(ops, "target_platform", lambda *a: "tpu")
    assert ops.resolve_interpret(None) is False
    with pytest.raises(TypeError, match="float64"):
        _tiny_round_jaxpr(jnp.float64, hessian="pallas")


def test_target_platform_follows_default_device():
    """A CPU reference inside a TPU process (jax.default_device(cpu)) is
    routed as CPU: the target is the data's device, then the default
    device, then the default backend."""
    cpu = jax.devices("cpu")[0]
    with jax.default_device(cpu):
        assert ops.target_platform() == "cpu"
    assert ops.target_platform(jnp.ones(3)) == jax.devices()[0].platform
