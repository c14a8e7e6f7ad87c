"""Unit + property tests for the MPO core (paper §3, Algorithm 1, Eq. 2-6).

``hypothesis`` is optional — the property tests run through the
hypothesis-or-fixed-seed shim in ``tests/conftest.py`` (fixed-seed example
tests when hypothesis is not installed)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import given, settings, st
from repro.core import mpo

jax.config.update("jax_enable_x64", False)


def _rand(shape, seed=0):
    return jax.random.normal(jax.random.PRNGKey(seed), shape)


# ---------------------------------------------------------------- Algorithm 1


@pytest.mark.parametrize("n", [2, 3, 5])
@pytest.mark.parametrize("dims", [(24, 36), (64, 64), (60, 96)])
def test_exact_reconstruction(n, dims):
    m = _rand(dims)
    spec = mpo.MPOSpec.make(*dims, n=n)
    cores, _ = mpo.decompose(m, spec)
    np.testing.assert_allclose(np.asarray(mpo.reconstruct(cores)),
                               np.asarray(m), atol=2e-4)


def test_core_shapes_match_spec():
    spec = mpo.MPOSpec.make(120, 96, n=5, bond_dim=7)
    cores, _ = mpo.decompose(_rand((120, 96)), spec)
    for c, s in zip(cores, spec.core_shapes()):
        assert c.shape == s
    assert spec.core_shapes()[0][0] == 1 and spec.core_shapes()[-1][-1] == 1


def test_bond_dims_eq2():
    """Eq. (2): d_k = min(prod left, prod right)."""
    spec = mpo.MPOSpec(in_factors=(2, 3, 4), out_factors=(3, 2, 4))
    # d1 = min(2*3, 3*4*2*4) = 6 ; d2 = min(2*3*3*2, 4*4) = 16
    assert spec.full_bonds() == (6, 16)


def test_apply_matches_matmul():
    m = _rand((48, 60))
    spec = mpo.MPOSpec.make(48, 60, n=3)
    cores, _ = mpo.decompose(m, spec)
    x = _rand((9, 48), 1)
    np.testing.assert_allclose(np.asarray(mpo.apply_mpo(cores, x)),
                               np.asarray(x @ m), atol=2e-4)
    z = _rand((5, 60), 2)
    np.testing.assert_allclose(np.asarray(mpo.apply_mpo_t(cores, z)),
                               np.asarray(z @ m.T), atol=2e-4)


def test_embed_lookup():
    m = _rand((120, 32))
    spec = mpo.MPOSpec.make(120, 32, n=3)
    cores, _ = mpo.decompose(m, spec)
    ids = jnp.array([[0, 1], [7, 119]])
    np.testing.assert_allclose(np.asarray(mpo.embed_lookup(cores, ids)),
                               np.asarray(m[ids]), atol=2e-4)


# ------------------------------------------------------------- Eq. 3/4 bounds


@pytest.mark.parametrize("bond", [2, 4, 8])
def test_truncation_error_bound_eq4(bond):
    m = _rand((48, 64), 3)
    spec = mpo.MPOSpec(mpo.auto_factorize(48, 3), mpo.auto_factorize(64, 3),
                       bond_dim=bond)
    cores, spectra = mpo.decompose(m, spec)
    err = float(jnp.linalg.norm(mpo.reconstruct(cores) - m))
    keeps = [min(bond, len(s)) for s in spectra]
    bound = float(mpo.total_error_bound(spectra, keeps))
    assert err <= bound + 1e-3


def test_truncation_error_monotone_in_bond():
    m = _rand((48, 64), 4)
    errs = []
    for bond in (2, 4, 8, 16):
        spec = mpo.MPOSpec(mpo.auto_factorize(48, 3),
                           mpo.auto_factorize(64, 3), bond_dim=bond)
        cores, _ = mpo.decompose(m, spec)
        errs.append(float(jnp.linalg.norm(mpo.reconstruct(cores) - m)))
    assert errs == sorted(errs, reverse=True)


def test_compression_ratio_eq5():
    spec = mpo.MPOSpec((2, 3, 4), (3, 2, 4), bond_dim=2)
    # rho = sum d'_{k-1} i_k j_k d'_k / prod i_k j_k
    num = 1 * 2 * 3 * 2 + 2 * 3 * 2 * 2 + 2 * 4 * 4 * 1
    assert spec.compression_ratio() == num / (24 * 24)


# ------------------------------------------------------------------ entropy


def test_entropy_increases_with_spread():
    flat = jnp.ones(8)
    peaked = jnp.array([100.0] + [1e-6] * 7)
    assert float(mpo.entanglement_entropy(flat)) > \
        float(mpo.entanglement_entropy(peaked))


def test_central_bond_has_max_entropy():
    """Paper §4.1: the central tensor carries the largest entanglement."""
    m = _rand((64, 64), 5)
    spec = mpo.MPOSpec.make(64, 64, n=5)
    _, spectra = mpo.decompose(m, spec)
    ents = [float(mpo.entanglement_entropy(s)) for s in spectra]
    assert max(ents) == max(ents[1:3])  # one of the middle bonds


# ---------------------------------------------------------------- tt_round


def test_tt_round_matches_direct_truncation():
    m = _rand((48, 64), 6)
    spec_full = mpo.MPOSpec.make(48, 64, n=3)
    cores, _ = mpo.decompose(m, spec_full)
    rounded, _ = mpo.tt_round(cores, [4, 4])
    spec_t = mpo.MPOSpec(spec_full.in_factors, spec_full.out_factors,
                         bond_dim=4)
    direct, _ = mpo.decompose(m, spec_t)
    e1 = float(jnp.linalg.norm(mpo.reconstruct(rounded) - m))
    e2 = float(jnp.linalg.norm(mpo.reconstruct(direct) - m))
    assert abs(e1 - e2) < 1e-3


def test_right_orthogonalize_preserves_product():
    m = _rand((24, 36), 7)
    cores, _ = mpo.decompose(m, mpo.MPOSpec.make(24, 36, n=3))
    ortho = mpo.right_orthogonalize(cores)
    np.testing.assert_allclose(np.asarray(mpo.reconstruct(ortho)),
                               np.asarray(mpo.reconstruct(cores)), atol=2e-4)


# ------------------------------------------------------------ custom VJP


def test_matmul_reconstruct_grads():
    spec = mpo.MPOSpec.make(48, 96, n=3, bond_dim=8)
    cores = tuple(mpo.init_cores(jax.random.PRNGKey(0), spec))
    x = _rand((7, 48), 1)
    g1 = jax.grad(lambda x, c: jnp.sum(jnp.sin(mpo.matmul_reconstruct(x, c))),
                  argnums=(0, 1))(x, cores)
    g2 = jax.grad(lambda x, c: jnp.sum(jnp.sin(x @ mpo.reconstruct(list(c)))),
                  argnums=(0, 1))(x, cores)
    for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g2)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-2,
                                   rtol=5e-2)


# ------------------------------------------------------------ property-based


@settings(max_examples=25, deadline=None)
@given(st.integers(2, 64).map(lambda k: 2 * k),
       st.integers(2, 64).map(lambda k: 2 * k),
       st.integers(2, 5))
def test_prop_factorize_product(i, j, n):
    fi = mpo.auto_factorize(i, n)
    fj = mpo.auto_factorize(j, n)
    assert int(np.prod(fi)) == i and int(np.prod(fj)) == j


@settings(max_examples=10, deadline=None)
@given(st.integers(1, 6), st.integers(1, 6), st.integers(0, 10))
def test_prop_exact_roundtrip(a, b, seed):
    i, j = 4 * a, 4 * b
    m = _rand((i, j), seed)
    cores, _ = mpo.decompose(m, mpo.MPOSpec.make(i, j, n=3))
    assert float(jnp.max(jnp.abs(mpo.reconstruct(cores) - m))) < 1e-3


@settings(max_examples=10, deadline=None)
@given(st.integers(0, 5), st.integers(1, 8))
def test_prop_truncated_error_never_exceeds_bound(seed, bond):
    m = _rand((32, 48), seed + 100)
    spec = mpo.MPOSpec(mpo.auto_factorize(32, 3), mpo.auto_factorize(48, 3),
                       bond_dim=bond)
    cores, spectra = mpo.decompose(m, spec)
    err = float(jnp.linalg.norm(mpo.reconstruct(cores) - m))
    bound = float(mpo.total_error_bound(
        spectra, [min(bond, len(s)) for s in spectra]))
    assert err <= bound + 1e-3


@settings(max_examples=10, deadline=None)
@given(st.integers(0, 5))
def test_prop_multiple_divides_factor(seed):
    dims = [(64, 4), (128, 8), (96, 16), (256, 16)][seed % 4]
    n, mult = dims
    f = mpo.auto_factorize(n, 5, mult, 0)
    assert f[0] % mult == 0


@settings(max_examples=8, deadline=None)
@given(st.integers(0, 4))
def test_prop_entropy_monotone_in_bond_truncation(seed):
    """Keeping more singular values never lowers the Eq.3 local error; the
    entropy of the spectrum upper-bounds any truncated sub-spectrum's."""
    s = jnp.sort(jnp.abs(_rand((16,), seed)))[::-1]
    errs = [float(mpo.local_truncation_error(s, k)) for k in range(1, 16)]
    assert errs == sorted(errs, reverse=True)


@settings(max_examples=8, deadline=None)
@given(st.integers(0, 4), st.integers(2, 6))
def test_prop_tt_round_never_increases_params(seed, bond):
    m = _rand((32, 48), seed + 50)
    cores, _ = mpo.decompose(m, mpo.MPOSpec.make(32, 48, n=3))
    before = sum(int(np.prod(c.shape)) for c in cores)
    rounded, _ = mpo.tt_round(cores, [bond, bond])
    after = sum(int(np.prod(c.shape)) for c in rounded)
    assert after <= before


@settings(max_examples=8, deadline=None)
@given(st.integers(0, 4))
def test_prop_reconstruct_stagings_agree(seed):
    """The right-to-left chain equals the interleaved left-to-right
    staging: all cores contracted over their bonds into
    ``(i1, j1, ..., in, jn)``, then de-interleaved to ``(I, J)``."""
    m = _rand((24, 40), seed + 9)
    cores, _ = mpo.decompose(m, mpo.MPOSpec.make(24, 40, n=4, bond_dim=5))
    acc = cores[0].reshape(-1, cores[0].shape[-1])
    for c in cores[1:]:
        acc = (acc @ c.reshape(c.shape[0], -1)).reshape(-1, c.shape[-1])
    t = acc.reshape([d for c in cores for d in c.shape[1:3]])
    t = t.transpose([0, 2, 4, 6, 1, 3, 5, 7]).reshape(24, 40)
    np.testing.assert_allclose(np.asarray(mpo.reconstruct(cores)),
                               np.asarray(t), atol=1e-5)
