"""Matrix Product Operator (MPO) decomposition — the paper's core primitive.

Implements Algorithm 1 (sequential-SVD MPO decomposition), bond truncation
(Eq. 3/4 truncation errors), compression ratio (Eq. 5), entanglement entropy
(Eq. 6), TT-rounding (used by dimension squeezing, Alg. 2), and the two
execution paths for ``y = x @ MPO(W)``:

  * ``apply_mpo``   — factorized sequential contraction (paper-faithful,
                      Table 2 complexity O(n m d^3));
  * ``reconstruct`` — materialize W once, then dense MXU matmul (beyond-paper
                      fast path for compute-bound shapes).

Conventions
-----------
A matrix ``M[I, J]`` with ``I = prod(in_factors)``, ``J = prod(out_factors)``
is decomposed into ``n`` 4-order cores ``T_k[d_{k-1}, i_k, j_k, d_k]`` with
``d_0 = d_n = 1``.  Row/col indices are row-major:
``I-index = (((i_1) * i_2 + ...) * i_n + i_n)``.  The *central* core is
``k = n // 2`` (0-based); the rest are *auxiliary*.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

# --------------------------------------------------------------------------
# factorization utilities
# --------------------------------------------------------------------------


def _prime_factors(n: int) -> list[int]:
    out, d = [], 2
    while d * d <= n:
        while n % d == 0:
            out.append(d)
            n //= d
        d += 1
    if n > 1:
        out.append(n)
    return out


def auto_factorize(n: int, parts: int = 5, multiple: int = 1,
                   multiple_index: int = 0) -> tuple[int, ...]:
    """Split ``n`` into ``parts`` balanced integer factors (product == n).

    ``multiple`` forces ``slots[multiple_index]`` to be divisible by the
    given value, so that leg of the corresponding MPO core can be sharded
    over the ``model`` mesh axis (GSPMD tiling divisibility).  The sharded
    leg lives on the FIRST core (index 0): row-major index order then makes
    the sharded factor the outermost I/J digit, i.e. the reconstructed W is
    tiled in clean contiguous row/column blocks — no resharding reshape
    (observed as 17 GiB/step of all-gathers when the central leg was sharded
    instead; see EXPERIMENTS §Perf).
    """
    if n % multiple != 0:
        raise ValueError(f"multiple {multiple} must divide {n}")
    slots = [1] * parts
    slots[multiple_index] = multiple
    rest = n // multiple
    for p in sorted(_prime_factors(rest), reverse=True):
        # multiply into the currently-smallest slot -> balanced factors
        k = min(range(parts), key=lambda i: slots[i])
        slots[k] *= p
    assert math.prod(slots) == n
    return tuple(slots)


# --------------------------------------------------------------------------
# spec
# --------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class MPOSpec:
    """Static description of one MPO-factorized matrix."""

    in_factors: tuple[int, ...]
    out_factors: tuple[int, ...]
    bond_dim: int | None = None  # max bond dimension (None = exact / full rank)

    def __post_init__(self):
        if len(self.in_factors) != len(self.out_factors):
            raise ValueError("in/out factor lists must have equal length")

    @property
    def n(self) -> int:
        return len(self.in_factors)

    @property
    def in_dim(self) -> int:
        return math.prod(self.in_factors)

    @property
    def out_dim(self) -> int:
        return math.prod(self.out_factors)

    @property
    def central_index(self) -> int:
        return self.n // 2

    def full_bonds(self) -> tuple[int, ...]:
        """Exact (untruncated) bond dims d_1..d_{n-1} per Eq. (2)."""
        n = self.n
        bonds = []
        for k in range(1, n):
            left = math.prod(self.in_factors[:k]) * math.prod(self.out_factors[:k])
            right = math.prod(self.in_factors[k:]) * math.prod(self.out_factors[k:])
            bonds.append(min(left, right))
        return tuple(bonds)

    def bonds(self) -> tuple[int, ...]:
        full = self.full_bonds()
        if self.bond_dim is None:
            return full
        return tuple(min(b, self.bond_dim) for b in full)

    def core_shapes(self) -> list[tuple[int, int, int, int]]:
        b = (1,) + self.bonds() + (1,)
        return [
            (b[k], self.in_factors[k], self.out_factors[k], b[k + 1])
            for k in range(self.n)
        ]

    def num_params(self) -> int:
        return sum(math.prod(s) for s in self.core_shapes())

    def compression_ratio(self) -> float:
        """rho of Eq. (5): MPO params / original matrix params."""
        return self.num_params() / (self.in_dim * self.out_dim)

    @staticmethod
    def make(in_dim: int, out_dim: int, *, n: int = 5, bond_dim: int | None = None,
             in_multiple: int = 1, out_multiple: int = 1) -> "MPOSpec":
        return MPOSpec(
            in_factors=auto_factorize(in_dim, n, in_multiple, 0),
            out_factors=auto_factorize(out_dim, n, out_multiple, 0),
            bond_dim=bond_dim,
        )


# --------------------------------------------------------------------------
# decomposition (Algorithm 1)
# --------------------------------------------------------------------------


def _interleave_perm(n: int) -> list[int]:
    """(i1..in, j1..jn) -> (i1, j1, i2, j2, ...)."""
    perm = []
    for k in range(n):
        perm += [k, n + k]
    return perm


def decompose(matrix: jax.Array, spec: MPOSpec):
    """Algorithm 1: sequential-SVD MPO decomposition with bond truncation.

    Returns ``(cores, spectra)`` where ``spectra[k]`` holds the *pre-truncation*
    singular values seen at bond ``k`` (used for Eq. 3 errors, Eq. 6 entropy and
    dimension-squeezing candidate selection).
    """
    n = spec.n
    m = jnp.asarray(matrix, jnp.float32)
    if m.shape != (spec.in_dim, spec.out_dim):
        raise ValueError(f"matrix {m.shape} != spec ({spec.in_dim},{spec.out_dim})")
    t = m.reshape(spec.in_factors + spec.out_factors).transpose(_interleave_perm(n))
    bonds = spec.bonds()
    cores, spectra = [], []
    d_prev = 1
    rem = t.reshape(-1)
    for k in range(n - 1):
        rows = d_prev * spec.in_factors[k] * spec.out_factors[k]
        mat = rem.reshape(rows, -1)
        u, s, vt = jnp.linalg.svd(mat, full_matrices=False)
        dk = min(bonds[k], s.shape[0])
        spectra.append(s)
        cores.append(u[:, :dk].reshape(d_prev, spec.in_factors[k], spec.out_factors[k], dk))
        rem = (s[:dk, None] * vt[:dk]).reshape(-1)
        d_prev = dk
    cores.append(rem.reshape(d_prev, spec.in_factors[-1], spec.out_factors[-1], 1))
    return cores, spectra


def reconstruct(cores: Sequence[jax.Array]) -> jax.Array:
    """Contract cores back to the (approximate) matrix ``W[I, J]``.

    The chain runs from the LAST core to the first, keeping the partial
    product as ``T[d, (i_k..i_n), (j_k..j_n)]``: each step is one matmul
    and one transpose whose minor dim is the merged trailing-j block, which
    grows with every step.  An interleaved ``(i1, j1, ..., in, jn)`` staging
    would instead hold W-sized tensors whose minor dim is the single last
    factor ``jn``; a TPU pads that dim to 128 lanes (a 6-wide factor costs
    21x the bytes, more than a chip holds for a vocabulary head).

    Core 0's i/j legs are contracted last and stay the outermost digits of
    W's rows and columns, so a TP-sharded core 0 (``layers._core_axes``)
    yields contiguous row/column tiles with every matmul local.
    """
    t = cores[-1][..., 0]                          # (d, i_n, j_n)
    for c in reversed(cores[:-1]):
        d, i, j, e = c.shape
        _, ir, jr = t.shape
        b = c.reshape(d * i * j, e) @ t.reshape(e, ir * jr)
        t = (b.reshape(d, i, j, ir, jr).transpose(0, 1, 3, 2, 4)
             .reshape(d, i * ir, j * jr))
    return t[0]


# --------------------------------------------------------------------------
# factorized application (paper's inference path)
# --------------------------------------------------------------------------


def apply_mpo(cores: Sequence[jax.Array], x: jax.Array,
              precision=jax.lax.Precision.DEFAULT) -> jax.Array:
    """``y[..., J] = x[..., I] @ W`` without materializing ``W``.

    Sequential contraction; each step is a single matmul of shape
    ``(Beff*rest, d0*ik) x (d0*ik, jk*d1)`` — MXU-friendly when bonds are
    reasonably sized.
    """
    outs = [c.shape[2] for c in cores]
    lead = x.shape[:-1]
    b = math.prod(lead) if lead else 1
    h = x.reshape(b, 1, -1)  # (Beff, d0, rest)
    for c in cores:
        d0, ik, jk, d1 = c.shape
        beff = h.shape[0]
        rest = h.shape[2] // ik
        h = h.reshape(beff, d0, ik, rest)
        h = jnp.einsum("bdir,dijc->bjcr", h, c, precision=precision)
        h = h.reshape(beff * jk, d1, rest)
    return h.reshape(*lead, math.prod(outs))


def transpose_cores(cores: Sequence[jax.Array]) -> list[jax.Array]:
    """Cores of ``W^T`` (swap the i/j legs of every core)."""
    return [c.transpose(0, 2, 1, 3) for c in cores]


def apply_mpo_t(cores: Sequence[jax.Array], x: jax.Array, **kw) -> jax.Array:
    """``y[..., I] = x[..., J] @ W^T`` (e.g. tied-embedding logits)."""
    return apply_mpo(transpose_cores(cores), x, **kw)


def embed_lookup(cores: Sequence[jax.Array], ids: jax.Array) -> jax.Array:
    """Row lookup ``W[ids, :]`` from a factorized embedding table.

    ``ids`` is decomposed into mixed-radix digits over ``in_factors``; each
    digit selects a row-slice of its core via a *one-hot matmul* (not a
    gather — GSPMD propagates batch sharding through dots but resorts to full
    rematerialization on million-row gathers), chained with small batched
    matmuls.  The full ``[vocab, d]`` table never materializes.
    """
    from repro.parallel.ctx import shard_batch_dim  # lazy: avoid cycle
    ins = [c.shape[1] for c in cores]
    lead = ids.shape
    flat = ids.reshape(-1)
    # mixed-radix digits, most-significant first (row-major I index)
    digits = []
    rem = flat
    for base in reversed(ins):
        digits.append(rem % base)
        rem = rem // base
    digits = digits[::-1]
    dt = cores[0].dtype
    # h: (B, j_so_far, d_k), batch dim kept sharded throughout
    oh0 = jax.nn.one_hot(digits[0], ins[0], dtype=dt)
    h = jnp.einsum("bi,ije->bje", oh0, cores[0][0])
    h = shard_batch_dim(h)
    for k in range(1, len(cores)):
        oh = jax.nn.one_hot(digits[k], ins[k], dtype=dt)
        sel = shard_batch_dim(jnp.einsum("bi,dije->bdje", oh, cores[k]))
        h = shard_batch_dim(jnp.einsum("bxd,bdje->bxje", h, sel))
        h = shard_batch_dim(h.reshape(h.shape[0], -1, h.shape[-1]))
    return h[..., 0].reshape(*lead, -1)


# --------------------------------------------------------------------------
# reconstruct-mode matmul with core-space gradient reduction
# --------------------------------------------------------------------------


@jax.custom_vjp
def matmul_reconstruct(x: jax.Array, cores: tuple) -> jax.Array:
    """``x @ reconstruct(cores)`` — dense-MXU forward, *factorized* backward.

    The naive backward materializes the dense ``dW = x^T dy`` and all-reduces
    it across the data axis before projecting into the tiny cores — a
    dense-model-sized gradient all-reduce per layer (measured 212 GB/device/
    step on qwen3 train_4k) that erases the paper's compression win.

    Mitigations (taking the VJP through the factorized chain instead was
    measured 300x worse in FLOPs — chain intermediates shard badly):
      * ``dW`` is cast to bf16 before the cross-shard reduction (2x bytes);
      * its rows are sharding-constrained over the batch axes, turning the
        all-reduce into a reduce-scatter (2x again); the subsequent local
        projection to core-space emits only small core-grad all-reduces.
    """
    return x @ reconstruct(list(cores))


def _mm_recon_fwd(x, cores):
    return x @ reconstruct(list(cores)), (x, cores)


def _project_dw(cores, x, dy):
    """dcores from local tokens: dW = x^T dy projected into core-space.

    The token contraction is an einsum over the *unflattened* leading dims —
    reshaping (B, S, D) -> (B*S, D) first merges a possibly seq-sharded dim
    into a strided layout GSPMD can't tile, forcing 4 GB full-activation
    all-gathers in the remat backward (§Perf it.15).
    """
    dw = jnp.einsum("...i,...j->ij", x, dy)
    _, vjp = jax.vjp(lambda cs: reconstruct(list(cs)), cores)
    (dcores,) = vjp(dw.astype(cores[0].dtype))
    return dcores


def _mm_recon_bwd(res, dy):
    x, cores = res
    w = reconstruct(list(cores))          # recompute (cheap: O(params*d'))
    dx = dy @ w.T

    # NOTE (§Perf it.7): a shard_map-scoped variant that projects each data
    # shard's partial dW into core-space locally and psums only the
    # compressed core grads (killing the dense dW all-reduce entirely) is
    # the right play on real TPUs, but the XLA *host* backend CHECK-crashes
    # compiling shard_map inside custom_vjp-inside-remat-inside-scan
    # ("Invalid binary instruction opcode copy"), so it cannot be validated
    # in this container and is not shipped.
    dcores = _project_dw(cores, x.astype(jnp.bfloat16),
                         dy.astype(jnp.bfloat16))
    return dx, dcores


matmul_reconstruct.defvjp(_mm_recon_fwd, _mm_recon_bwd)


# --------------------------------------------------------------------------
# truncation errors / entropy (Eq. 3, 4, 6)
# --------------------------------------------------------------------------


def local_truncation_error(spectrum: jax.Array, keep: int) -> jax.Array:
    """eps_k — Frobenius-optimal local truncation error at one bond.

    Note: the paper's Eq. (3) writes a plain sum of discarded singular values;
    the Eckart–Young quantity entering the Eq. (4) bound is the l2 norm of the
    discarded tail, which is what we compute (``paper_epsilon`` gives the
    literal Eq. (3) sum).
    """
    tail = spectrum[keep:]
    return jnp.sqrt(jnp.sum(tail * tail))


def paper_epsilon(spectrum: jax.Array, keep: int) -> jax.Array:
    """Literal Eq. (3): sum of discarded singular values."""
    return jnp.sum(spectrum[keep:])


def total_error_bound(spectra: Sequence[jax.Array], keeps: Sequence[int]) -> jax.Array:
    """Eq. (4) right-hand side: sqrt(sum_k eps_k^2)."""
    eps2 = [local_truncation_error(s, k) ** 2 for s, k in zip(spectra, keeps)]
    return jnp.sqrt(sum(eps2))


def entanglement_entropy(spectrum: jax.Array) -> jax.Array:
    """Eq. (6): S = -sum v ln v with v = normalized singular values."""
    v = spectrum / jnp.sum(spectrum)
    return -jnp.sum(jnp.where(v > 0, v * jnp.log(jnp.where(v > 0, v, 1.0)), 0.0))


# --------------------------------------------------------------------------
# TT-rounding (used by dimension squeezing on *trained* cores)
# --------------------------------------------------------------------------


def right_orthogonalize(cores: Sequence[jax.Array]) -> list[jax.Array]:
    """Sweep n..2 making every core right-orthogonal (LQ decomposition)."""
    cores = [jnp.asarray(c, jnp.float32) for c in cores]
    out = list(cores)
    for k in range(len(cores) - 1, 0, -1):
        c = out[k]
        d0 = c.shape[0]
        m = c.reshape(d0, -1)
        # LQ via QR of the transpose: m = (q r)^T = r^T q^T
        q, r = jnp.linalg.qr(m.T)
        out[k] = q.T.reshape((q.shape[1],) + c.shape[1:])
        prev = out[k - 1]
        out[k - 1] = jnp.einsum("aijb,cb->aijc", prev, r)
    return out


def bond_spectra(cores: Sequence[jax.Array]) -> list[jax.Array]:
    """Singular values at every bond of the *current* (possibly trained) MPO."""
    cs = right_orthogonalize(cores)
    spectra = []
    carry = None
    for k in range(len(cs) - 1):
        c = cs[k] if carry is None else jnp.einsum("ab,bijc->aijc", carry, cs[k])
        m = c.reshape(-1, c.shape[-1])
        u, s, vt = jnp.linalg.svd(m, full_matrices=False)
        spectra.append(s)
        carry = (s[:, None] * vt)
    return spectra


def tt_round(cores: Sequence[jax.Array], new_bonds: Sequence[int]):
    """Truncate an existing MPO to ``new_bonds`` (Oseledets TT-rounding).

    Right-orthogonalize, then left->right truncated-SVD sweep.  Returns
    ``(new_cores, spectra)`` where spectra are the pre-truncation singular
    values at each bond (feeds Eq. 3/4 and squeeze-candidate selection).
    """
    cs = right_orthogonalize(cores)
    n = len(cs)
    out = []
    spectra = []
    carry = None
    for k in range(n - 1):
        c = cs[k] if carry is None else jnp.einsum("ab,bijc->aijc", carry, cs[k])
        d0, ik, jk, d1 = c.shape
        m = c.reshape(d0 * ik * jk, d1)
        u, s, vt = jnp.linalg.svd(m, full_matrices=False)
        spectra.append(s)
        dk = min(int(new_bonds[k]), s.shape[0])
        out.append(u[:, :dk].reshape(d0, ik, jk, dk))
        carry = s[:dk, None] * vt[:dk]
    last = cs[-1] if carry is None else jnp.einsum("ab,bijc->aijc", carry, cs[-1])
    out.append(last)
    return out, spectra


# --------------------------------------------------------------------------
# initialization (training from scratch in MPO form)
# --------------------------------------------------------------------------


def init_cores(key: jax.Array, spec: MPOSpec, *, scale: float | None = None,
               dtype=jnp.float32) -> list[jax.Array]:
    """Random cores such that ``reconstruct(cores)`` has fan-in variance.

    Entry of W sums ``prod(bonds)`` independent products of ``n`` core entries,
    so per-core std ``sigma = (var_W / prod(bonds)) ** (1 / (2n))``.
    """
    shapes = spec.core_shapes()
    var_w = (scale ** 2) if scale is not None else 1.0 / spec.in_dim
    prod_bonds = math.prod(spec.bonds()) if spec.n > 1 else 1.0
    sigma = (var_w / prod_bonds) ** (1.0 / (2 * spec.n))
    keys = jax.random.split(key, spec.n)
    return [sigma * jax.random.normal(k, s, dtype) for k, s in zip(keys, shapes)]


def count_params(cores: Sequence[jax.Array]) -> int:
    return sum(int(np.prod(c.shape)) for c in cores)
