"""The MLN collective entity matcher (paper §2.1, Appendix B) in JAX.

The matcher is the paper's Markov-Logic-Network matcher [Singla & Domingos
2006] restricted to the monotone/supermodular rule class of Appendix A
(Prop. 4: a single ``Match`` term in each implicant) — the exact class for
which the paper's soundness theory holds.

Grounding.  For a neighborhood with entity slots ``0..k-1`` and candidate
pairs ``p = (i, j)`` on the upper triangle (``P = k(k-1)/2`` slots), the
rule set (Appendix B)::

    similar(e1,e2,L)  => equals(e1,e2)                      w_sim[L]
    coauthor(e1,c1) & coauthor(e2,c2) & equals(c1,c2)
                      => equals(e1,e2)                      w_co

grounds to a supermodular pseudo-Boolean objective over x in {0,1}^P ::

    f(x) = sum_p u_p x_p  +  1/2 sum_{p != q} C_pq x_p x_q

    u_p  = w_sim[level_p] + w_co * n_shared(p)      (reflexive Match(d,d))
    C_pq = w_co * link(p, q)

where ``n_shared(p)`` counts shared coauthors of the pair and
``link(p, q)`` is 1 iff matching q fires the coauthor rule for p (one
firing per unordered coupled pair — this follows the paper's §2.1/§2.2
arithmetic: the -10 + 8 and -15 + 16 examples).  All couplings are
nonnegative, hence ``P(S) ~ exp f(S)`` is supermodular (Def. 6) and the
matcher is monotone Type-I (Prop. 2).

MAP inference (the Alchemy/MaxWalkSAT replacement — see DESIGN §3).
TPU-native, branch-free, fixed shape:

  1. *closure*: repeated conditional-delta sweeps ``delta = u + C @ x``
     activating every pair with positive delta (monotone; never
     deactivates) — ``jax.lax.while_loop`` of batched mat-vecs.
  2. *collective promotion*: connected components of the mutual
     entailment graph among still-inactive pairs (the same graph
     COMPUTEMAXIMAL builds), greedily *peeled* of negative-marginal
     members, then activated wholesale when the joint delta is >= 0
     (ties prefer the larger set, per the Type-II output definition).
  3. repeat 1+2 to fixpoint.

Step 2 is what makes the matcher *purely collective* (the paper's
{(a1,a2),(b2,b3),(c2,c3)} chain matches jointly even though every single
pair has negative delta).  The entailment matrix is one (P,P)@(P,P)
matmul per sweep — MXU work, backed by the ``mln_score``/``icm_sweep``
Pallas kernels on TPU.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import pairs as pairlib
from repro.core.types import NeighborhoodBatch
from repro.kernels.icm_sweep import ops as icm_ops
from repro.kernels.mln_score import ops as score_ops

NEG = -1.0e9  # unary for invalid / padded pairs
TIE_EPS = 1.0e-5  # "delta >= 0" tolerance (largest-tie preference)


@dataclasses.dataclass(frozen=True)
class MLNWeights:
    """Rule weights. w_sim[0] unused (level 0 = not a candidate)."""

    w_sim: tuple[float, float, float, float]
    w_co: float

    def as_arrays(self):
        return (
            jnp.asarray(self.w_sim, dtype=jnp.float32),
            jnp.float32(self.w_co),
        )


# Appendix B, learned with Alchemy on the bibliographic data.
PAPER_LEARNED = MLNWeights(w_sim=(0.0, -2.28, -3.84, 12.75), w_co=2.46)
# §2.1 pedagogical weights (R1 = -5, R2 = +8), used by the Fig. 1/2 tests.
PEDAGOGICAL = MLNWeights(w_sim=(0.0, -5.0, -5.0, -5.0), w_co=8.0)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class Grounding:
    """Dense grounded MLN for a batch of neighborhoods."""

    u: jax.Array  # (B, P) f32, NEG where invalid
    u_raw: jax.Array  # (B, P) f32, 0 where invalid (for scoring)
    C: jax.Array  # (B, P, P) f32, symmetric, zero diag, >= 0
    valid: jax.Array  # (B, P) bool

    def tree_flatten(self):
        return (self.u, self.u_raw, self.C, self.valid), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


def ground_structure(batch: NeighborhoodBatch, slot_i=None, slot_j=None):
    """Weight-independent grounded structure of a neighborhood batch.

    ``slot_i``/``slot_j`` (B, P) int give the entity slots of each pair
    slot of each row; by default every row holds the upper triangle
    (``pairlib.triu_indices(k)``).  The round engine passes compacted
    per-row slots (candidate pairs first, inert padding after), so
    ``P`` is the staged width, not k(k-1)/2.

    Returns (lev, valid, n_shared, link):
      lev      (B, P) int32   similarity level (0 = not a candidate)
      valid    (B, P) bool    candidate-pair validity
      n_shared (B, P) f32     shared-coauthor count (reflexive Match(d,d))
      link     (B, P, P) f32  1 iff matching q fires the coauthor rule
                              for p (zero diagonal, masked to valid pairs)
    Shared by the MLN (weights applied on top) and RULES matchers.
    """
    k = batch.k
    B = batch.entity_mask.shape[0]
    if slot_i is None:
        ii, jj = pairlib.triu_indices(k)
        slot_i = np.broadcast_to(ii, (B, len(ii)))
        slot_j = np.broadcast_to(jj, (B, len(jj)))
    # one-hot entity selectors: sel_i[b, p, a] = 1 iff a == slot_i[b, p]
    slots = jnp.arange(k, dtype=jnp.int32)
    sel_i = (jnp.asarray(slot_i)[:, :, None] == slots).astype(jnp.float32)
    sel_j = (jnp.asarray(slot_j)[:, :, None] == slots).astype(jnp.float32)

    co = jnp.asarray(batch.coauthor, dtype=jnp.float32)  # (B, k, k)
    # Defensive: no self-coauthorship, no padded-slot edges.
    emask = jnp.asarray(batch.entity_mask, dtype=jnp.float32)
    co = co * emask[:, :, None] * emask[:, None, :]
    co = co * (1.0 - jnp.eye(k, dtype=jnp.float32))

    lev = jnp.asarray(batch.sim_level, dtype=jnp.int32)  # (B, P)
    valid = jnp.asarray(batch.pair_mask) & (lev > 0)

    # Reflexive boost: n_shared[b, p] = |{d : co(i,d) & co(j,d)}|.
    shared = jnp.einsum("bid,bjd->bij", co, co)  # (B, k, k) counts
    n_shared = jnp.sum(
        jnp.einsum("bpa,bac->bpc", sel_i, shared) * sel_j, axis=2
    )  # (B, P): shared[i_p, j_p]
    n_shared = jnp.where(valid, n_shared, 0.0)

    # Couplings: link(p, q) = (co[ip,iq] & co[jp,jq]) | (co[ip,jq] & co[jp,iq])
    # Every selection is a 0/1 product with one nonzero term: exact.
    co_i = jnp.einsum("bpa,bac->bpc", sel_i, co)  # (B, P, k) co[i_p, :]
    co_j = jnp.einsum("bpa,bac->bpc", sel_j, co)  # (B, P, k) co[j_p, :]
    co_ii = jnp.einsum("bpc,bqc->bpq", co_i, sel_i)  # (B, P, P): co[i_p, i_q]
    co_jj = jnp.einsum("bpc,bqc->bpq", co_j, sel_j)  # co[j_p, j_q]
    co_ij = jnp.einsum("bpc,bqc->bpq", co_i, sel_j)  # co[i_p, j_q]
    co_ji = jnp.einsum("bpc,bqc->bpq", co_j, sel_i)  # co[j_p, i_q]
    link = jnp.clip(co_ii * co_jj + co_ij * co_ji, 0.0, 1.0)
    vf = valid.astype(jnp.float32)
    pmask2 = vf[:, :, None] * vf[:, None, :]
    P = lev.shape[1]
    link = link * pmask2 * (1.0 - jnp.eye(P, dtype=jnp.float32))
    return lev, valid, n_shared, link


def ground(
    batch: NeighborhoodBatch, weights: MLNWeights, slot_i=None, slot_j=None
) -> Grounding:
    """Ground the MLN rules on a padded neighborhood batch (jnp); the
    pair layout is :func:`ground_structure`'s."""
    w_sim, w_co = weights.as_arrays()
    lev, valid, n_shared, link = ground_structure(batch, slot_i, slot_j)

    u_raw = jnp.take(w_sim, lev) + w_co * n_shared
    u_raw = jnp.where(valid, u_raw, 0.0)
    u = jnp.where(valid, u_raw, NEG)
    C = w_co * link

    return Grounding(u=u, u_raw=u_raw, C=C, valid=valid)


# ---------------------------------------------------------------------------
# Inference primitives (single neighborhood; vmapped over the batch)
# ---------------------------------------------------------------------------


def _closure(u, C, ev_pos, ev_neg, valid):
    """Monotone greedy closure from ev_pos; ev_neg frozen off. (P,) bool."""
    x0 = ev_pos & valid & ~ev_neg

    def cond(state):
        _, changed = state
        return changed

    def body(state):
        x, _ = state
        delta = icm_ops.sweep(u, C, x.astype(jnp.float32))
        # ">= -TIE_EPS": zero-delta additions keep the score and the
        # Type-II output prefers the larger set among ties.  Sound for
        # supermodular f: marginal(p | x) >= 0 and x subset of the optimum
        # O imply marginal(p | O) >= 0, hence p in O (tie-larger unique O).
        new = (delta >= -TIE_EPS) & valid & ~ev_neg
        x2 = x | new | (ev_pos & valid)
        return x2, jnp.any(x2 != x)

    x, _ = jax.lax.while_loop(cond, body, (x0, jnp.bool_(True)))
    return x


def closure_batch(u, C, ev_pos, ev_neg, valid):
    """Monotone greedy closure for a whole bin in one ``while_loop``.

    All arguments are batched ``(B, P)`` / ``(B, P, P)``; each iteration
    is a single batched conditional-delta sweep (``icm_ops.sweep_batch``)
    and the loop runs until *every* neighborhood is converged — exactly
    the semantics of ``vmap(_closure)`` (the extra iterations a converged
    lane sees are idempotent: the closure is monotone), but with one
    MXU-shaped contraction per iteration instead of B lane-wise sweeps.
    This is the round body the fused device-resident engine
    (:mod:`repro.core.parallel`) keeps inside its multi-round loop.
    """
    x0 = ev_pos & valid & ~ev_neg

    def cond(state):
        _, changed = state
        return changed

    def body(state):
        x, _ = state
        delta = icm_ops.sweep_batch(u, C, x.astype(jnp.float32))
        new = (delta >= -TIE_EPS) & valid & ~ev_neg
        x2 = x | new | (ev_pos & valid)
        return x2, jnp.any(x2 != x)

    x, _ = jax.lax.while_loop(cond, body, (x0, jnp.bool_(True)))
    return x


def _entailment_matrix(u, C, x, ev_neg, valid):
    """X[s, q] = 1 iff q in closure(x U {s}), for every seed pair s.

    One batched closure over the seed axis: (P, P) @ (P, P) matmuls.
    """
    P = u.shape[0]
    eye = jnp.eye(P, dtype=bool)
    seeds = eye & valid[None, :] & ~ev_neg[None, :] & ~x[None, :]
    X0 = seeds | x[None, :]

    def cond(state):
        _, changed = state
        return changed

    def body(state):
        X, _ = state
        delta = icm_ops.sweep_matrix(u, C, X.astype(jnp.float32))
        new = (delta >= -TIE_EPS) & valid[None, :] & ~ev_neg[None, :]
        X2 = X | new | X0
        return X2, jnp.any(X2 != X)

    X, _ = jax.lax.while_loop(cond, body, (X0, jnp.bool_(True)))
    return X, seeds


def _components(adj, nodes):
    """Min-label propagation. adj (P,P) bool symmetric, nodes (P,) bool.

    Returns labels (P,) int32: equal labels <=> same component; invalid
    nodes get label P (out of band).
    """
    P = adj.shape[0]
    big = jnp.int32(P)
    lab0 = jnp.where(nodes, jnp.arange(P, dtype=jnp.int32), big)
    adj = adj & nodes[:, None] & nodes[None, :]

    def cond(state):
        _, changed = state
        return changed

    def body(state):
        lab, _ = state
        nbr = jnp.where(adj, lab[None, :], big)
        lab2 = jnp.minimum(lab, jnp.min(nbr, axis=1))
        return lab2, jnp.any(lab2 != lab)

    lab, _ = jax.lax.while_loop(cond, body, (lab0, jnp.bool_(True)))
    return lab


def _peel_and_promote(u, C, x, lab, valid, ev_neg, num_pairs=None):
    """Greedy-peel each component, activate those with joint delta >= 0.

    Group matrix G[l, p] = 1 iff lab[p] == l (l ranges over pair slots;
    component labels are min member indices so G rows are mostly empty).
    Peeling: drop members with negative marginal (u + C@(x + s))_p until
    none; then activate components whose joint delta >= -TIE_EPS.
    ``num_pairs`` is the neighborhood's k(k-1)/2, which bounds the peel;
    it defaults to the slot width, and a compacted row (fewer slots than
    k(k-1)/2) must pass it so the bound does not shrink with the width.
    """
    P = u.shape[0]
    labels = jnp.arange(P, dtype=jnp.int32)
    undecided = valid & ~x & ~ev_neg
    G0 = (lab[None, :] == labels[:, None]) & undecided[None, :]  # (P_l, P)

    xf = x.astype(jnp.float32)
    base = u + C @ xf  # (P,) marginal from already-active set

    def peel_body(state):
        G, i, _ = state
        Gf = G.astype(jnp.float32)
        # marginal of member p of group l: base_p + (C @ s_l)_p
        marg = base[None, :] + Gf @ C  # (P_l, P)
        drop = G & (marg < 0.0)
        # drop only the single worst member per group per iteration
        worst = jnp.argmin(jnp.where(drop, marg, jnp.inf), axis=1)
        any_drop = jnp.any(drop, axis=1)
        onehot = jax.nn.one_hot(worst, P, dtype=bool)
        return G & ~(onehot & any_drop[:, None]), i + 1, jnp.any(any_drop)

    # Peeling drops at most one member per group per iteration; component
    # size is bounded by the neighborhood entity count k ~ sqrt(2P).  The
    # loop exits as soon as an iteration drops nothing (further
    # iterations are idempotent, so this is exactly the bounded-unroll
    # result) — on an already-converged group matrix the peel costs ONE
    # (P, P) matmul instead of ~sqrt(2P) of them, which is what makes
    # quiescence-check rounds cheap.
    peel_iters = int(np.ceil(np.sqrt(2 * (num_pairs or P)))) + 2

    def peel_cond(state):
        _, i, changed = state
        return changed & (i < peel_iters)

    G, _, _ = jax.lax.while_loop(
        peel_cond, peel_body, (G0, jnp.int32(0), jnp.bool_(True))
    )

    Gf = G.astype(jnp.float32)
    lin = Gf @ base  # (P_l,)
    quad = 0.5 * jnp.sum((Gf @ C) * Gf, axis=1)
    delta = lin + quad
    size = jnp.sum(G, axis=1)
    promote = (delta >= -TIE_EPS) & (size > 0)
    newx = jnp.any(G & promote[:, None], axis=0)
    return x | newx


def _infer_one(u, u_raw, C, ev_pos, ev_neg, valid, num_pairs=None):
    """Full MAP inference for one neighborhood. Returns (x, lab).

    x   : (P,) bool final match set (includes evidence).
    lab : (P,) int32 entailment-component labels of *undecided* pairs
          (the maximal messages), P where not applicable.
    num_pairs: the neighborhood's k(k-1)/2 when its P slots are a
          compacted layout (see :func:`_peel_and_promote`).
    """

    def round_body(state):
        x, _, _ = state
        x1 = _closure(u, C, ev_pos | x, ev_neg, valid)
        X, seeds = _entailment_matrix(u, C, x1, ev_neg, valid)
        mutual = X & X.T
        undecided = valid & ~x1 & ~ev_neg
        lab = _components(mutual, undecided)
        x2 = _peel_and_promote(u, C, x1, lab, valid, ev_neg, num_pairs)
        x3 = _closure(u, C, x2 | ev_pos, ev_neg, valid)
        return x3, lab, jnp.any(x3 != x)

    def cond(state):
        _, _, changed = state
        return changed

    x0 = jnp.zeros_like(valid)
    state = (x0, jnp.full(valid.shape, valid.shape[0], jnp.int32), jnp.bool_(True))
    # bounded outer fixpoint: while_loop with an explicit change flag
    x, lab, _ = jax.lax.while_loop(cond, round_body, state)
    return x, lab


@functools.lru_cache(maxsize=None)
def _jitted_infer():
    batched = jax.vmap(_infer_one, in_axes=(0, 0, 0, 0, 0, 0))
    return jax.jit(batched)


@functools.lru_cache(maxsize=None)
def _jitted_score():
    def f(u_raw, C, x):
        return score_ops.score_sets(u_raw, C, x[:, None, :].astype(jnp.float32))[:, 0]

    return jax.jit(f)


@functools.lru_cache(maxsize=None)
def _jitted_closure_only():
    return jax.jit(closure_batch)


# ---------------------------------------------------------------------------
# Public matcher
# ---------------------------------------------------------------------------


class MLNMatcher:
    """Supermodular Type-II matcher over padded neighborhood batches.

    run(batch, ev_pos, ev_neg)          -> match mask (B, P) bool [Type-I out]
    run_with_messages(batch, ...)       -> (match mask, component labels)
    score(batch, x)                     -> unnormalized log P_E (B,)
    closure_only(batch, ev_pos, ev_neg) -> greedy-only variant (ablation /
                                           the iterative matchers of App. A)
    """

    def __init__(self, weights: MLNWeights = PAPER_LEARNED, collective: bool = True):
        self.weights = weights
        self.collective = collective

    # -- grounding ---------------------------------------------------------
    def ground(self, batch: NeighborhoodBatch) -> Grounding:
        return ground(batch, self.weights)

    def parallel_backend(self) -> tuple[str, MLNWeights]:
        """Grounding key for the round-parallel engine (core.parallel)."""
        return ("mln", self.weights)

    # -- Type-I interface ---------------------------------------------------
    def run(
        self,
        batch: NeighborhoodBatch,
        ev_pos: np.ndarray | None = None,
        ev_neg: np.ndarray | None = None,
    ) -> np.ndarray:
        x, _ = self.run_with_messages(batch, ev_pos, ev_neg)
        return x

    def run_with_messages(
        self,
        batch: NeighborhoodBatch,
        ev_pos: np.ndarray | None = None,
        ev_neg: np.ndarray | None = None,
    ) -> tuple[np.ndarray, np.ndarray]:
        g = self.ground(batch)
        B, P = g.u.shape
        ev_pos = self._mask(ev_pos, (B, P))
        ev_neg = self._mask(ev_neg, (B, P))
        if self.collective:
            x, lab = _jitted_infer()(g.u, g.u_raw, g.C, ev_pos, ev_neg, g.valid)
        else:
            x = _jitted_closure_only()(g.u, g.C, ev_pos, ev_neg, g.valid)
            lab = jnp.full((B, P), P, dtype=jnp.int32)
        return np.asarray(x), np.asarray(lab)

    # -- Type-II interface ---------------------------------------------------
    def score(self, batch: NeighborhoodBatch, x: np.ndarray) -> np.ndarray:
        """Unnormalized log P_E(x) per neighborhood (exact, cheap)."""
        g = self.ground(batch)
        return np.asarray(_jitted_score()(g.u_raw, g.C, jnp.asarray(x)))

    def closure_only(self, batch, ev_pos=None, ev_neg=None) -> np.ndarray:
        g = self.ground(batch)
        B, P = g.u.shape
        ev_pos = self._mask(ev_pos, (B, P))
        ev_neg = self._mask(ev_neg, (B, P))
        return np.asarray(_jitted_closure_only()(g.u, g.C, ev_pos, ev_neg, g.valid))

    @staticmethod
    def _mask(m, shape) -> jax.Array:
        if m is None:
            return jnp.zeros(shape, dtype=bool)
        return jnp.asarray(m, dtype=bool)
