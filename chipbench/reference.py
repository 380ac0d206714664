"""Plain reference of collective entity matching (Rastogi, Dalvi and
Garofalakis, "Large-Scale Collective Entity Matching", VLDB 2011).

It imports nothing of the program under test.  From a corpus (names and
coauthor edges) and the matcher settings of a configuration file it
computes what the deployment has to answer:

1. the cover (paper section 4): canopies over hashed character-3-gram
   profiles of each name's blocking key, seeded in id order (cosine at
   least ``t_loose`` joins a canopy, at least ``t_tight`` stops being a
   seed), oversized canopies cut into overlapping windows, each window
   expanded by its coauthors up to ``k_max`` members, then a totality
   sweep for uncovered coauthor edges and uncovered references;
2. the Similar relation: Jaro-Winkler on surname-first names,
   discretized into levels 1 to 3, with abbreviation-aware weak
   candidates and a veto for different full first names;
3. the MLN matcher of Appendix B on every neighborhood (greedy closure,
   mutual-entailment components, peeling, collective promotion);
4. the fixpoint of simple message passing (SMP, Algorithm 1) or of
   maximal message passing (MMP, Algorithm 3, with step-7 promotion
   against the global grounding), computed in synchronous rounds over
   every neighborhood: by the paper's consistency theorems the fixpoint
   does not depend on the evaluation order;
5. the entities: connected components of the matched pairs.

The cover is computed exactly (integer profile counts, rational
thresholds) and the matcher in float32 with matrix products at the
highest precision, the precision the configuration states.  With
``precision="bfloat16"`` every similarity, weight and product is taken in
bfloat16 instead: that run is the control that the comparison must
reject.
"""

from __future__ import annotations

import dataclasses
import functools
from fractions import Fraction

import numpy as np

GID_STRIDE = 1 << 32
NEG = -1.0e9
TIE_EPS = 1.0e-5  # "delta >= 0" tolerance of the matcher (ties prefer the larger set)
PROMOTE_EPS = 1.0e-6  # step-7 tolerance: promote when P_E does not fall
K = 32  # neighborhoods are held in 32 entity slots
ROWS = 256  # neighborhoods per device call


def bf16_round(x) -> np.ndarray:
    """Round to the nearest bfloat16 (ties to even), returned as float64."""
    b = np.asarray(x, dtype=np.float32).reshape(-1).view(np.uint32).astype(np.uint64)
    b = (b + 0x7FFF + ((b >> 16) & 1)) & 0xFFFF0000
    return b.astype(np.uint32).view(np.float32).astype(np.float64).reshape(np.shape(x))


# ---------------------------------------------------------------------------
# Names and the Similar relation
# ---------------------------------------------------------------------------


def jaro(s1: str, s2: str) -> float:
    if s1 == s2:
        return 1.0
    n1, n2 = len(s1), len(s2)
    if n1 == 0 or n2 == 0:
        return 0.0
    window = max(max(n1, n2) // 2 - 1, 0)
    m1, m2 = [False] * n1, [False] * n2
    matches = 0
    for i, c in enumerate(s1):
        for j in range(max(0, i - window), min(n2, i + window + 1)):
            if not m2[j] and s2[j] == c:
                m1[i] = m2[j] = True
                matches += 1
                break
    if matches == 0:
        return 0.0
    t, j = 0, 0
    for i in range(n1):
        if m1[i]:
            while not m2[j]:
                j += 1
            t += s1[i] != s2[j]
            j += 1
    m = float(matches)
    return (m / n1 + m / n2 + (m - t // 2) / m) / 3.0


def jaro_winkler(s1: str, s2: str) -> float:
    j = jaro(s1, s2)
    prefix = 0
    for a, b in zip(s1, s2):
        if a != b or prefix >= 4:
            break
        prefix += 1
    return j + prefix * 0.1 * (1.0 - j)


def surname_first(name: str) -> str:
    t = name.lower().split()
    return " ".join([t[-1]] + t[:-1]) if len(t) >= 2 else name.lower()


def block_key(name: str) -> str:
    """Surname and first initial: "alessandro rossi" and "a. rossi" agree."""
    t = name.lower().replace(".", "").split()
    return f"{t[-1]} {t[0][0]}" if len(t) >= 2 else name.lower()


def _first_names(a: str, b: str):
    ta, tb = a.lower().split(), b.lower().split()
    if len(ta) < 2 or len(tb) < 2:
        return None
    return ta, tb, ta[0].rstrip("."), tb[0].rstrip(".")


def similarity_level(a: str, b: str, thresholds, bf16: bool = False) -> int:
    """Level 0 (no candidate) to 3 of the pair of names ``a``, ``b``."""
    def score(x, y):
        s = jaro_winkler(x, y)
        return float(bf16_round(s)) if bf16 else s

    s = score(surname_first(a), surname_first(b))
    lev = sum(s >= t for t in thresholds)
    f = _first_names(a, b)
    if lev == 0:
        # weak candidate: one side abbreviates the other's first name
        if f and f[0][-1] == f[1][-1] and f[2] and f[3] and f[2][0] == f[3][0]:
            if (len(f[2]) == 1 or len(f[3]) == 1) and f[2] != f[3]:
                return 1
        return 0
    if f and f[2] and f[3]:
        # veto: two different full first names are different people
        if f[2][0] != f[3][0]:
            return 0
        if len(f[2]) > 1 and len(f[3]) > 1 and score(f[2], f[3]) < 0.84:
            return 0
    return lev


def ngram_counts(keys: list[str], dim: int) -> np.ndarray:
    """(N, dim) counts of the FNV-1a-hashed 3-grams of ``^key$``."""
    mask = (1 << 64) - 1
    mix = 0x9E3779B97F4A7C15
    out = np.zeros((len(keys), dim), dtype=np.float64)
    for r, key in enumerate(keys):
        s = "^" + key.lower() + "$"
        for i in range(max(1, len(s) - 2)):
            h = 1469598103934665603
            for ch in s[i : i + 3].encode("utf-8"):
                h = ((h ^ ch) * 1099511628211) & mask
            out[r, (h ^ mix) % dim] += 1.0
    return out


# ---------------------------------------------------------------------------
# Cover
# ---------------------------------------------------------------------------


def _above(counts: np.ndarray, thresholds, bf16: bool, block: int = 2048):
    """Boolean (N, N) matrices ``cosine >= t`` for each threshold."""
    n = counts.shape[0]
    sq = (counts * counts).sum(axis=1)
    out = [np.zeros((n, n), dtype=bool) for _ in thresholds]
    if bf16:
        unit = bf16_round(counts / np.sqrt(np.maximum(sq, 1.0))[:, None])
    for lo in range(0, n, block):
        hi = min(n, lo + block)
        if bf16:
            cos = bf16_round(unit[lo:hi] @ unit.T)
            for o, t in zip(out, thresholds):
                o[lo:hi] = cos >= t
        else:
            dot = counts[lo:hi] @ counts.T  # small integers: exact
            lhs = dot * dot
            nn = sq[lo:hi, None] * sq[None, :]
            for o, t in zip(out, thresholds):
                f = Fraction(str(t))
                o[lo:hi] = lhs * f.denominator**2 >= nn * f.numerator**2
    return out


def canopies(names: list[str], m: dict, bf16: bool) -> list[np.ndarray]:
    counts = ngram_counts([block_key(x) for x in names], m["feature_dim"])
    loose, tight = _above(counts, (m["t_loose"], m["t_tight"]), bf16)
    remaining = np.ones(len(names), dtype=bool)
    out = []
    for seed in range(len(names)):
        if not remaining[seed]:
            continue
        members = np.nonzero(loose[seed])[0]
        out.append(members if len(members) else np.array([seed]))
        remaining[tight[seed]] = False
        remaining[seed] = False
    return out


def adjacency(edges: np.ndarray) -> dict[int, set[int]]:
    adj: dict[int, set[int]] = {}
    for a, b in edges:
        adj.setdefault(int(a), set()).add(int(b))
        adj.setdefault(int(b), set()).add(int(a))
    return adj


def cover(names: list[str], edges: np.ndarray, m: dict, bf16: bool = False):
    """Member lists (sorted ids) of every neighborhood of the total cover."""
    adj = adjacency(edges)
    k_max = m["k_max"]
    k_core = max(2, int(k_max * 0.6))
    seen: set[tuple] = set()
    full: list[list[int]] = []
    for members in canopies(names, m, bf16):
        parts = [members]
        if len(members) > k_core:
            order = np.argsort([names[int(e)] for e in members], kind="stable")
            ranked = members[order]
            parts = []
            for lo in range(0, len(ranked), max(k_core // 2, 1)):
                parts.append(ranked[lo : lo + k_core])
                if lo + k_core >= len(ranked):
                    break
        for part in parts:
            key = tuple(sorted(int(e) for e in part))
            if key in seen or len(part) < 2:
                continue
            seen.add(key)
            core = set(key)
            boundary: set[int] = set()
            for e in part:
                boundary |= adj.get(int(e), set())
            boundary -= core
            room = k_max - len(part)
            if len(boundary) > room:
                # keep the best-connected coauthors (stable on set order)
                boundary = set(sorted(boundary, key=lambda b: -len(adj.get(b, set()) & core))[:room])
            full.append(sorted(core | boundary))
    covered: set[tuple[int, int]] = set()
    for ms in full:
        mset = set(ms)
        for e in ms:
            for b in adj.get(e, ()):
                if b in mset:
                    covered.add((min(e, b), max(e, b)))
    missing = sorted({(min(int(a), int(b)), max(int(a), int(b)))
                      for a, b in edges if int(a) != int(b)} - covered)
    group: set[int] = set()
    for a, b in missing:
        if len(group | {a, b}) > k_max:
            full.append(sorted(group))
            group = set()
        group |= {a, b}
    if group:
        full.append(sorted(group))
    seen_e = {e for ms in full for e in ms}
    left = [e for e in range(len(names)) if e not in seen_e]
    full += [left[lo : lo + k_max] for lo in range(0, len(left), k_max)]
    return full, adj


# ---------------------------------------------------------------------------
# Neighborhood tensors and the global grounding
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Instance:
    """Every neighborhood in K slots, over one universe of candidate pairs."""

    co: np.ndarray  # (N, K, K) bool coauthorship among members
    lev: np.ndarray  # (N, P) int8 similarity level, 0 where no candidate
    uidx: np.ndarray  # (N, P) int64 index into ``gids``, -1 where no candidate
    gids: np.ndarray  # (U,) sorted candidate pair ids a * 2**32 + b, a < b
    levels: np.ndarray  # (U,) level of each candidate pair
    size: np.ndarray  # (N,) members of each neighborhood
    adj: dict

    @property
    def n(self) -> int:
        return self.co.shape[0]


def instance(names, edges, m: dict, bf16: bool = False) -> Instance:
    full, adj = cover(names, edges, m, bf16)
    ii, jj = np.triu_indices(K, k=1)
    P = len(ii)
    n = len(full)
    co = np.zeros((n, K, K), dtype=bool)
    lev = np.zeros((n, P), dtype=np.int8)
    gid = np.full((n, P), -1, dtype=np.int64)
    slot = {(int(a), int(b)): p for p, (a, b) in enumerate(zip(ii, jj))}
    memo: dict[int, int] = {}
    th = tuple(m["level_thresholds"])
    for r, ms in enumerate(full):
        for i, a in enumerate(ms):
            nb = adj.get(a, ())
            for j in range(i + 1, len(ms)):
                b = ms[j]
                if b in nb:
                    co[r, i, j] = co[r, j, i] = True
                g = a * GID_STRIDE + b
                lv = memo.get(g)
                if lv is None:
                    lv = memo[g] = similarity_level(names[a], names[b], th, bf16)
                if lv:
                    p = slot[(i, j)]
                    lev[r, p] = lv
                    gid[r, p] = g
    gids = np.unique(gid[gid >= 0])
    uidx = np.where(gid >= 0, np.searchsorted(gids, gid), -1)
    levels = np.zeros(len(gids), dtype=np.int8)
    levels[uidx[uidx >= 0]] = lev[uidx >= 0]
    size = np.array([len(ms) for ms in full], dtype=np.int64)
    return Instance(co=co, lev=lev, uidx=uidx, gids=gids, levels=levels, size=size, adj=adj)


def global_grounding(inst: Instance, w_sim, w_co, bf16: bool = False):
    """Unary of every candidate pair over the whole coauthor graph, and the
    couplings: candidate pairs (a, b), (c, d) with c ~ a and d ~ b."""
    adj = inst.adj
    index = {int(g): i for i, g in enumerate(inst.gids)}
    u = np.zeros(len(inst.gids))
    coup: set[tuple[int, int]] = set()
    for i, g in enumerate(inst.gids):
        a, b = divmod(int(g), GID_STRIDE)
        na, nb = adj.get(a, set()), adj.get(b, set())
        u[i] = w_sim[inst.levels[i]] + w_co * len(na & nb)
        for c in na:
            for d in nb:
                if c != d:
                    j = index.get(min(c, d) * GID_STRIDE + max(c, d))
                    if j is not None and j != i:
                        coup.add((min(i, j), max(i, j)))
    cp = np.array(sorted(coup), dtype=np.int64).reshape(-1, 2)
    if bf16:
        u = bf16_round(u)
    return u, cp[:, 0], cp[:, 1]


# ---------------------------------------------------------------------------
# The MLN matcher on the device
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=None)
def infer_fn(w_sim: tuple, w_co: float, bf16: bool):
    """Jitted matcher over ROWS neighborhoods: (co, lev, ev_pos, peel) ->
    (match mask, message labels), both (ROWS, P)."""
    import jax
    import jax.numpy as jnp

    dt = jnp.bfloat16 if bf16 else jnp.float32
    prec = jax.lax.Precision.DEFAULT if bf16 else jax.lax.Precision.HIGHEST
    ii, jj = np.triu_indices(K, k=1)
    P = len(ii)
    mm = functools.partial(jnp.matmul, precision=prec)
    eps = jnp.asarray(-TIE_EPS, dt)

    def fixed(body, *init):
        """Iterate ``body`` over the state until its last entry is False."""
        return jax.lax.while_loop(lambda s: s[-1], body, (*init, jnp.bool_(True)))

    def one(co, lev, ev_pos, peel_iters):
        valid = lev > 0
        cof = co.astype(dt)
        n_shared = mm(cof, cof.T)[ii, jj]
        link = (co[ii][:, ii] & co[jj][:, jj]) | (co[ii][:, jj] & co[jj][:, ii])
        link = link & valid[:, None] & valid[None, :] & ~jnp.eye(P, dtype=bool)
        w = jnp.asarray(w_sim, dt)
        u = jnp.where(valid, w[lev] + jnp.asarray(w_co, dt) * n_shared, jnp.asarray(NEG, dt))
        C = link.astype(dt) * jnp.asarray(w_co, dt)

        def closure(x0):
            def body(s):
                x, _ = s
                x2 = x | ((u + mm(x.astype(dt), C) >= eps) & valid) | (x0 & valid)
                return x2, jnp.any(x2 != x)
            return fixed(body, x0 & valid)[0]

        def entail(x):
            X0 = (jnp.eye(P, dtype=bool) & valid[None, :] & ~x[None, :]) | x[None, :]

            def body(s):
                X, _ = s
                X2 = X | ((u[None, :] + mm(X.astype(dt), C) >= eps) & valid[None, :]) | X0
                return X2, jnp.any(X2 != X)
            return fixed(body, X0)[0]

        def components(adj, nodes):
            lab0 = jnp.where(nodes, jnp.arange(P), P)
            adj = adj & nodes[:, None] & nodes[None, :]

            def body(s):
                lab, _ = s
                lab2 = jnp.minimum(lab, jnp.min(jnp.where(adj, lab[None, :], P), axis=1))
                return lab2, jnp.any(lab2 != lab)
            return fixed(body, lab0)[0]

        def promote(x, lab):
            und = valid & ~x
            G0 = (lab[None, :] == jnp.arange(P)[:, None]) & und[None, :]
            base = u + mm(x.astype(dt), C)

            def body(s):
                G, i, _ = s
                marg = base[None, :] + mm(G.astype(dt), C)
                drop = G & (marg < 0)
                worst = jnp.argmin(jnp.where(drop, marg, jnp.inf), axis=1)
                any_drop = jnp.any(drop, axis=1)
                G = G & ~((jnp.arange(P)[None, :] == worst[:, None]) & any_drop[:, None])
                return G, i + 1, jnp.any(any_drop) & (i + 1 < peel_iters)
            G = jax.lax.while_loop(lambda s: s[2], body, (G0, 0, peel_iters > 0))[0]
            Gf = G.astype(dt)
            delta = mm(Gf, base[:, None])[:, 0] + 0.5 * jnp.sum(mm(Gf, C) * Gf, axis=1)
            take = (delta >= eps) & jnp.any(G, axis=1)
            return x | jnp.any(G & take[:, None], axis=0)

        def body(s):
            x, _, _ = s
            x1 = closure(ev_pos | x)
            X = entail(x1)
            lab = components(X & X.T, valid & ~x1)
            x3 = closure(promote(x1, lab) | ev_pos)
            return x3, lab, jnp.any(x3 != x)

        x, lab, _ = fixed(body, jnp.zeros(P, bool), jnp.full(P, P))
        return x, lab

    return jax.jit(jax.vmap(one))


def peel_bound(n_members: np.ndarray) -> np.ndarray:
    """The matcher peels at most ceil(sqrt(2P)) + 2 members per component,
    with P the pair count of the neighborhood's size class (8/16/24/32)."""
    k = np.select([n_members <= 8, n_members <= 16, n_members <= 24], [8, 16, 24], 32)
    p = k * (k - 1) // 2
    return (np.ceil(np.sqrt(2 * p)) + 2).astype(np.int32)


def evaluate(inst: Instance, rows: np.ndarray, M: np.ndarray, fn, peel: np.ndarray):
    """Matcher outputs of ``rows`` with the matches ``M`` as evidence."""
    import jax

    xs, labs = [], []
    for lo in range(0, len(rows), ROWS):
        r = rows[lo : lo + ROWS]
        pad = ROWS - len(r)
        u = inst.uidx[r]
        ev = (u >= 0) & M[np.maximum(u, 0)]
        args = [inst.co[r], inst.lev[r], ev, peel[r]]
        args = [np.concatenate([a, np.zeros((pad,) + a.shape[1:], a.dtype)]) for a in args]
        x, lab = jax.device_get(fn(*args))
        xs.append(x[: len(r)])
        labs.append(lab[: len(r)])
    return np.concatenate(xs), np.concatenate(labs)


class _Pool:
    """Disjoint maximal messages over candidate-pair indices (union-find)."""

    def __init__(self, n: int):
        self.parent = np.arange(n)
        self.used = np.zeros(n, dtype=bool)

    def find(self, i: int) -> int:
        p = self.parent
        while p[i] != i:
            p[i] = p[p[i]]
            i = p[i]
        return i

    def add(self, members) -> None:
        r0 = self.find(int(members[0]))
        self.used[members] = True
        for g in members[1:]:
            r = self.find(int(g))
            if r != r0:
                self.parent[r] = r0

    def group_of(self) -> np.ndarray:
        """(U,) group root of every index in a group of two or more, else -1."""
        idx = np.nonzero(self.used)[0]
        roots = np.array([self.find(int(i)) for i in idx], dtype=np.int64)
        out = np.full(len(self.parent), -1, dtype=np.int64)
        out[idx] = roots
        sizes = np.bincount(roots, minlength=len(self.parent))
        out[idx[sizes[roots] < 2]] = -1
        return out


def _promote(pool: _Pool, u, cp, cq, w_co, M):
    """Step 7: promote every message group whose addition does not lower
    P_E, to a fixpoint (P_E is supermodular, so the order is free)."""
    grp = pool.group_of()
    if not (grp >= 0).any():
        return M
    n = len(M)
    while True:
        g = np.where(M, -1, grp)  # groups over their members not yet matched
        has_new = np.bincount(g[g >= 0], minlength=n) > 0
        lin = np.bincount(g[g >= 0], weights=u[g >= 0], minlength=n)
        gp, gq = g[cp], g[cq]
        quad = np.zeros(n)
        one = M[cp] & (gq >= 0)
        np.add.at(quad, gq[one], 1.0)
        one = M[cq] & (gp >= 0)
        np.add.at(quad, gp[one], 1.0)
        both = (gp >= 0) & (gp == gq)
        np.add.at(quad, gp[both], 1.0)
        take = has_new & (lin + w_co * quad >= -PROMOTE_EPS)
        if not take.any():
            return M
        M = M | ((grp >= 0) & take[np.maximum(grp, 0)])


def fixpoint(inst: Instance, scheme: str, m: dict, bf16: bool = False) -> np.ndarray:
    """Matched candidate-pair ids at the SMP or MMP fixpoint."""
    w_sim = tuple(float(w) for w in m["weights"]["w_sim"])
    w_co = float(m["weights"]["w_co"])
    if bf16:
        w_sim = tuple(float(w) for w in bf16_round(w_sim))
        w_co = float(bf16_round(w_co))
    fn = infer_fn(w_sim, w_co, bf16)
    peel = peel_bound(inst.size)
    U = len(inst.gids)
    M = np.zeros(U, dtype=bool)
    flat = inst.uidx.reshape(-1)
    ok = flat >= 0
    inc_rows = np.repeat(np.arange(inst.n), inst.uidx.shape[1])[ok]
    inc_gid = flat[ok]
    if scheme == "mmp":
        u, cp, cq = global_grounding(inst, w_sim, w_co, bf16)
        pool = _Pool(U)
    active = np.arange(inst.n)
    P = inst.uidx.shape[1]
    while len(active):
        x, lab = evaluate(inst, active, M, fn, peel)
        uid = inst.uidx[active]
        hit = x & (uid >= 0)
        new = np.zeros(U, dtype=bool)
        new[uid[hit]] = True
        new &= ~M
        if scheme == "mmp":
            ok2 = (lab < P) & (uid >= 0)
            ok2 &= ~M[np.maximum(uid, 0)]
            rows, cols = np.nonzero(ok2)
            key = rows * P + lab[rows, cols]
            order = np.argsort(key, kind="stable")
            key, members = key[order], uid[rows, cols][order]
            _, starts, counts = np.unique(key, return_index=True, return_counts=True)
            for s, c in zip(starts, counts):
                if c >= 2:
                    pool.add(members[s : s + c])
        M = M | new
        if scheme == "mmp":
            M2 = _promote(pool, u, cp, cq, w_co, M)
            new |= M2 & ~M
            M = M2
        active = np.unique(inc_rows[new[inc_gid]])
    return inst.gids[M]


def clusters(n: int, gids: np.ndarray) -> np.ndarray:
    """(n,) smallest member id of each reference's entity."""
    parent = np.arange(n)

    def find(i):
        while parent[i] != i:
            parent[i] = parent[parent[i]]
            i = parent[i]
        return i

    for g in gids:
        a, b = find(int(g) // GID_STRIDE), find(int(g) % GID_STRIDE)
        if a != b:
            parent[max(a, b)] = min(a, b)
    return np.array([find(i) for i in range(n)])
