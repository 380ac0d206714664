"""The plain reference at a corpus's full size.

``chipbench/reference.py`` builds its instance with two steps that grow
with the square of the corpus: the canopy cosines as full (N, N)
matrices over the references, and one similarity level per member pair
of every neighborhood in a Python loop.  This module computes the same
instance without either, and leaves everything else to
``reference.py``: its names, n-gram counts, cover rules, matcher,
global grounding and fixpoint.  Like it, it imports nothing of the
program under test.

1. Canopies.  References with one blocking key have one count vector,
   so the cosines are taken between distinct keys only.  A float32
   cosine screens the key pairs, with room for its rounding; each
   threshold is then decided exactly on the integer dot product and the
   rational threshold, as ``reference._above`` decides it.  Seeds run
   in id order as before: a reference whose key is within ``t_tight`` of
   a seed's key stops being a seed, so every seed is the first of its key
   still standing.
2. Levels.  Jaro similarity is at most (M/|x| + M/|y| + 1) / 3, where M
   is the number of letters the two keys share as multisets (a matched
   character is a shared letter), and Jaro-Winkler at most
   J + 0.1 l (1 - J), with l the keys' common prefix (at most 4).  Two
   full first names with different initials give level 0 whatever the
   score (no weak candidate below the thresholds, the veto above them).
   A pair whose bound stays under the lowest threshold is a weak
   candidate (level 1) or nothing, by the weak-candidate rule; every
   other distinct pair of name strings is given
   ``reference.similarity_level``.

``instance(names, edges, m)`` equals ``reference.instance(names, edges,
m)`` (float32 only: the bfloat16 control stays with ``reference.py``).
"""

from __future__ import annotations

from fractions import Fraction

import numpy as np

from chipbench import reference as ref

_SLACK = 1e-9  # float rounding of the bound is ~1e-16: no level is lost
_ROWS = 1024  # distinct keys per block of cosines
_SCREEN = 1e-3  # float32 cosines are within ~1e-6 of the exact ones


def _key_neighbors(counts: np.ndarray, thresholds) -> list[list[np.ndarray]]:
    """For each threshold and each distinct key, the keys whose cosine
    with it is at least the threshold.

    A float32 cosine screens the pairs with room for its rounding; each
    pair that passes is decided exactly on the integer dot product."""
    sq = (counts * counts).sum(axis=1)
    unit = (counts / np.sqrt(sq)[:, None]).astype(np.float32)
    fracs = [Fraction(str(t)) for t in thresholds]
    out: list[list[np.ndarray]] = [[] for _ in thresholds]
    for lo in range(0, len(counts), _ROWS):
        rows, cols = np.nonzero(unit[lo : lo + _ROWS] @ unit.T >= min(thresholds) - _SCREEN)
        dot = np.einsum("ij,ij->i", counts[lo + rows], counts[cols])  # small integers: exact
        lhs, nn = dot * dot, sq[lo + rows] * sq[cols]
        for o, f in zip(out, fracs):
            hit = lhs * f.denominator**2 >= nn * f.numerator**2
            r, c = rows[hit], cols[hit]
            o += np.split(c, np.searchsorted(r, np.arange(1, min(_ROWS, len(counts) - lo))))
    return out


def canopies(names: list[str], m: dict) -> list[np.ndarray]:
    """``reference.canopies`` (float32 thresholds decided exactly)."""
    keys, kid = np.unique([ref.block_key(x) for x in names], return_inverse=True)
    counts = ref.ngram_counts(list(keys), m["feature_dim"])
    loose, tight = _key_neighbors(counts, (m["t_loose"], m["t_tight"]))
    order = np.argsort(kid, kind="stable")
    refs_of = np.split(order, np.searchsorted(kid[order], np.arange(1, len(keys))))
    remaining = np.ones(len(names), dtype=bool)
    out = []
    for seed in range(len(names)):
        if not remaining[seed]:
            continue
        k = kid[seed]
        members = np.sort(np.concatenate([refs_of[j] for j in loose[k]]))
        out.append(members if len(members) else np.array([seed]))
        for j in tight[k]:
            remaining[refs_of[j]] = False
        remaining[seed] = False
    return out


def _edge_keys(edges: np.ndarray) -> np.ndarray:
    """Sorted ids ``a * 2**32 + b`` (a < b) of the coauthor edges."""
    e = np.asarray(edges, dtype=np.int64).reshape(-1, 2)
    lo, hi = e.min(axis=1), e.max(axis=1)
    return np.unique((lo * ref.GID_STRIDE + hi)[lo != hi])


def cover(names: list[str], edges: np.ndarray, m: dict):
    """``reference.cover`` over :func:`canopies`."""
    adj = ref.adjacency(edges)
    k_max = m["k_max"]
    k_core = max(2, int(k_max * 0.6))
    seen: set[tuple] = set()
    full: list[list[int]] = []
    for members in canopies(names, m):
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
                ranked = sorted(boundary, key=lambda b: -len(adj.get(b, set()) & core))
                boundary = set(ranked[:room])
            full.append(sorted(core | boundary))
    ms = np.full((len(full), k_max), -1, dtype=np.int64)
    for r, x in enumerate(full):
        ms[r, : len(x)] = x
    ii, jj = np.triu_indices(k_max, k=1)
    both = (ms[:, ii] >= 0) & (ms[:, jj] >= 0)
    inside = np.unique((ms[:, ii] * ref.GID_STRIDE + ms[:, jj])[both])
    edge_keys = _edge_keys(edges)
    missing = [divmod(int(g), ref.GID_STRIDE) for g in np.setdiff1d(edge_keys, inside)]
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


def levels(names: list[str], a: np.ndarray, b: np.ndarray, thresholds) -> np.ndarray:
    """``reference.similarity_level`` of each pair ``(names[a], names[b])``."""
    text: dict[str, int] = {}
    nid = np.array([text.setdefault(x, len(text)) for x in names], dtype=np.int64)
    strs = list(text)
    keys = [ref.surname_first(x) for x in strs]
    alphabet = {ch: i for i, ch in enumerate(sorted(set("".join(keys))))}
    hist = np.zeros((len(keys), max(len(alphabet), 1)), dtype=np.int32)
    for r, k in enumerate(keys):
        for ch in k:
            hist[r, alphabet[ch]] += 1
    size = hist.sum(axis=1).astype(np.float64)
    head = np.array([[ord(ch) for ch in k[:4]] + [-1] * (4 - len(k[:4])) for k in keys],
                    dtype=np.int64).reshape(-1, 4)
    # the two name rules' premises, per name: surname, full first name, initial
    toks = [x.lower().split() for x in strs]
    first = [t[0].rstrip(".") if len(t) >= 2 else "" for t in toks]
    sids: dict[str, int] = {}
    fids: dict[str, int] = {}
    surname = np.array([sids.setdefault(t[-1], len(sids)) if f else -1
                        for t, f in zip(toks, first)], dtype=np.int64)
    fname = np.array([fids.setdefault(f, len(fids)) if f else -1 for f in first], dtype=np.int64)
    initial = np.array([ord(f[0]) if f else -1 for f in first], dtype=np.int64)
    short = np.array([len(f) == 1 for f in first])
    pairs, back = np.unique(nid[a] * len(strs) + nid[b], return_inverse=True)
    pa, pb = pairs // len(strs), pairs % len(strs)
    lev = np.zeros(len(pairs), dtype=np.int8)
    keep = np.zeros(len(pairs), dtype=bool)
    step = 1 << 17
    for lo in range(0, len(pairs), step):
        x, y = pa[lo : lo + step], pb[lo : lo + step]
        shared = np.minimum(hist[x], hist[y]).sum(axis=1)
        with np.errstate(divide="ignore", invalid="ignore"):
            j = np.where(shared > 0, (shared / size[x] + shared / size[y] + 1.0) / 3.0, 1.0)
        prefix = np.cumprod((head[x] == head[y]) & (head[x] >= 0), axis=1).sum(axis=1)
        low = j + 0.1 * prefix * (1.0 - j) < min(thresholds) - _SLACK
        named = (fname[x] >= 0) & (fname[y] >= 0)
        # different initials of two full first names: vetoed above the
        # thresholds, no weak candidate below them
        other = named & (initial[x] != initial[y])
        weak = (named & (surname[x] == surname[y]) & (initial[x] == initial[y])
                & (short[x] | short[y]) & (fname[x] != fname[y]))
        lev[lo : lo + step] = low & ~other & weak
        keep[lo : lo + step] = ~low & ~other
    for i in np.flatnonzero(keep):
        lev[i] = ref.similarity_level(strs[pa[i]], strs[pb[i]], thresholds)
    return lev[back.reshape(-1)]


def instance(names, edges, m: dict) -> ref.Instance:
    """``reference.instance(names, edges, m)``, built without its two
    quadratic steps."""
    full, adj = cover(names, edges, m)
    K = ref.K
    n = len(full)
    ms = np.full((n, K), -1, dtype=np.int64)
    for r, x in enumerate(full):
        ms[r, : len(x)] = x
    ii, jj = np.triu_indices(K, k=1)
    a, b = ms[:, ii], ms[:, jj]
    valid = (a >= 0) & (b >= 0)
    g = np.where(valid, a * ref.GID_STRIDE + b, -1)
    edge_keys = _edge_keys(edges)
    co = np.zeros((n, K, K), dtype=bool)
    if len(edge_keys):
        at = np.minimum(np.searchsorted(edge_keys, g), len(edge_keys) - 1)
        r, p = np.nonzero(valid & (edge_keys[at] == g))
        co[r, ii[p], jj[p]] = co[r, jj[p], ii[p]] = True
    cand, back = np.unique(g[valid], return_inverse=True)
    lv = levels(names, cand // ref.GID_STRIDE, cand % ref.GID_STRIDE, tuple(m["level_thresholds"]))
    lev = np.zeros((n, len(ii)), dtype=np.int8)
    lev[valid] = lv[back.reshape(-1)]
    gid = np.where(lev > 0, g, -1)
    gids = np.unique(gid[gid >= 0])
    uidx = np.where(gid >= 0, np.searchsorted(gids, gid), -1)
    levels_u = np.zeros(len(gids), dtype=np.int8)
    levels_u[uidx[uidx >= 0]] = lev[uidx >= 0]
    size = np.array([len(x) for x in full], dtype=np.int64)
    return ref.Instance(co=co, lev=lev, uidx=uidx, gids=gids, levels=levels_u, size=size, adj=adj)
