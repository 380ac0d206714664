"""Seeded bibliographic corpora shaped like the paper's HEPTH and DBLP.

A copy of the generator the repository's tests use, so that the corpus a
cell measures on cannot drift with the program.  Author references are
emitted paper by paper: a surface form per (paper, author), abbreviated
first names for the HEPTH style, full names with typo mutations for the
DBLP style, coauthor edges inside each paper, and a few engineered
chains and rings of weak candidates at the end (the paper's Fig. 1 at
scale).  ``generate(params, seed)`` takes the ``corpus`` block of a
configuration file.
"""

from __future__ import annotations

import dataclasses
import itertools

import numpy as np

_FIRST = [
    "james", "john", "robert", "michael", "william", "david", "mary",
    "maria", "anna", "wei", "lei", "jun", "yan", "hiro", "kenji", "sara",
    "laura", "marco", "andrea", "pavel", "ivan", "olga", "rahul", "amit",
    "priya", "chen", "ming", "tao", "yuki", "akira", "hans", "peter",
    "klaus", "pierre", "jean", "luc", "carlos", "jose", "ana", "sofia",
]
_COMMON_LAST = [
    "smith", "johnson", "lee", "wang", "chen", "kumar", "singh", "patel",
    "mueller", "schmidt", "rossi", "ferrari", "ivanov", "petrov", "sato",
    "tanaka", "kim", "park", "nguyen", "tran", "garcia", "martinez",
]
_SYL_A = ["an", "ber", "cas", "dor", "el", "fal", "gor", "hab", "ir", "jas",
          "kol", "lam", "mor", "nev", "os", "pal", "qui", "ras", "sol", "tem",
          "ul", "var", "wes", "xan", "yor", "zel"]
_SYL_B = ["ak", "bel", "cot", "din", "er", "fas", "gul", "hom", "is", "jor",
          "ket", "lov", "mun", "nor", "ot", "pes", "quin", "rit", "sun", "tov",
          "ur", "vin", "wit", "xi", "yev", "zor"]
_SYL_C = ["a", "ez", "i", "man", "o", "ski", "sen", "son", "ton", "u", "ova"]
_LONG_FIRST = ("alessandro", "konstantin", "maximilian", "sebastiano",
               "evangelina", "bartholomew")


@dataclasses.dataclass
class Corpus:
    names: list[str]
    truth: np.ndarray  # (N,) true author per reference
    paper_of: np.ndarray  # (N,) paper per reference; references are in paper order
    edges: np.ndarray  # (E, 2) coauthor edges between references

    def __len__(self) -> int:
        return len(self.names)

    def prefix_edges(self, n: int) -> np.ndarray:
        """Coauthor edges among the first ``n`` references."""
        keep = (self.edges[:, 0] < n) & (self.edges[:, 1] < n)
        return self.edges[keep]


def _surname_pool(rng, size):
    """``size`` distinct surnames: the common ones, then seeded syllable
    compounds.  Once every compound of the current length is drawn, one
    more middle syllable is allowed, so any size finishes and a size that
    two middle syllables hold draws as before."""
    pool = list(_COMMON_LAST)
    seen = set(pool)
    heads = [_SYL_A, _SYL_B]
    while len(pool) < size:
        room = len({"".join(h) + t for h in itertools.product(*heads) for t in ("", *_SYL_C)} - seen)
        while room and len(pool) < size:
            s = "".join(h[int(rng.integers(0, len(h)))] for h in heads) + (
                _SYL_C[int(rng.integers(0, len(_SYL_C)))] if rng.random() < 0.6 else ""
            )
            if s not in seen:
                seen.add(s)
                pool.append(s)
                room -= 1
        heads.append(_SYL_B)
    ranks = np.arange(1, len(pool) + 1, dtype=np.float64)
    w = 1.0 / (ranks + 25.0)
    return pool, w / w.sum()


def _typo(rng, s):
    if len(s) < 4:
        return s
    op = rng.integers(0, 3)
    i = int(rng.integers(1, len(s) - 1))
    if op == 0:
        return s[:i] + s[i + 1:]
    if op == 1:
        return s[: i - 1] + s[i] + s[i - 1] + s[i + 1:]
    c = chr(ord("a") + int(rng.integers(0, 26)))
    return s[:i] + c + s[i + 1:]


def generate(params: dict, seed: int) -> Corpus:
    """The corpus of one configuration for one seed.

    ``params``: ``style`` ('hepth' abbreviates, 'dblp' keeps full names),
    ``n_authors``, ``n_papers``, ``refs_per_paper`` (the mean number of
    authors of a paper, fractional: 1 plus a Poisson draw, at most 6),
    ``n_communities``
    (0: n_authors / 12), ``surname_collision_rate``, ``typo_rate``,
    ``abbrev_rate``, ``chain_motifs``.
    """
    style = params["style"]
    n_authors, n_papers = int(params["n_authors"]), int(params["n_papers"])
    rng = np.random.default_rng(seed)
    n_comm = int(params["n_communities"]) or max(8, n_authors // 12)

    last_pool, last_w = _surname_pool(rng, max(150, int(n_authors * 1.5)))
    canon: list[str] = []
    seen_names: set[str] = set()
    for a in range(n_authors):
        for _attempt in range(20):
            if a > 0 and rng.random() < params["surname_collision_rate"]:
                prev = canon[int(rng.integers(0, len(canon)))]
                last = prev.split()[-1]
                prevfirst = prev.split()[0]
                pool = [f for f in _FIRST if f[0] == prevfirst[0] and f != prevfirst]
                first = (
                    pool[int(rng.integers(0, len(pool)))]
                    if pool and rng.random() < 0.12
                    else _FIRST[int(rng.integers(0, len(_FIRST)))]
                )
            else:
                first = _FIRST[int(rng.integers(0, len(_FIRST)))]
                last = last_pool[int(rng.choice(len(last_pool), p=last_w))]
            name = f"{first} {last}"
            if name not in seen_names:
                break
            mid = chr(ord("a") + int(rng.integers(0, 26)))
            name = f"{first} {mid}. {last}"
            if name not in seen_names:
                break
        seen_names.add(name)
        canon.append(name)

    community = rng.integers(0, n_comm, size=n_authors)
    names: list[str] = []
    truth: list[int] = []
    paper_of: list[int] = []
    edges: list[tuple[int, int]] = []
    by_comm = {c: np.where(community == c)[0] for c in range(n_comm)}
    for p in range(n_papers):
        pool = by_comm[int(rng.integers(0, n_comm))]
        if len(pool) == 0:
            continue
        n_auth = int(np.clip(rng.poisson(params["refs_per_paper"] - 1) + 1, 1, 6))
        authors = rng.choice(pool, size=min(n_auth, len(pool)), replace=False)
        refs = []
        for a in authors:
            parts = canon[int(a)].split()
            if style == "hepth" and rng.random() < params["abbrev_rate"]:
                surface = f"{parts[0][0]}. {parts[-1]}"
            else:
                surface = canon[int(a)]
            if rng.random() < params["typo_rate"]:
                surface = _typo(rng, surface)
            refs.append(len(names))
            names.append(surface)
            truth.append(int(a))
            paper_of.append(p)
        edges += [(refs[i], refs[j]) for i in range(len(refs)) for j in range(i + 1, len(refs))]

    def fresh_author(tag):
        surname = "".join(chr(ord("a") + int(rng.integers(0, 26))) for _ in range(8))
        canon.append(f"{_LONG_FIRST[tag % len(_LONG_FIRST)]} {surname}")
        return len(canon) - 1

    def pair_refs(a, p_id, abbrev):
        parts = canon[a].split()
        weak = f"{parts[0][0]}. {parts[-1]}" if abbrev else canon[a]
        names.extend([canon[a], weak])
        truth.extend([a, a])
        paper_of.extend([p_id, p_id])
        return len(names) - 2, len(names) - 1

    tag = 0
    for m in range(int(params["chain_motifs"])):
        ring = m % 2 == 1
        length = 4 + int(rng.integers(0, 2))
        authors = [fresh_author(tag + i) for i in range(length)]
        tag += length
        refs = [pair_refs(a, n_papers + m, ring or i > 0) for i, a in enumerate(authors)]
        for i in range(length) if ring else range(length - 1):
            j = (i + 1) % length
            edges.append((refs[i][0], refs[j][0]))
            edges.append((refs[i][1], refs[j][1]))

    return Corpus(
        names=names,
        truth=np.asarray(truth, dtype=np.int64),
        paper_of=np.asarray(paper_of, dtype=np.int64),
        edges=np.asarray(edges, dtype=np.int64).reshape(-1, 2),
    )
