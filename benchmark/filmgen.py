"""The benchmark's own copy of ``dgraph_tpu/utils/filmgen.py`` (PR 21), kept
here so that no later PR can move the data the cells are measured on.

Freebase-film-shaped synthetic graph: the data set behind the upstream's
own published benchmark (BASELINE.md: 21M RDF, the wiki 3-hop co-actor and
4-level director-detail queries), generated from a seed because no dump is
in the sandbox.

Shape (unchanged from bench21m.py's generator, which this replaces):
directors with a bounded-Pareto(α=2) number of films (mean 8, cap 15),
films with a name, a release date, one genre from a Zipf-ish table of 32
and a bounded-Pareto number of performances (mean 6, cap 8), each
performance pointing at one of 400,000 actors with celebrity skew
(``ACTORS * u**4``); ≈88 quads per director.  Every director owns a fixed
140-uid window, so uids are a function of (director, film, performance)
and the edge arrays below ARE the graph — the benchmark's plain numpy
reference (reference.py) walks them, the server loads their N-Quad rendering.

One change from the original (PR 25, PERF.md "the seed shuffles, the degree
sequences stay"): the DEGREE SEQUENCES — films per director, performances per
film, roles per actor, films per genre — are drawn once from
``STRUCTURE_SEED`` under the laws above, and ``seed`` deals them out: which
director has how many films, which film how many performances, which
performance which actor, and the dates.  Every seed is a different graph of
the same degree multisets.  The program sizes its compiled chain programs by
each predicate's exact overflow length (``models/arena.py``, not bucketed), so
a graph with other degree sequences recompiles every program — 6 to 10
minutes at 5.25M quads on the chip — and no run after the first would fit its
time limit.

Imports numpy only: the processes that generate data never touch JAX.
"""

from __future__ import annotations

import gzip
import os
import time
from dataclasses import dataclass

import numpy as np

SCHEMA = """
    name: string @index(term, exact) .
    initial_release_date: datetime @index(year) .
    director.film: uid @reverse @count .
    genre: uid @reverse .
    starring: uid .
    performance.actor: uid @reverse .
"""

GENRES = 32
ACTORS = 400_000          # at the upstream's 21M scale
PER_DIR = 140             # uid window per director: 1 + 15 * (1 + 8) = 136 fit
QUADS_PER_DIRECTOR = 88   # measured mean of this generator
FULL_QUADS = 21_000_000
STRUCTURE_SEED = 0        # the degree sequences' own seed: the same for every run


def _zipfish(rng, mean: float, hi: int, n: int) -> np.ndarray:
    """Bounded Pareto(α=2) integers with the given mean: heavy-tailed
    degrees (a few prolific directors / ensemble films)."""
    x = (1.0 - rng.random(n)) ** -0.5
    return np.clip((x * mean / 2).astype(np.int64), 1, hi)


@dataclass
class FilmGraph:
    """The generated graph as parallel numpy arrays (uids are int64)."""

    n_actors: int
    director: np.ndarray       # [D] director uids
    film: np.ndarray           # [F] film uids
    film_dir: np.ndarray       # [F] index into ``director``
    film_no: np.ndarray        # [F] film ordinal within its director
    film_genre: np.ndarray     # [F] genre uids
    film_date: np.ndarray      # [F, 3] (year, month digit, day digit)
    perf: np.ndarray           # [P] performance uids
    perf_film: np.ndarray      # [P] index into ``film``
    perf_actor: np.ndarray     # [P] actor uids

    @property
    def actor_base(self) -> int:
        return 1 + GENRES

    def n_quads(self) -> int:
        return (
            GENRES + self.n_actors + len(self.director)
            + 4 * len(self.film) + 2 * len(self.perf)
        )

    def date_str(self, f: int) -> str:
        y, m, d = self.film_date[f]
        return f"{y}-0{m}-1{d}"

    def edges(self) -> dict:
        """predicate → (src uids, dst uids) of every uid edge."""
        return {
            "director.film": (self.director[self.film_dir], self.film),
            "genre": (self.film, self.film_genre),
            "starring": (self.film[self.perf_film], self.perf),
            "performance.actor": (self.perf, self.perf_actor),
        }


def generate(quads: int, seed: int) -> FilmGraph:
    """The film graph of about ``quads`` N-Quads.  Below the upstream's
    21M the actor table shrinks with the graph (a 20k-quad rehearsal must
    not be 400k actor names); at and above it, it is the upstream's."""
    shape = np.random.default_rng(STRUCTURE_SEED)  # the degree sequences
    rng = np.random.default_rng(seed)              # who gets which, and the dates
    n_dirs = max(1, quads // QUADS_PER_DIRECTOR)
    n_actors = int(min(ACTORS, max(64, quads // 52)))
    base = 1 + GENRES + n_actors
    director = base + np.arange(n_dirs, dtype=np.int64) * PER_DIR
    n_films = rng.permutation(_zipfish(shape, 8, 15, n_dirs))
    film_dir = np.repeat(np.arange(n_dirs, dtype=np.int64), n_films)
    first_film = np.cumsum(n_films) - n_films
    F = len(film_dir)
    film_no = np.arange(F, dtype=np.int64) - first_film[film_dir]
    n_perfs = rng.permutation(_zipfish(shape, 6, 8, F))
    # uids run sequentially through the director's window: the director,
    # then each film followed by its performances
    block = 1 + n_perfs
    before = np.cumsum(block) - block
    film = director[film_dir] + 1 + (before - before[first_film][film_dir])
    perf_film = np.repeat(np.arange(F, dtype=np.int64), n_perfs)
    first_perf = np.cumsum(n_perfs) - n_perfs
    P = len(perf_film)
    perf = film[perf_film] + 1 + (np.arange(P, dtype=np.int64) - first_perf[perf_film])
    film_date = np.stack(
        [1960 + rng.integers(0, 60, F), 1 + rng.integers(0, 9, F),
         rng.integers(0, 9, F)], axis=1,
    )
    return FilmGraph(
        n_actors=n_actors,
        director=director,
        film=film,
        film_dir=film_dir,
        film_no=film_no,
        film_genre=rng.permutation(_zipfish(shape, 4, GENRES, F)),   # uids 1..GENRES
        film_date=film_date,
        perf=perf,
        perf_film=perf_film,
        perf_actor=1 + GENRES + rng.permutation(
            (n_actors * shape.random(P) ** 4.0).astype(np.int64)),
    )


def nquad_lines(g: FilmGraph, d_lo: int, d_hi: int) -> list:
    """N-Quad lines for directors [d_lo, d_hi); the genre and actor name
    tables ride the chunk that starts at director 0."""
    out = []
    if d_lo == 0:
        out += [f'<0x{1 + i:x}> <name> "Genre {i}" .' for i in range(GENRES)]
        a0 = g.actor_base
        out += [f'<0x{a0 + i:x}> <name> "Actor {i}" .' for i in range(g.n_actors)]
    out += [
        f'<0x{u:x}> <name> "Director {i}" .'
        for i, u in zip(range(d_lo, d_hi), g.director[d_lo:d_hi].tolist())
    ]
    f_lo, f_hi = np.searchsorted(g.film_dir, [d_lo, d_hi])
    fu = g.film[f_lo:f_hi].tolist()
    fd = g.film_dir[f_lo:f_hi].tolist()
    out += [
        f'<0x{u:x}> <name> "Film {d}-{n}" .'
        for u, d, n in zip(fu, fd, g.film_no[f_lo:f_hi].tolist())
    ]
    out += [
        f'<0x{u:x}> <initial_release_date> "{y}-0{m}-1{d}" .'
        for u, (y, m, d) in zip(fu, g.film_date[f_lo:f_hi].tolist())
    ]
    out += [
        f"<0x{d:x}> <director.film> <0x{u:x}> ."
        for d, u in zip(g.director[g.film_dir[f_lo:f_hi]].tolist(), fu)
    ]
    out += [
        f"<0x{u:x}> <genre> <0x{x:x}> ."
        for u, x in zip(fu, g.film_genre[f_lo:f_hi].tolist())
    ]
    p_lo, p_hi = np.searchsorted(g.perf_film, [f_lo, f_hi])
    pu = g.perf[p_lo:p_hi].tolist()
    out += [
        f"<0x{p:x}> <performance.actor> <0x{a:x}> ."
        for p, a in zip(pu, g.perf_actor[p_lo:p_hi].tolist())
    ]
    out += [
        f"<0x{f:x}> <starring> <0x{p:x}> ."
        for f, p in zip(g.film[g.perf_film[p_lo:p_hi]].tolist(), pu)
    ]
    return out


def write_rdf_gz(g: FilmGraph, path: str) -> dict:
    """Render ``g`` to a gzip N-Quad file; returns {quads, seconds, bytes}."""
    t0 = time.monotonic()
    n = 0
    chunk = 20_000  # directors rendered at a time (~1.7M lines in memory)
    # level 1: the file is set-up for one load, not an archive
    with gzip.open(path, "wb", compresslevel=1) as f:
        for lo in range(0, len(g.director), chunk):
            lines = nquad_lines(g, lo, min(lo + chunk, len(g.director)))
            n += len(lines)
            f.write(("\n".join(lines) + "\n").encode())
    return {
        "quads": n,
        "seconds": round(time.monotonic() - t0, 2),
        "bytes": os.path.getsize(path),
    }
