"""Inputs and weights, made from --seed. numpy only: the parent (which
never imports JAX) and the child both call these and get the same bits,
so the reference regenerates what the program was fed without taking
anything the program made.

`rating_events` is copied from chip_smoke.write_events (every id appears,
skewed item popularity, a rank-4 signal plus 0.3 noise on the half-star
scale), returns columns instead of writing JSON lines, and keeps the
rating counts the same for every seed.
`factors` plants a low-rank signal and a popularity skew in synthetic
ALS factors for the cells that serve and score without training.
"""

from __future__ import annotations

from typing import Dict, Tuple

import numpy as np

#: standard deviation of the generator's noise before half-star rounding
RATING_NOISE = 0.3


def rating_events(n_users: int, n_items: int, n_events: int, seed: int,
                  structure_seed: int = 0
                  ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """(users, items, ratings): 0-based ids as int64, ratings as float64
    on the half-star scale. The generator of chip_smoke.write_events,
    with one change: WHO rated WHAT (how many ratings each user and item
    has) comes from `structure_seed`, fixed in the configuration, and
    `seed` relabels users and items, draws the latent signal and noise,
    and orders the events. Every seed therefore holds the same work in
    another order -- the train's padded shapes follow the rating counts,
    and a seed that crosses a bucket edge would compile a new program."""
    cover = max(n_users, n_items)
    if n_events < cover:
        raise ValueError(f"{n_events} events cannot cover {n_users} users "
                         f"x {n_items} items")
    shape = np.random.default_rng([structure_seed, n_users, n_items,
                                   n_events])
    users = np.concatenate([np.arange(cover) % n_users,
                            shape.integers(0, n_users, n_events - cover)])
    items = np.concatenate([
        np.arange(cover) % n_items,
        (n_items * shape.random(n_events - cover) ** 2).astype(np.int64)])
    rng = np.random.default_rng(seed)
    order = rng.permutation(n_events)
    users = rng.permutation(n_users)[users[order]]
    items = rng.permutation(n_items)[items[order]]
    lat_u = rng.normal(size=(n_users, 4))
    lat_v = rng.normal(size=(n_items, 4))
    raw = 3.0 + 0.7 * np.einsum("nk,nk->n", lat_u[users], lat_v[items]) \
        + RATING_NOISE * rng.normal(size=n_events)
    ratings = np.clip(np.round(raw * 2) / 2, 0.5, 5.0)
    return users.astype(np.int64), items.astype(np.int64), ratings


def entity_ids(n: int, prefix: str) -> np.ndarray:
    """Zero-padded ids, so that sorted order is numeric order and row j of
    a factor matrix belongs to id j (the program keeps vocabularies
    sorted)."""
    width = len(str(n))
    return np.char.add(prefix, np.char.zfill(
        np.arange(n).astype(str), width))


def factors(n_users: int, n_items: int, rank: int, seed: int,
            latent: int = 16, popularity_exponent: float = 0.3
            ) -> Dict[str, np.ndarray]:
    """Synthetic f32 ALS factors: both sides mix a `latent`-dimensional
    planted signal into `rank` dimensions plus noise, and item rows are
    scaled by a popularity law (rank ** -exponent over a seeded
    permutation) so that top-k is neither degenerate nor the same for
    every user."""
    rng = np.random.default_rng([seed, n_users, n_items, rank])
    mix = rng.standard_normal((latent, rank), dtype=np.float32) \
        / np.float32(np.sqrt(latent))
    U = rng.standard_normal((n_users, latent), dtype=np.float32) @ mix
    U += np.float32(0.5) * rng.standard_normal((n_users, rank),
                                               dtype=np.float32)
    V = rng.standard_normal((n_items, latent), dtype=np.float32) @ mix
    V += np.float32(0.5) * rng.standard_normal((n_items, rank),
                                               dtype=np.float32)
    pop = (1.0 + rng.permutation(n_items)) ** -popularity_exponent
    V *= (pop / pop.mean()).astype(np.float32)[:, None]
    V /= np.float32(np.sqrt(rank))
    return {"U": U, "V": V}


def zipf_ranks(n: int, alpha: float, size: int, rng: np.random.Generator
               ) -> np.ndarray:
    """`size` draws from P(r) ~ (r+1) ** -alpha over [0, n). Copied from
    loadtest/population.ZipfSampler (precomputed CDF, uniform + binary
    search), taking the generator instead of owning one."""
    weights = np.arange(1, n + 1, dtype=np.float64) ** -alpha
    cdf = np.cumsum(weights / weights.sum())
    cdf[-1] = 1.0
    return np.searchsorted(cdf, rng.random(size), side="right").astype(
        np.int64)


def query_users(n_users: int, alpha: float, size: int, seed: int
                ) -> np.ndarray:
    """User indices of `size` queries: Zipf popularity over a seeded
    permutation of the population (so the popular users are not the
    first rows of U)."""
    rng = np.random.default_rng([seed, 0x5EED])
    ranks = zipf_ranks(n_users, alpha, size, rng)
    return rng.permutation(n_users)[ranks]
