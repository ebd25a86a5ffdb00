"""`view` events of heavy-tailed sessions for the `sessionrec` template:
`n_users` users, one session each, the sessions' lengths the `n_users`
quantiles ((i + 0.5) / n_users) of a log-normal, rounded and cut to
[`min`, `max`] events (`session_lengths`: median, sigma, min, max): the
same multiset of lengths on every seed, so the work of a train does not
depend on the seed. Sizes and shares from the configuration (`n_users`,
`n_items`, `session_lengths`, `zipf_exponent`, `successor_share`),
everything else from the seed: which user has which length, the items,
the order of the rows.

The items lie on one seeded cycle, as events/sessions_longhist.py's. A
session opens with its share of ONE sweep over the cycle, shared out by
length (the users together view every item, so the vocabulary is the
whole catalogue); after that the next item is the last one's successor
on the cycle `successor_share` of the time, and otherwise a jump to an
item drawn by popularity (Zipf over a seeded ranking). Events one second
apart, a user a day; rows are shuffled: the data source has to order a
session by event time."""

from __future__ import annotations

import math
import statistics

import numpy as np

#: 2024-01-01T00:00:00Z
START_MS = 1_704_067_200_000


def lengths(config: dict) -> np.ndarray:
    """[n_users] the sessions' lengths in events, rising: quantile (i +
    0.5) / n of the log-normal, rounded, cut to [min, max]."""
    n, shape = config["n_users"], config["session_lengths"]
    normal = statistics.NormalDist()
    return np.asarray([
        min(shape["max"], max(shape["min"], round(shape["median"] * math.exp(
            shape["sigma"] * normal.inv_cdf((i + 0.5) / n)))))
        for i in range(n)], np.int64)


def generate(config: dict, seed: int):
    n_users, n_items = config["n_users"], config["n_items"]
    rng = np.random.default_rng([seed, n_users, n_items])
    length = rng.permutation(lengths(config))     # user -> its length
    total = int(length.sum())
    cycle = rng.permutation(n_items)              # cycle position -> item
    where = np.empty(n_items, np.int64)
    where[cycle] = np.arange(n_items)             # item -> cycle position
    by_rank = rng.permutation(n_items)            # popularity rank -> item
    weights = np.arange(1, n_items + 1) ** -float(config["zipf_exponent"])
    cdf = np.cumsum(weights / weights.sum())

    # one sweep over the cycle, each user a stretch in proportion to its
    # session's length: it opens there and walks it before its first jump
    ends = np.cumsum(length) * n_items // total
    opens = np.concatenate([[0], ends[:-1]])
    users = np.repeat(np.arange(n_users), length)
    first = np.concatenate([[0], np.cumsum(length)[:-1]])  # user -> its row
    at = np.arange(total) - first[users]          # place in the session
    jump = rng.random(total) >= config["successor_share"]
    jump[at < (ends - opens)[users]] = False
    jump[at == 0] = True
    ranks = np.minimum(np.searchsorted(cdf, rng.random(total)), n_items - 1)
    landing = where[by_rank[ranks]]               # cycle position jumped to
    landing[at == 0] = opens
    # each event continues from the last jump before it, along the cycle
    # (a session's first event is one, so none reaches into another's)
    every = np.arange(total)
    last = np.maximum.accumulate(np.where(jump, every, 0))
    items = cycle[(landing[last] + every - last) % n_items]

    when = START_MS + 86_400_000 * users + 1000 * at
    order = rng.permutation(total)
    columns = {
        "event": "view",
        "entity_type": "user", "entity_id": (users + 1)[order],
        "target_entity_type": "item",
        "target_entity_id": (items + 1)[order],
        "properties": None,
        "event_time_ms": when[order],
    }
    return columns, {"sessions": [s + 1 for s in np.split(items,
                                                          first[1:])]}
