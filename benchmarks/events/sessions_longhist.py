"""`view` events of long single-session histories for the `sessionrec`
template: `n_users` users, each one session of `session_len` items over
`n_items` items, one second apart. Sizes and shares from the
configuration (`n_users`, `n_items`, `session_len`, `zipf_exponent`,
`successor_share`), everything else from the seed.

The items lie on one seeded cycle. A session opens with its share of one
sweep over the cycle (ceil(n_items / n_users) items, so that the users
together view every item and the vocabulary is the whole catalogue);
after that the next item is the last one's successor on the cycle
`successor_share` of the time, so there is something to learn, and
otherwise a jump to an item drawn by popularity (Zipf over a seeded
ranking). Rows are shuffled: the data source has to order a session by
event time."""

from __future__ import annotations

import numpy as np

#: 2024-01-01T00:00:00Z
START_MS = 1_704_067_200_000


def generate(config: dict, seed: int):
    n_users, n_items, length = (config[k] for k in
                                ("n_users", "n_items", "session_len"))
    rng = np.random.default_rng([seed, n_users, n_items, length])
    cycle = rng.permutation(n_items)              # cycle position -> item
    where = np.empty(n_items, np.int64)
    where[cycle] = np.arange(n_items)             # item -> cycle position
    by_rank = rng.permutation(n_items)            # popularity rank -> item
    weights = np.arange(1, n_items + 1) ** -float(config["zipf_exponent"])
    cdf = np.cumsum(weights / weights.sum())

    sweep = -(-n_items // n_users)
    jump = rng.random((n_users, length)) >= config["successor_share"]
    jump[:, :sweep] = False
    jump[:, 0] = True
    ranks = np.minimum(np.searchsorted(cdf, rng.random((n_users, length))),
                       n_items - 1)
    landing = where[by_rank[ranks]]               # cycle position jumped to
    landing[:, 0] = (np.arange(n_users) * sweep) % n_items
    # each position continues from the last jump before it, along the cycle
    at = np.arange(length)[None, :]
    last = np.maximum.accumulate(np.where(jump, at, 0), axis=1)
    sessions = cycle[(np.take_along_axis(landing, last, axis=1)
                      + at - last) % n_items]     # [users, length] items

    users = np.repeat(np.arange(n_users), length)
    when = START_MS + 86_400_000 * users \
        + 1000 * np.tile(np.arange(length), n_users)
    order = rng.permutation(n_users * length)
    columns = {
        "event": "view",
        "entity_type": "user", "entity_id": (users + 1)[order],
        "target_entity_type": "item",
        "target_entity_id": (sessions.reshape(-1) + 1)[order],
        "properties": None,
        "event_time_ms": when[order],
    }
    return columns, {"sessions": sessions + 1}
