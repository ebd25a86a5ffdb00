"""`rate` events of users on items with a `rating` property, all at one
event time: what the `recommendation` template's data source reads.
Sizes from the configuration (`n_users`, `n_items`, `n_events`,
`structure_seed`), everything else from the seed
(lib/datagen.rating_events)."""

from __future__ import annotations

import json

import numpy as np

from benchmarks.lib import datagen

#: 2015-03-31T00:00:00Z, the last day of MovieLens-20M's ratings
EVENT_TIME_MS = 1_427_760_000_000


def generate(config: dict, seed: int):
    users, items, ratings = datagen.rating_events(
        config["n_users"], config["n_items"], config["n_events"], seed,
        config.get("structure_seed", 0))
    # one JSON text per rating value, as the store's DataMap writes it
    values, codes = np.unique(ratings, return_inverse=True)
    texts = np.array([json.dumps({"rating": v}, sort_keys=True)
                      for v in values.tolist()], dtype=object)
    columns = {
        "event": "rate",
        "entity_type": "user", "entity_id": users + 1,
        "target_entity_type": "item", "target_entity_id": items + 1,
        "properties": texts[codes],
        "event_time_ms": EVENT_TIME_MS,
    }
    return columns, {"users": users, "items": items, "ratings": ratings}
