"""TEST DATA (benchmarks/tests/test_files_only.py): sessions for the
`sessionrec` template. Each user views `session_len` items one second
apart; the next item is the last one's successor nine times in ten, so
there is something to learn. Rows are shuffled: the data source has to
order a session by event time."""

import numpy as np

START_MS = 1_600_000_000_000


def generate(config: dict, seed: int):
    n_users, n_items, length = (config[k] for k in
                                ("n_users", "n_items", "session_len"))
    rng = np.random.default_rng([seed, n_users, n_items, length])
    first = rng.integers(0, n_items, n_users)
    jump = np.where(rng.random((n_users, length - 1)) < 0.9, 1,
                    rng.integers(2, n_items, (n_users, length - 1)))
    steps = np.concatenate([first[:, None], jump], axis=1)
    sessions = np.cumsum(steps, axis=1) % n_items          # [users, length]
    users = np.repeat(np.arange(n_users), length)
    when = START_MS + 60_000 * users + 1000 * np.tile(np.arange(length), n_users)
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
