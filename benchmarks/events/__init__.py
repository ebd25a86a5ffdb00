"""Event generators: one module per name a configuration gives under
`"events"`, each with `generate(config, seed) -> (columns, truth)`.
numpy only, nothing of the program. `columns` is what the harness's bulk
writer (fill.fill_event_store) takes; `truth` goes to the
configuration's check unchanged."""
