"""Output checks of training configurations: one module per name a
configuration gives under `"check"`, each with `check(run) -> rows` of
(name, value, limit, ok) and `shapes(run) -> dict` for the count
functions. `run` is child.TrainRun; a check brings its own copy of the
reference and imports nothing of the program."""
