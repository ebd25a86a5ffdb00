"""Training-kernel metrics: ALS solver block sweeps, Gramian cache, timing.

The subspace (iALS++ block coordinate descent) ALS solver executes its
rank-block sweeps fused inside one jitted device loop, so these metrics
are accounted host-side per training dispatch:

* ``pio_train_als_block_sweeps_total`` — rank-block solves executed
  (2 * iterations * blocks-per-sweep per train). Flat at zero on a box
  that believes it enabled the subspace solver = misconfiguration.
* ``pio_train_als_gramian_cache_hits_total`` — block solves served from
  the per-half-sweep cached Gramian/count terms (the global V^T V slices
  and the ALS-WR lambda counts are built once per half-sweep and reused
  by every subsequent block) instead of a per-block rebuild.
* ``pio_train_als_half_sweep_seconds{solver}`` — per-half-sweep wall
  time, DERIVED as dispatch wall / (2 * iterations): the sweeps run
  fused under ``lax.fori_loop``, so per-sweep sampling would require
  breaking the fusion this kernel exists to keep. WARM dispatches only:
  a run whose program had to trace+compile observes nothing, since
  compile seconds would drown the per-solver kernel comparison.

The device dispatch itself is wrapped in an ``als_solve`` span
(``pio_span_duration_seconds{span="als_solve"}``); the host work around
it in ``train_id_assign``, ``als_pack``, ``als_put`` and ``als_fetch``
spans, each with the count of what crossed that boundary:

* ``pio_train_als_entities{side}`` — distinct users / items the last
  train's id assignment saw (the padded program shapes follow them).
* ``pio_train_als_row_fill_ratio{side}`` — ratings over padded slots
  (rows x row length) of the packed layout, once per side per train:
  useful work over attempted, for the rows the Gramian assembly pays for.
* ``pio_train_als_put_bytes_total`` / ``pio_train_als_fetch_bytes_total``
  — bytes sent to the device by ``ALSData.put`` and fetched back as
  factors.
"""

from __future__ import annotations

from predictionio_tpu.obs.registry import (
    MetricsRegistry, default_registry, exponential_buckets,
)

#: a ratio in (0, 1], twentieths
FILL_RATIO_BUCKETS = tuple(i / 20 for i in range(1, 21))

#: 1 ms .. ~2 min doubling — a half-sweep, not a whole training run
HALF_SWEEP_BUCKETS = exponential_buckets(0.001, 2.0, 17)


def als_block_sweeps(registry: MetricsRegistry = None):
    return (registry or default_registry()).counter(
        "pio_train_als_block_sweeps_total",
        "Rank-block solves executed by the subspace ALS solver")


def als_gramian_cache_hits(registry: MetricsRegistry = None):
    return (registry or default_registry()).counter(
        "pio_train_als_gramian_cache_hits_total",
        "Block solves served from the per-half-sweep cached Gramian/"
        "regularization terms instead of a rebuild")


def als_half_sweep_seconds(registry: MetricsRegistry = None):
    return (registry or default_registry()).histogram(
        "pio_train_als_half_sweep_seconds",
        "Per-half-sweep ALS wall time (dispatch wall / half-sweeps), "
        "by solver", labelnames=("solver",), buckets=HALF_SWEEP_BUCKETS)


def als_entities(registry: MetricsRegistry = None):
    return (registry or default_registry()).gauge(
        "pio_train_als_entities",
        "Distinct entities the last train's id assignment saw, by side",
        labelnames=("side",))


def als_row_fill_ratio(registry: MetricsRegistry = None):
    return (registry or default_registry()).histogram(
        "pio_train_als_row_fill_ratio",
        "Ratings over padded slots (rows x row length) of the packed ALS "
        "layout, per side per train", labelnames=("side",),
        buckets=FILL_RATIO_BUCKETS)


def als_put_bytes(registry: MetricsRegistry = None):
    return (registry or default_registry()).counter(
        "pio_train_als_put_bytes_total",
        "Bytes of packed rating rows ALSData.put sent to the device")


def als_fetch_bytes(registry: MetricsRegistry = None):
    return (registry or default_registry()).counter(
        "pio_train_als_fetch_bytes_total",
        "Bytes of factor matrices fetched from the device after training")


def observe_row_fill(data) -> None:
    """One `pio_train_als_row_fill_ratio` sample per side of a packed
    ALSData (models/als.py)."""
    hist = als_row_fill_ratio()
    for side, rows in (("user", data.by_user), ("item", data.by_item)):
        slots = rows.tgt.shape[0] * rows.tgt.shape[1] * rows.row_len
        if slots:
            hist.observe(data.nnz / slots, side=side)
