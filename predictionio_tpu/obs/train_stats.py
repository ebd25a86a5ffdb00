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

The sequence model's train (`models/seqrec.train_seqrec`) is wrapped in
`seqrec_prepare` (vocabulary, coding, padding), `seqrec_init` (drawing
the weights), `seqrec_put` (sharding, the optimizer's state),
`seqrec_steps` (an epoch's steps, each waited for) and
`seqrec_fetch` spans, with:

* ``pio_train_seqrec_param_bytes`` — bytes of the last train's weights.
* ``pio_train_seqrec_step_seconds`` — one step's wall from its dispatch
  until its loss is ready, one sample a step; warm steps only.
* ``pio_train_seqrec_tokens_total`` / ``pio_train_seqrec_pad_tokens_total``
  — positions of the trained batches that carry a target / that are
  padding.
* ``pio_train_seqrec_attention_tokens_total{impl}`` — positions of the
  trained batches, padding too, by the route ``blockwise_attention``
  took when their step was traced: ``pallas`` (every softmax-attention
  layer through the kernels of ops/attention_pallas.py) or ``xla``. A
  model without such a layer counts nothing here.
* ``pio_train_seqrec_attention_layout_tokens_total{layout}`` — the
  positions counted under ``impl="pallas"`` above, by where the kernels
  read a head when their step was traced: ``rows`` (every
  softmax-attention layer's q, k, v token-first, [B, L, heads x width]
  as a projection writes them, a head a block of columns:
  ops/attention.rotary_attention at widths of whole lane tiles) or
  ``heads`` ([B, heads, L, width] behind a transpose: every caller of
  ``blockwise_attention``). A step on the scan counts nothing here.
* ``pio_train_seqrec_linear_attention_tokens_total{impl}`` — positions of
  the trained batches, padding too, times the linear-attention
  (``gdn``) layers, by the route ``gated_delta_rule`` took when their
  step was traced: ``pallas`` (every such layer's recurrence through
  the kernels of ops/linear_attention_pallas.py) or ``xla``. A model
  without such a layer counts nothing here.
* ``pio_train_seqrec_linear_attention_chain_tokens_total{impl}`` — the
  same positions times layers, by the route of what such a layer runs
  around the rule (convolution, SiLU, unit length, head norm and gate)
  when their step was traced: ``pallas`` (every such layer through the
  fused passes of ops/linear_attention_pallas.gated_delta_chain_pallas)
  or ``xla``. The chain's route is the rule's
  (ops/linear_attention.gated_delta_chain decides both), so the two
  counters move together.
* ``pio_train_seqrec_short_conv_chain_tokens_total{impl}`` — positions
  of the trained batches, padding too, times the short-convolution
  (``conv``) layers, by the route of what such a layer runs between its
  two projections (the gates and the causal convolution) when their step
  was traced: ``pallas`` (every such layer through the fused passes of
  ops/short_conv_pallas.py) or ``xla``
  (ops/linear_attention.gated_short_conv_route decides). A model without
  such a layer counts nothing here.
* ``pio_train_seqrec_mixer_tokens_total{mixer}`` — positions of the
  trained batches, padding too, times the layers of each mixer
  (``mha``, ``mla``, ``gqa``, ``swa``, ``gdn``, ``conv``, ``ssm``) the
  compiled step ran, a multi-token-prediction module's among them; a layer that
  is a feed-forward alone counts under no mixer.
* ``pio_train_seqrec_window_band_pairs_total`` /
  ``pio_train_seqrec_window_block_pairs_total`` — of the sliding-window
  (``swa``) layers of the trained batches, a session, layer and query
  head: the (query, key) pairs inside the band (a query's own key and
  the window - 1 before it) and the pairs of the blocks that the route
  their step took visits for them (its pair table's entries times a
  block pair's scores: ops/attention.band_pairs). Their ratio is how
  much of the computed scores counts. A model without such a layer
  counts nothing here; the windowed calls' route and layout are counted
  with the full layers' under the two attention counters above.
* ``pio_train_seqrec_layer_pass_tokens_total{pass}`` — positions of the
  trained batches, padding too, times the layers the compiled step ran
  in the stack's ``first`` pass and in its ``repeat``s (`n_loops`): a
  step that ran one pass counts 0 repeats. Every layer counts, a layer
  of one sub-layer and a multi-token-prediction module's too.
* ``pio_train_seqrec_mtp_loss`` — under a multi-token-prediction module,
  the module's own cross-entropy (position t against item t + 2) in a
  train's last step, beside the record's ``loss``, which holds it
  ``mtp_loss_weight`` times.
* ``pio_train_seqrec_loop_loss{loop}`` / ``pio_train_seqrec_exit_share{loop}``
  — under an exit gate, each pass's own next-item loss and the mean
  probability of leaving at it, over the targets of a train's last step.
* ``pio_train_seqrec_expert_tokens_total{layer}`` — tokens the experts
  held here received, by expert layer (under `n_loops` an expert layer
  once a pass).
* ``pio_train_seqrec_expert_load_max_over_mean`` — the busiest routed
  expert's tokens over the mean, over all the router's experts, mean
  over a train's steps and expert layers (one sample a train).
* ``pio_train_seqrec_dropped_tokens_total`` — tokens routed to an expert
  held here whose output row is all zeros (read from the layer's
  output; expected 0).
* ``pio_train_seqrec_fetch_bytes_total`` — bytes of weights fetched from
  the device after training (their copies start in ``seqrec_fetch`` and
  arrive under the release's write).
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


#: 1 ms .. ~2 min doubling — one optimizer step
STEP_BUCKETS = exponential_buckets(0.001, 2.0, 17)

#: max load over mean load: 1 is perfect balance
LOAD_RATIO_BUCKETS = (1.0, 1.05, 1.1, 1.2, 1.35, 1.5, 2.0, 3.0, 5.0, 10.0,
                      100.0)


def seqrec_param_bytes(registry: MetricsRegistry = None):
    return (registry or default_registry()).gauge(
        "pio_train_seqrec_param_bytes",
        "Bytes of the sequence model's weights in the last train")


def seqrec_step_seconds(registry: MetricsRegistry = None):
    return (registry or default_registry()).histogram(
        "pio_train_seqrec_step_seconds",
        "One step's wall time in the sequence model's train, from its "
        "dispatch until its loss is ready", buckets=STEP_BUCKETS)


def seqrec_tokens(registry: MetricsRegistry = None):
    return (registry or default_registry()).counter(
        "pio_train_seqrec_tokens_total",
        "Positions of the trained batches that carry a target")


def seqrec_pad_tokens(registry: MetricsRegistry = None):
    return (registry or default_registry()).counter(
        "pio_train_seqrec_pad_tokens_total",
        "Positions of the trained batches that are padding")


def seqrec_attention_tokens(registry: MetricsRegistry = None):
    return (registry or default_registry()).counter(
        "pio_train_seqrec_attention_tokens_total",
        "Positions of the trained batches by the route their step's "
        "attention was traced on (ops/attention.attention_route)",
        labelnames=("impl",))


def seqrec_attention_layout_tokens(registry: MetricsRegistry = None):
    return (registry or default_registry()).counter(
        "pio_train_seqrec_attention_layout_tokens_total",
        "Positions of the trained batches whose step's attention was "
        "traced on the Pallas kernels, by where the kernels read a head "
        "(ops/attention.attention_layout)",
        labelnames=("layout",))


def seqrec_linear_attention_tokens(registry: MetricsRegistry = None):
    return (registry or default_registry()).counter(
        "pio_train_seqrec_linear_attention_tokens_total",
        "Positions of the trained batches times the linear-attention "
        "layers, by the route their step's delta rule was traced on "
        "(ops/linear_attention.gated_delta_rule_route)",
        labelnames=("impl",))


def seqrec_linear_attention_chain_tokens(registry: MetricsRegistry = None):
    return (registry or default_registry()).counter(
        "pio_train_seqrec_linear_attention_chain_tokens_total",
        "Positions of the trained batches times the linear-attention "
        "layers, by the route their step's chain around the delta rule "
        "was traced on (ops/linear_attention.gated_delta_chain: the "
        "rule's)",
        labelnames=("impl",))


def seqrec_short_conv_chain_tokens(registry: MetricsRegistry = None):
    return (registry or default_registry()).counter(
        "pio_train_seqrec_short_conv_chain_tokens_total",
        "Positions of the trained batches times the short-convolution "
        "layers, by the route their step's chain between the layer's two "
        "projections was traced on "
        "(ops/linear_attention.gated_short_conv_route)",
        labelnames=("impl",))


def seqrec_mixer_tokens(registry: MetricsRegistry = None):
    return (registry or default_registry()).counter(
        "pio_train_seqrec_mixer_tokens_total",
        "Positions of the trained batches times the layers of each "
        "mixer the compiled step ran", labelnames=("mixer",))


def seqrec_window_band_pairs(registry: MetricsRegistry = None):
    return (registry or default_registry()).counter(
        "pio_train_seqrec_window_band_pairs_total",
        "(query, key) pairs inside the band of the trained batches' "
        "sliding-window layers, a session, layer and query head")


def seqrec_window_block_pairs(registry: MetricsRegistry = None):
    return (registry or default_registry()).counter(
        "pio_train_seqrec_window_block_pairs_total",
        "Pairs of the blocks the route of the trained batches' "
        "sliding-window layers visits, a session, layer and query head "
        "(ops/attention.band_pairs)")


def seqrec_rows(registry: MetricsRegistry = None):
    return (registry or default_registry()).counter(
        "pio_train_seqrec_rows_total",
        "Rows of the trained batches of a packed train (models/seqrec."
        "pack_sessions: several whole sessions a row)")


def seqrec_packed_sessions(registry: MetricsRegistry = None):
    return (registry or default_registry()).counter(
        "pio_train_seqrec_packed_sessions_total",
        "Sessions laid into the rows of the trained batches of a packed "
        "train")


def seqrec_packed_attention_pairs(registry: MetricsRegistry = None):
    return (registry or default_registry()).counter(
        "pio_train_seqrec_packed_attention_pairs_total",
        "(query, key) pairs of one session (causal) that the full-"
        "attention layers of a packed train's batches see, a row, layer "
        "and query head")


def seqrec_packed_attention_block_pairs(registry: MetricsRegistry = None):
    return (registry or default_registry()).counter(
        "pio_train_seqrec_packed_attention_block_pairs_total",
        "Pairs of the block pairs that the route of a packed train's "
        "full-attention layers multiplied, a row, layer and query head "
        "(ops/attention.session_pairs)")


def seqrec_packed_window_pairs(registry: MetricsRegistry = None):
    return (registry or default_registry()).counter(
        "pio_train_seqrec_packed_window_pairs_total",
        "(query, key) pairs of one session (causal, inside the band) that "
        "the sliding-window layers of a packed train's batches see, a "
        "row, layer and query head")


def seqrec_packed_window_block_pairs(registry: MetricsRegistry = None):
    return (registry or default_registry()).counter(
        "pio_train_seqrec_packed_window_block_pairs_total",
        "Pairs of the block pairs that the route of a packed train's "
        "sliding-window layers multiplied, a row, layer and query head "
        "(ops/attention.session_pairs)")


#: a packed train's pair counters by the scope of the layers' kind: (the
#: pairs that count, the pairs of the block pairs multiplied for them)
_PACKED_PAIRS = {
    "seqrec_attention": (seqrec_packed_attention_pairs,
                         seqrec_packed_attention_block_pairs),
    "seqrec_window_attention": (seqrec_packed_window_pairs,
                                seqrec_packed_window_block_pairs)}


def seqrec_layer_pass_tokens(registry: MetricsRegistry = None):
    return (registry or default_registry()).counter(
        "pio_train_seqrec_layer_pass_tokens_total",
        "Positions of the trained batches times the layers the compiled "
        "step ran in the stack's first pass and in its repeats",
        labelnames=("pass",))


def seqrec_loop_loss(registry: MetricsRegistry = None):
    return (registry or default_registry()).gauge(
        "pio_train_seqrec_loop_loss",
        "Each pass's own next-item loss in the last train's last step",
        labelnames=("loop",))


def seqrec_exit_share(registry: MetricsRegistry = None):
    return (registry or default_registry()).gauge(
        "pio_train_seqrec_exit_share",
        "Mean probability of leaving at each pass over the targets of the "
        "last train's last step", labelnames=("loop",))


def seqrec_mtp_loss(registry: MetricsRegistry = None):
    return (registry or default_registry()).gauge(
        "pio_train_seqrec_mtp_loss",
        "The multi-token-prediction module's own loss in the last "
        "train's last step")


def seqrec_expert_tokens(registry: MetricsRegistry = None):
    return (registry or default_registry()).counter(
        "pio_train_seqrec_expert_tokens_total",
        "Tokens the experts held here received, by expert layer",
        labelnames=("layer",))


def seqrec_expert_product_tokens(registry: MetricsRegistry = None):
    return (registry or default_registry()).counter(
        "pio_train_seqrec_expert_product_tokens_total",
        "Routed slots the experts held here multiplied, by the route "
        "their step's grouped products were traced on "
        "(ops/moe.grouped_product_route)", labelnames=("impl",))


def seqrec_expert_load_ratio(registry: MetricsRegistry = None):
    return (registry or default_registry()).histogram(
        "pio_train_seqrec_expert_load_max_over_mean",
        "Busiest routed expert's tokens over the mean, mean over a "
        "train's steps and expert layers", buckets=LOAD_RATIO_BUCKETS)


def seqrec_dropped_tokens(registry: MetricsRegistry = None):
    return (registry or default_registry()).counter(
        "pio_train_seqrec_dropped_tokens_total",
        "Tokens routed to an expert held here whose output is all zeros")


def seqrec_fetch_bytes(registry: MetricsRegistry = None):
    return (registry or default_registry()).counter(
        "pio_train_seqrec_fetch_bytes_total",
        "Bytes of sequence-model weights fetched from the device after "
        "training")


def observe_seqrec_record(record: dict, targets, rows,
                          attention_impl: str, linear_attention_impl: str,
                          expert_product_impl: str,
                          mixer_layers: dict, family_layers: dict,
                          layer_passes: dict = None,
                          attention_layout: str = "heads",
                          short_conv_impl: str = "xla",
                          window_pairs: tuple = None,
                          row_sessions: list = None,
                          packed_pairs: dict = None) -> None:
    """The token and expert counters from one train's record
    (models/seqrec.train_seqrec): `targets` the padded target ids of all
    sessions, `rows` the sessions of each step's batch, `attention_impl`
    and `linear_attention_impl` the routes its step's softmax and linear
    attention (the rule and the chain around it) were traced on,
    `mixer_layers` the layer passes that step ran by mixer,
    `family_layers` the same by the family each mixer's record counts its
    tokens under ("attention", "linear_attention", "short_conv", None),
    `layer_passes` those of its first pass and of its repeats (None from
    a step of one pass: all are first), `attention_layout` where its
    attention kernels read a head, `short_conv_impl` the route its
    short-convolution layers' chain was traced on, `window_pairs` (the
    pairs inside the band, the pairs of the blocks visited) of a session
    and head over its step's sliding-window layers (None: no such
    layer, or a packed train); of a packed train (`rows` then its
    batches' rows, padding their unfilled tails) `row_sessions` [step,
    row], the sessions laid into each row, and `packed_pairs` {scope:
    (pairs that count, pairs multiplied)} over all its rows and each
    scope's layers (`_PACKED_PAIRS`)."""
    import numpy as np

    real = sum(int((targets[r] > 0).sum()) for r in rows)
    positions = sum(targets[r].size for r in rows)
    seqrec_tokens().inc(real)
    seqrec_pad_tokens().inc(positions - real)
    for mixer, layers in mixer_layers.items():
        seqrec_mixer_tokens().inc(positions * layers, mixer=mixer)
    if layer_passes is None:
        layer_passes = {"first": sum(mixer_layers.values()), "repeat": 0}
    for name, layers in layer_passes.items():
        seqrec_layer_pass_tokens().inc(positions * layers, **{"pass": name})
    for key, gauge in (("loop_loss", seqrec_loop_loss),
                       ("exit_share", seqrec_exit_share)):
        if record.get(key):
            for loop, value in enumerate(record[key][-1]):
                gauge().set(value, loop=str(loop))
    if record.get("mtp_loss"):
        seqrec_mtp_loss().set(record["mtp_loss"][-1])
    if "attention" in family_layers:
        seqrec_attention_tokens().inc(positions, impl=attention_impl)
        if attention_impl == "pallas":
            seqrec_attention_layout_tokens().inc(positions,
                                                 layout=attention_layout)
    if window_pairs is not None:
        sessions = sum(len(r) for r in rows)
        seqrec_window_band_pairs().inc(sessions * window_pairs[0])
        seqrec_window_block_pairs().inc(sessions * window_pairs[1])
    if row_sessions is not None:
        seqrec_rows().inc(sum(len(step) for step in row_sessions))
        seqrec_packed_sessions().inc(sum(map(sum, row_sessions)))
    for scope, pairs in (packed_pairs or {}).items():
        for counter, n in zip(_PACKED_PAIRS[scope], pairs):
            counter().inc(n)
    if "linear_attention" in family_layers:
        for counter in (seqrec_linear_attention_tokens,
                        seqrec_linear_attention_chain_tokens):
            counter().inc(positions * family_layers["linear_attention"],
                          impl=linear_attention_impl)
    if "short_conv" in family_layers:
        seqrec_short_conv_chain_tokens().inc(
            positions * family_layers["short_conv"], impl=short_conv_impl)
    if "load" not in record or not record["load"]:
        return
    load = np.asarray(record["load"], np.float64)      # [step, layer, expert]
    seqrec_expert_load_ratio().observe(
        float((load.max(-1) / np.maximum(load.mean(-1), 1e-9)).mean()))
    held = np.asarray(record["held_tokens"]).sum(axis=(0, 2))
    for layer, tokens in enumerate(held.tolist()):
        seqrec_expert_tokens().inc(tokens, layer=str(layer))
    seqrec_expert_product_tokens().inc(int(held.sum()),
                                       impl=expert_product_impl)
    seqrec_dropped_tokens().inc(int(np.asarray(record["dropped"]).sum()))
