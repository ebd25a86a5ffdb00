"""Three sequence cells' train steps (state-space, looped, sliding-window)
compiled at their real sizes for one described (not attached) v5e: the
kernels each layer kind runs on, how often, and that arguments +
temporaries fit the chip. Nothing runs, so nothing is measured. The next
configuration's step goes into the lighter of this file and
`test_tpu_compile_steps_b.py` (ROADMAP.md D17).

The topology's fixtures (`chips`, `one_chip`) and the helpers
(`_cell_step`: a configuration's step lowered and compiled) live in
tests/conftest.py; the driver's command lets each worker load the TPU's
library (`ALLOW_MULTIPLE_LIBTPU_LOAD=1`), so the three
`test_tpu_compile_*` files may run on three workers at once."""

from conftest import _cell_step, _kernel_calls, _relayouts


def test_the_looped_cells_step_fits_a_v5e(chips, one_chip, monkeypatch):
    """ouro-2.6b-pp8.train's step compiled for one described v5e: 8,192
    positions four times through six layers as ONE loop of passes around
    one copy of the stack (the kernels' calls from a `while` body: two
    forward a layer under `remat`, one backward, six layers, once), the
    whole vocabulary's head a pass at a time; arguments + temporaries
    leave the 16 GB chip 1 GB and more. (The stack written out four
    times compiles to 12.1 GB of temporaries beside 6.1 of arguments and
    does not fit: PERF.md section 6, PR 38.)"""
    from predictionio_tpu.models import seqrec
    from predictionio_tpu.ops import attention

    kind = chips[0].device_kind
    monkeypatch.setattr(attention, "_device_kind", lambda: kind)
    assert seqrec.LOOP_UNROLL == 1
    p, _, compiled = _cell_step("seqrec-ouro-2.6b-pp8", one_chip)
    assert (p.n_loops, p.n_layers, p.max_len, p.remat) == (4, 6, 8192, True)
    text = compiled.as_text()
    assert _kernel_calls(text, "flash_attention_pallas_fwd") == 2 * 6
    assert _kernel_calls(text, "flash_attention_pallas_bwd") == 6
    # token-first: the rotary pass in front of every forward call, its
    # transpose behind every backward call, and no activation relaid
    # between a projection and a kernel
    assert _kernel_calls(text, "attention_rotary_fwd") == 2 * 6
    assert _kernel_calls(text, "attention_rotary_bwd") == 6
    assert not _relayouts(text, p.max_len, "seqrec_attention")
    memory = compiled.memory_analysis()
    held = memory.argument_size_in_bytes + memory.temp_size_in_bytes
    assert held <= 15.75 * 2 ** 30 - 1e9, held


def test_the_state_space_cells_step_fits_a_v5e(chips, one_chip, monkeypatch):
    """nemotron3-super-tp8ep64.train's step compiled for one described
    v5e: 8,192 positions through one period of single-sub-layer layers
    (an attention, five state-space and five latent expert layers at this
    chip's tensor and expert share) and the multi-token-prediction
    module; the two attention layers (the stack's and the module's)
    through the Pallas attention kernels at 4 query heads on 1 key/value
    head of 128, the six expert layers' two-matrix experts through the
    grouped-product kernels at 1024 x 2688 (no `ragged-dot`), the scan as
    XLA operations; arguments +
    temporaries (6.8 + 4.1 GiB: PERF.md section 6, PR 40) leave the 16
    GB chip 1 GB and more."""
    from predictionio_tpu.ops import attention, moe, moe_pallas

    kind = chips[0].device_kind
    monkeypatch.setattr(attention, "_device_kind", lambda: kind)
    monkeypatch.setattr(moe, "_device_kind", lambda: kind)
    p, parameters, compiled = _cell_step(
        "seqrec-nemotron3-super-120b-a12b-tp8ep64", one_chip)
    assert (len(p.layer_kinds()), p.max_len, p.remat) == (13, 8192, True)
    assert moe_pallas.tiles(8192, p.moe_latent_size, p.moe_width, 8) == 128
    assert parameters == 607_038_960
    text = compiled.as_text()
    assert _kernel_calls(text, "flash_attention_pallas_fwd") == 2 * 2
    assert _kernel_calls(text, "flash_attention_pallas_bwd") == 2
    # a layer's two products forward, again in `remat`'s recomputation and
    # again inside the layer's own backward pass; four gradient products
    assert _kernel_calls(text, "grouped_product_pallas_rows") == 6 * 6
    assert _kernel_calls(text, "grouped_product_pallas_rows_t") == 6 * 2
    assert _kernel_calls(text, "grouped_product_pallas_groups") == 6 * 2
    assert "ragged-dot" not in text
    memory = compiled.memory_analysis()
    held = memory.argument_size_in_bytes + memory.temp_size_in_bytes
    assert held <= 15.75 * 2 ** 30 - 1e9, held


def test_the_window_cells_step_fits_a_v5e(chips, one_chip, monkeypatch):
    """laguna-xs2-ep8.train's step compiled for one described v5e: 16,384
    positions through a full layer with a dense feed-forward, three
    sliding-window layers and a full one with their experts; the two
    full layers on the whole-causal kernels at 48 heads (two forward
    calls under `remat`, one backward, each), the three sliding ones on
    the banded kernels at 64 under names of their own, under the
    layer's own scope; the four expert layers' products on the
    grouped-product kernels at 2048 x 512; arguments + temporaries leave
    the 16 GB chip 0.5 GB and more."""
    from predictionio_tpu.models import seqrec
    from predictionio_tpu.obs import profiler
    from predictionio_tpu.ops import attention, moe

    kind = chips[0].device_kind
    for module in (attention, moe):
        monkeypatch.setattr(module, "_device_kind", lambda: kind)
    p, parameters, compiled = _cell_step("seqrec-laguna-xs2-ep8", one_chip)
    assert p.remat and p.max_len == 16384
    assert p.mixer_kinds() == ("gqa", "swa", "swa", "swa", "gqa")
    assert parameters == 691_624_960
    text = compiled.as_text()
    assert _kernel_calls(text, "flash_attention_pallas_fwd") == 2 * 2
    assert _kernel_calls(text, "flash_attention_pallas_bwd") == 2
    assert _kernel_calls(text, "window_attention_pallas_fwd") == 3 * 2
    assert _kernel_calls(text, "window_attention_pallas_bwd") == 3
    # both kinds token-first: a pass in front and the gate's pass with
    # every forward call, the gate's backward pass and the pass behind
    # with every backward one, and no activation relaid in either scope
    assert _kernel_calls(text, "grouped_attention_front") == 5 * 2
    assert _kernel_calls(text, "attention_head_gate") == 5 * 2
    assert _kernel_calls(text, "attention_head_gate_bwd") == 5
    assert _kernel_calls(text, "grouped_attention_back") == 5
    for scope in ("seqrec_attention", "seqrec_window_attention"):
        assert not _relayouts(text, p.max_len, scope), scope
    assert _kernel_calls(text, "grouped_product_pallas_[a-z_]*") == 4 * 12
    assert "ragged-dot" not in text
    rows = profiler.parse_scope_table(text, seqrec.STEP_SCOPES)[1]
    scopes = {row[0] for key, row in rows.items()
              if "window_attention_pallas" in key}
    assert scopes == {"seqrec_window_attention"}, scopes
    memory = compiled.memory_analysis()
    held = memory.argument_size_in_bytes + memory.temp_size_in_bytes
    print("window cell step: arguments", memory.argument_size_in_bytes,
          "temporaries", memory.temp_size_in_bytes)
    assert held <= 15.75 * 2 ** 30 - 0.5e9, held
