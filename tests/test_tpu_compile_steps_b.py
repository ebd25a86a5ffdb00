"""Three sequence cells' train steps (hybrid, convolution, packed)
compiled at their real sizes for one described (not attached) v5e: the
kernels each layer kind runs on, how often, and that arguments +
temporaries fit the chip. Nothing runs, so nothing is measured. The next
configuration's step goes into the lighter of this file and
`test_tpu_compile_steps_a.py` (ROADMAP.md D17).

The topology's fixtures (`chips`, `one_chip`) and the helpers
(`_cell_step`: a configuration's step lowered and compiled) live in
tests/conftest.py; the driver's command lets each worker load the TPU's
library (`ALLOW_MULTIPLE_LIBTPU_LOAD=1`), so the three
`test_tpu_compile_*` files may run on three workers at once."""

from conftest import _cell_step, _kernel_calls, _relayouts


def test_the_hybrid_cells_step_fits_a_v5e_with_all_heads_at_once(
        chips, one_chip, monkeypatch):
    """qwen3next-a3b-ep16.train's step compiled for one described v5e: on
    the kernels' route a linear layer takes all its heads at once, its
    forward kernel runs twice a layer (the block's pass and `remat`'s)
    and no third time, the fused passes around it likewise (the front's
    three calls and the back's one twice forward, once backward: PERF.md
    section 6, PR 39), and arguments + temporaries leave the 16 GB chip
    1 GB and more (the rule by which the head groups went; PERF.md
    section 6, PR 32)."""
    from predictionio_tpu.ops import attention, linear_attention, moe

    kind = chips[0].device_kind
    for module in (attention, linear_attention, moe):
        monkeypatch.setattr(module, "_device_kind", lambda: kind)
    p, _, compiled = _cell_step("seqrec-qwen3-next-80b-a3b-ep16", one_chip)
    assert p.remat and p.mixer_kinds().count("gdn") == 3
    text = compiled.as_text()
    assert _kernel_calls(text, "gated_delta_rule_pallas_fwd") == 2 * 3
    assert _kernel_calls(text, "gated_delta_rule_pallas_bwd") == 3
    for kernel, calls in (("gdn_chain_front_fwd", 3 * 2),
                          ("gdn_chain_back_fwd", 2),
                          ("gdn_chain_front_bwd", 3),
                          ("gdn_chain_back_bwd", 1)):
        assert _kernel_calls(text, kernel) == 3 * calls, kernel
    assert _kernel_calls(text, "flash_attention_pallas_bwd") == 1
    # four expert layers, twelve grouped products each
    assert _kernel_calls(text, "grouped_product_pallas_[a-z_]*") == 4 * 12
    assert "ragged-dot" not in text
    memory = compiled.memory_analysis()
    held = memory.argument_size_in_bytes + memory.temp_size_in_bytes
    assert held <= 15.75 * 2 ** 30 - 1e9, held


def test_the_conv_cells_step_fits_a_v5e(chips, one_chip, monkeypatch):
    """lfm2-a2b-ep8.train's step compiled for one described v5e: 32,768
    positions through a dense convolution layer, the attention layer on
    the kernels (two forward calls under `remat`, one backward) and
    three convolution layers with their experts, the head tied, every
    convolution layer's chain as the fused passes (likewise two forward,
    one backward); arguments + temporaries leave the 16 GB chip 1 GB and
    more."""
    from predictionio_tpu.models import seqrec
    from predictionio_tpu.obs import profiler
    from predictionio_tpu.ops import attention, linear_attention, moe

    kind = chips[0].device_kind
    for module in (attention, linear_attention, moe):
        monkeypatch.setattr(module, "_device_kind", lambda: kind)
    p, _, compiled = _cell_step("seqrec-lfm2-24b-a2b-ep8", one_chip)
    assert p.remat and p.tied_head and p.max_len == 32768
    assert p.mixer_kinds() == ("conv", "gqa", "conv", "conv", "conv")
    text = compiled.as_text()
    assert _kernel_calls(text, "flash_attention_pallas_fwd") == 2
    assert _kernel_calls(text, "flash_attention_pallas_bwd") == 1
    assert _kernel_calls(text, "grouped_product_pallas_[a-z_]*") == 4 * 12
    assert _kernel_calls(text, "short_conv_chain_fwd") == 4 * 2
    assert _kernel_calls(text, "short_conv_chain_bwd") == 4
    # the passes' instructions carry the layer's scope and their phase:
    # what `step_scope_ms.short_conv` adds their device time to
    rows = profiler.parse_scope_table(text, seqrec.STEP_SCOPES)[1]
    passes = sorted((key.split(".")[0], *row) for key, row in rows.items()
                    if "short_conv_chain" in key)
    assert passes == sorted(
        [("short_conv_chain_fwd", "seqrec_short_conv", "")] * 4
        + [("short_conv_chain_fwd", "seqrec_short_conv", "tr")] * 4
        + [("short_conv_chain_bwd", "seqrec_short_conv", "t")] * 4), passes
    assert "ragged-dot" not in text
    memory = compiled.memory_analysis()
    held = memory.argument_size_in_bytes + memory.temp_size_in_bytes
    assert held <= 15.75 * 2 ** 30 - 1e9, held


def test_the_packed_cells_step_fits_a_v5e(chips, one_chip, monkeypatch):
    """mellum2-a2.5b-ep4.train's step compiled for one described v5e: 2
    packed rows of 8,192 positions (a position's session and its place in
    it two more arguments) through three sliding-window layers and a full
    one with their experts; the full layer on the whole-causal kernels at
    32 heads over 4 (two forward calls under `remat`, one backward), the
    sliding ones on the banded kernels, both token-first and both told
    the sessions; the four expert layers' products on the grouped-product
    kernels at 2304 x 896; arguments + temporaries leave the 16 GB chip
    0.5 GB and more."""
    from predictionio_tpu.ops import attention, moe

    kind = chips[0].device_kind
    for module in (attention, moe):
        monkeypatch.setattr(module, "_device_kind", lambda: kind)
    p, parameters, compiled = _cell_step("seqrec-mellum2-12b-a2.5b-ep4",
                                         one_chip, packed=True)
    assert p.remat and p.packing and p.max_len == 8192
    assert p.mixer_kinds() == ("swa", "swa", "swa", "gqa")
    # and each layer's selection bias
    assert parameters == 595_153_152 + 4 * 64
    text = compiled.as_text()
    assert _kernel_calls(text, "flash_attention_pallas_fwd") == 2
    assert _kernel_calls(text, "flash_attention_pallas_bwd") == 1
    assert _kernel_calls(text, "window_attention_pallas_fwd") == 3 * 2
    assert _kernel_calls(text, "window_attention_pallas_bwd") == 3
    assert _kernel_calls(text, "grouped_attention_front") == 4 * 2
    assert _kernel_calls(text, "grouped_attention_back") == 4
    assert _kernel_calls(text, "attention_head_gate") == 0
    for scope in ("seqrec_attention", "seqrec_window_attention"):
        assert not _relayouts(text, p.max_len, scope), scope
    assert _kernel_calls(text, "grouped_product_pallas_[a-z_]*") == 4 * 12
    assert "ragged-dot" not in text
    memory = compiled.memory_analysis()
    held = memory.argument_size_in_bytes + memory.temp_size_in_bytes
    print("packed cell step: arguments", memory.argument_size_in_bytes,
          "temporaries", memory.temp_size_in_bytes)
    assert held <= 15.75 * 2 ** 30 - 0.5e9, held
