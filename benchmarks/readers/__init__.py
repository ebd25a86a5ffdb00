"""Reductions that are not on lib/layer_readers.MENU: one module per
kind, each with `read(evidence, reader)` returning a number or None."""
