#!/usr/bin/env python3
"""Whether a sequence configuration's train step is the program it was (a
builder's tool; no cell runs it, and it needs no chip):

    python3 benchmarks/tools/step_text_hash.py            > here.txt
    python3 benchmarks/tools/step_text_hash.py --tree DIR > there.txt
    diff here.txt there.txt

Lowers `models/seqrec.make_train_step` of the default spec and of every
sequence configuration (the tiny section and the cell's own) for a TPU
v5e from abstract arguments, on the CPU, and prints a sha256 of the text
a line: a configuration whose line reads the same in two trees runs the
same program in both. `--tree DIR` imports `predictionio_tpu` from
another checkout (a `git archive` of a commit in a git-ignored
directory), a process a tree.

The Mosaic kernels are IN the hashed text. A Pallas call lowers to a
custom call whose `backend_config` holds the kernel's module serialised
with the source locations of the lines that built it, so the raw text
differs whenever a line above a kernel moves. Cutting the payload out
(what PRs 38 to 40 hashed) hides exactly what a change to an index map or
a block shape changes. Here each payload is parsed and printed again
without its debug locations, and that text is what is hashed: grid,
block shapes, index maps and kernel bodies all count. The last number of
a line is how many kernels the step holds.
"""

from __future__ import annotations

import argparse
import base64
import hashlib
import json
import os
import re
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
KIND = "TPU v5 lite"
_CONFIG = re.compile(r'backend_config = "(?:[^"\\]|\\.)*"')
_BODY = re.compile(r'body\\22: \\22([A-Za-z0-9+/=]+)\\22')


def kernel_text(payload: str) -> str:
    """A serialised Mosaic module without its debug locations."""
    from jax._src.interpreters import mlir
    from jax._src.lib import tpu
    from jax._src.lib.mlir import ir

    ctx = mlir.make_ir_context()
    tpu.register_dialect(ctx)
    with ctx:
        # (the serialised form names its dialect `stable_mosaic`)
        ctx.allow_unregistered_dialects = True
        module = ir.Module.parse(base64.b64decode(payload))
        return module.operation.get_asm(enable_debug_info=False)


def step_hash(seqrec, p, n_items: int):
    """(sha256 of the step's text lowered for a TPU, its length, the
    kernels in it)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    optimizer = seqrec.make_optimizer(p)
    params = jax.eval_shape(lambda: seqrec.init_params(
        None if p.device_init else np.random.default_rng(0), n_items, p))
    seqs = jax.ShapeDtypeStruct((p.batch_size, p.max_len), jnp.int32)
    text = seqrec.make_train_step(None, p, optimizer).trace(
        params, jax.eval_shape(optimizer.init, params), seqs, seqs).lower(
            lowering_platforms=("tpu",)).as_text()
    kernels = 0

    def without_locations(m):
        nonlocal kernels
        body = _BODY.search(m.group(0))
        if body is None:
            return m.group(0)
        kernels += 1
        return m.group(0).replace(body.group(1), hashlib.sha256(
            kernel_text(body.group(1)).encode()).hexdigest())

    text = _CONFIG.sub(without_locations, text)
    return hashlib.sha256(text.encode()).hexdigest()[:16], len(text), kernels


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--tree", default=ROOT,
                    help="the checkout whose predictionio_tpu is lowered")
    ap.add_argument("--only", default="",
                    help="lower the specs whose name holds this")
    args = ap.parse_args(argv)
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    sys.path.insert(0, os.path.abspath(args.tree))
    from predictionio_tpu.models import seqrec
    from predictionio_tpu.ops import attention, linear_attention, moe

    for module in (attention, linear_attention, moe):
        if hasattr(module, "_device_kind"):     # route as the chip would
            module._device_kind = lambda: KIND
    specs = [("default", seqrec.SeqRecParams(), 50)]
    configs = os.path.join(os.path.abspath(args.tree), "benchmarks",
                           "configs")
    for name in sorted(os.listdir(configs)):
        with open(os.path.join(configs, name)) as f:
            cfg = json.load(f)
        if not name.startswith("seqrec-"):
            continue
        for tag, part in (("tiny", cfg["tiny"]), ("cell", cfg)):
            specs.append((f"{name[:-5]}.{tag}",
                          seqrec.SeqRecParams(**part["algorithm_params"]),
                          part["n_items"]))
    for name, p, n_items in specs:
        if args.only in name:
            print(name, *step_hash(seqrec, p, n_items), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
