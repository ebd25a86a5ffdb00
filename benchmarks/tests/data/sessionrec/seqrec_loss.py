"""TEST DATA (benchmarks/tests/test_files_only.py): the check of a
`sessionrec` train. The released weights, under a plain numpy forward
pass of the block models/seqrec.py documents (pre-norm, causal attention,
4d GELU, tied softmax), give the generated sessions a next-item loss
under half of what the same seeded weights give untrained (`epochs` 0,
through train_again); and every generated item is in the vocabulary."""

import numpy as np


def _norm(x, ln):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / np.sqrt(var + 1e-6) * ln["scale"] + ln["bias"]


def _gelu(x):
    return 0.5 * x * (1 + np.tanh(np.sqrt(2 / np.pi) * (x + 0.044715 * x ** 3)))


def next_item_nll(model, sessions) -> float:
    """Mean -log p(next item) over the generated sessions (item codes
    1..n in the model's vocabulary order, no padding: every session is
    shorter than max_len)."""
    p = {k: v for k, v in model.params.items()}
    heads = model.hyper.n_heads
    seqs, targets = sessions[:, :-1], sessions[:, 1:]
    b, l = seqs.shape
    d = p["emb"].shape[1]
    h = p["emb"][seqs] + p["pos"][None, :l]
    causal = np.tril(np.ones((l, l), bool))
    for layer in p["layers"]:
        q, k, v = np.split(_norm(h, layer["ln1"]) @ layer["wqkv"], 3, axis=-1)
        q, k, v = (t.reshape(b, l, heads, d // heads) for t in (q, k, v))
        s = np.einsum("bqhd,bkhd->bhqk", q, k) * (d // heads) ** -0.5
        s = np.where(causal, s, -np.inf)
        w = np.exp(s - s.max(-1, keepdims=True))
        w /= w.sum(-1, keepdims=True)
        att = np.einsum("bhqk,bkhd->bqhd", w, v).reshape(b, l, d)
        h = h + att @ layer["wo"]
        h = h + _gelu(_norm(h, layer["ln2"]) @ layer["w1"]) @ layer["w2"]
    logits = _norm(h, p["ln_f"]) @ p["emb"].T
    logp = logits - logits.max(-1, keepdims=True)
    logp = logp - np.log(np.exp(logp).sum(-1, keepdims=True))
    return float(-np.take_along_axis(logp, targets[..., None], -1).mean())


def _coded(model, sessions):
    """Generated item numbers -> the model's item codes; None where an
    item is not in the released vocabulary."""
    code = {str(it): i + 1 for i, it in enumerate(model.item_vocab)}
    flat = [code.get(str(it), 0) for it in sessions.reshape(-1).tolist()]
    return np.asarray(flat).reshape(sessions.shape)


def check(run):
    limits = run.config["limits"]
    sessions = run.truth["sessions"]
    trained = run.load_model(run.instance)
    start = run.load_model(run.train_again({"epochs": 0}))
    coded = _coded(trained, sessions)
    unknown = int((coded == 0).sum())
    ratio = float("inf") if unknown else \
        next_item_nll(trained, coded) / next_item_nll(start, coded)
    return [("seqrec_nll_vs_start", ratio, limits["seqrec_nll_vs_start"],
             bool(ratio <= limits["seqrec_nll_vs_start"])),
            ("seqrec_items_unknown", unknown, 0, unknown == 0)]


def shapes(run):
    model = run.load_model(run.instance)
    return {"n_items": len(model.item_vocab),
            "d_model": int(model.params["emb"].shape[1])}
