"""The program's paged serving path at InternLM2's shape, on the CPU:
a GQA group of 6, RMSNorm eps 1e-5, RoPE base 1e6 and a vocabulary that
is not a multiple of 256 (``tiny-internlm2``).  The logits of the
``Engine``'s prefill and of each paged decode step are held against the
plain float32 reference's full forward pass over the same tokens."""
from __future__ import annotations

import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import files, reference, run, weights

DATA = pathlib.Path(__file__).resolve().parent / "data"
# the program serves bfloat16 weights and activations and rounds its
# logits (|logit| <= ~4) to bfloat16: they lay at most 0.063 from the
# float32 reference's over 2 weight seeds x 3 prompt lengths; the
# reference at RoPE base 10,000 lay 0.55-3.99 from it
TOL = 0.15


def _served_logits(spec, flat, prompt, max_new, monkeypatch):
    """Greedy tokens and, per position that made one, the program's
    logits over the real vocabulary: the prefill's, then each paged
    decode step's row of the request's slot."""
    from repro.models.registry import Model, build_model
    from repro.serve import Engine, Request, ServeConfig
    seen = []

    def keep(logits):
        seen.append(np.asarray(logits, np.float32))

    prefill, decode = Model.prefill, Model.decode_step

    def prefill_kept(self, *a, **k):
        logits, caches = prefill(self, *a, **k)
        jax.debug.callback(keep, logits)
        return logits, caches

    def decode_kept(self, *a, **k):
        logits, caches = decode(self, *a, **k)
        jax.debug.callback(keep, logits)
        return logits, caches

    monkeypatch.setattr(Model, "prefill", prefill_kept)
    monkeypatch.setattr(Model, "decode_step", decode_kept)
    model = build_model(run.program_config(spec))
    eng = Engine(model, weights.to_program(flat),
                 ServeConfig(slots=2, cache_len=64, page_size=16, paged=True,
                             max_new_tokens=max_new, seed=0))
    req = Request(rid=0, tokens=list(prompt), max_new=max_new)
    eng.submit(req)
    while not req.done:
        eng.step()
    jax.effects_barrier()
    v = spec["model"]["vocab_size"]
    # prefill logits are (1, V); a decode step's are (slots, V), the
    # request in slot 0; a step's logits make the token after it
    rows = [seen[0][0]] + [s[0] for s in seen[1:len(req.out)]]
    return req.out, np.stack(rows)[:, :v]


def _reference_logits(flat, m, prompt, out):
    seq = list(prompt) + list(out)[:-1]
    x = reference.hidden(flat, m, seq)
    lg = np.concatenate([np.asarray(b) for _, b in
                         reference.logits_blocks(flat, m, x)])
    return lg[len(prompt) - 1:len(seq)]


@pytest.mark.parametrize("plen", [5, 23])
def test_group6_prefill_and_paged_decode_match_the_reference(monkeypatch,
                                                             plen):
    spec = files.config("tiny-internlm2", DATA)
    m = spec["model"]
    assert m["num_heads"] // m["num_kv_heads"] == 6
    assert m["vocab_size"] % weights.VOCAB_PAD != 0
    flat = weights.make(m, 11, jnp.bfloat16)
    prompt = np.random.default_rng(plen).integers(
        0, m["vocab_size"], plen).tolist()
    out, got = _served_logits(spec, flat, prompt, 10, monkeypatch)
    assert len(out) == 10 and max(out) < m["vocab_size"]
    want = _reference_logits(flat, m, prompt, out)
    assert got.shape == want.shape
    assert np.max(np.abs(got - want)) < TOL
    # the stated tolerance separates: the reference at the repo's default
    # RoPE base, 10,000, lies outside it
    wrong = _reference_logits(flat, {**m, "rope_theta": 10000.0}, prompt,
                              out)
    assert np.max(np.abs(wrong - want)) > TOL
