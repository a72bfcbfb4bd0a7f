"""Weights made by the benchmark from the seed, on the device, in one
jitted call, in the type they are served in.

The layout is the one the program's paged ``Engine`` takes for a dense
GQA decoder (layers stacked on a leading axis, norm scales stored as an
offset from 1); ``check_layout`` refuses to go on if the program expects
another.  The reference reads the same arrays by these names, so it
takes nothing that the program made.
"""
from __future__ import annotations

import math
from typing import Any, Dict

import numpy as np

NORM_STD = 0.1          # norm scales: 1 + N(0, 0.1^2)
VOCAB_PAD = 256         # the program pads its vocabulary to this multiple


def padded_vocab(v: int) -> int:
    return -(-v // VOCAB_PAD) * VOCAB_PAD


def layout(m: Dict[str, Any]) -> Dict[str, Any]:
    """{path: (shape, fan_in or None for a norm)} of every weight."""
    d, h, hkv, hd, ff, n = (m["d_model"], m["num_heads"], m["num_kv_heads"],
                            m["head_dim"], m["d_ff"], m["num_layers"])
    vp = padded_vocab(m["vocab_size"])
    return {
        "embed/table": ((vp, d), d),
        "unembed/table": ((d, vp), d),
        "final_norm": ((d,), None),
        "layers/ln1": ((n, d), None),
        "layers/ln2": ((n, d), None),
        "layers/attn/wq": ((n, d, h, hd), d),
        "layers/attn/wk": ((n, d, hkv, hd), d),
        "layers/attn/wv": ((n, d, hkv, hd), d),
        "layers/attn/wo": ((n, h, hd, d), h * hd),
        "layers/mlp/w_gate": ((n, d, ff), d),
        "layers/mlp/w_up": ((n, d, ff), d),
        "layers/mlp/w_down": ((n, ff, d), ff),
    }


def to_program(flat: Dict[str, Any]) -> Dict[str, Any]:
    """The program's parameter tree over the same arrays (no copies)."""
    layer = {"ln1": flat["layers/ln1"], "ln2": flat["layers/ln2"],
             "attn": {k: flat[f"layers/attn/{k}"]
                      for k in ("wq", "wk", "wv", "wo")},
             "mlp": {k: flat[f"layers/mlp/{k}"]
                     for k in ("w_gate", "w_up", "w_down")}}
    return {"embed": {"table": flat["embed/table"]},
            "unembed": {"table": flat["unembed/table"]},
            "final_norm": flat["final_norm"],
            "segments": [(layer,)]}


def check_layout(m: Dict[str, Any], program_abstract) -> None:
    """Refuse a program whose parameter tree differs from ``layout`` in
    its names or shapes."""
    import jax
    mine = to_program({k: jax.ShapeDtypeStruct(s, "float32")
                       for k, (s, _) in layout(m).items()})
    a = jax.tree_util.tree_structure(program_abstract)
    b = jax.tree_util.tree_structure(mine)
    shapes_a = [x.shape for x in jax.tree.leaves(program_abstract)]
    shapes_b = [x.shape for x in jax.tree.leaves(mine)]
    if a != b or shapes_a != shapes_b:
        raise RuntimeError(
            "the program's serving parameters are not laid out as this "
            f"benchmark makes them:\nprogram {a}\n{shapes_a}\n"
            f"benchmark {b}\n{shapes_b}")


def weight_key(seed: int) -> int:
    """A 32-bit key for any whole-number seed."""
    return int(np.random.default_rng([abs(int(seed)), int(seed < 0), 2])
               .integers(0, 2 ** 31 - 1))


def make(m: Dict[str, Any], seed: int, dtype) -> Dict[str, Any]:
    """Every weight, N(0, 1/fan_in) for matrices and 1 + N(0, 0.01)
    stored as the offset from 1 for norm scales, in ``dtype``."""
    import jax
    import jax.numpy as jnp
    spec = layout(m)

    def build(key):
        out = {}
        for i, (path, (shape, fan_in)) in enumerate(sorted(spec.items())):
            z = jax.random.normal(jax.random.fold_in(key, i), shape,
                                  jnp.float32)
            std = NORM_STD if fan_in is None else 1.0 / math.sqrt(fan_in)
            out[path] = (z * std).astype(dtype)
        return out

    return jax.jit(build)(jax.random.PRNGKey(weight_key(seed)))


def layer_slice(flat: Dict[str, Any], i: int) -> Dict[str, Any]:
    return {k[len("layers/"):]: v[i] for k, v in flat.items()
            if k.startswith("layers/")}
