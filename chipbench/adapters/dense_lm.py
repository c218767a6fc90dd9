"""The program's parameter tree for the ``dense_lm`` reference's weights.

``to_program`` places the reference's flat weights where
``repro.models.init_params`` keeps them (one scanned period of one
attention layer, layer axis first); ``from_program`` reads them back, for
any tree of that shape (parameters, gradients, Adam moments).
"""

from __future__ import annotations

_ATTN = {"wq": "q", "wk": "k", "wv": "v", "wo": "o"}
_FFN = {"w_up": "up", "w_gate": "gate", "w_down": "down"}


def to_program(w: dict, c: dict) -> dict:
    def norm(name):
        return {"g": w[name]} if name in w else {}

    layer = {"norm1": norm("ln1"), "norm2": norm("ln2"),
             "attn": {p: {"w": w[k]} for k, p in _ATTN.items()},
             "ffn": {p: {"w": w[k]} for k, p in _FFN.items()}}
    tree = {"embed": {"table": w["embed"]},
            "stack": {"prefix": [], "periods": {"sub0": layer}},
            "final_norm": norm("ln_f")}
    if "head" in w:
        tree["head"] = {"w": w["head"]}
    return tree


def from_program(tree: dict) -> dict:
    layer = tree["stack"]["periods"]["sub0"]
    w = {"embed": tree["embed"]["table"]}
    w.update({k: layer["attn"][p]["w"] for k, p in _ATTN.items()})
    w.update({k: layer["ffn"][p]["w"] for k, p in _FFN.items()})
    for name, node in (("ln1", layer["norm1"]), ("ln2", layer["norm2"]),
                       ("ln_f", tree["final_norm"])):
        if "g" in node:
            w[name] = node["g"]
    if "head" in tree:
        w["head"] = tree["head"]["w"]
    return w
