"""Checkpoint conversion of the dense decoder (``decoder.py``): its
published-layout weights into the program's parameter tree, and the
program's ``ModelConfig`` for a configuration file.

This is the one module of a configuration that knows the program's
layout, so it imports the program; the reference it converts from
imports nothing of it. A configuration file names it under
``program.convert``; the driver passes it the reference's sizes ``s``.
"""
from __future__ import annotations

import dataclasses

import numpy as np

# the program applies rotary embedding to interleaved pairs (2i, 2i+1)
PROGRAM_ROPE = "interleaved"


def program_config(conf: dict, s: dict):
    """The program's ModelConfig: the registry entry with every field
    the configuration file maps set from its published key."""
    from repro.configs.registry import get_config

    p = conf["program"]
    cfg = dataclasses.replace(
        get_config(p["arch"]), **{f: conf[k] for f, k in p["fields"].items()}
    )
    rot = int(cfg.head_dim * cfg.rotary_pct)
    if (cfg.qk_norm, cfg.qkv_bias, rot - rot % 2) != (
            s["qk_norm"], s["qkv_bias"], s["rotary_dim"]):
        raise ValueError(f"{conf['name']}: the program's qk_norm/qkv_bias/"
                         "rotary dims differ from the configuration file's")
    return cfg


def rope_permutation(s: dict) -> np.ndarray:
    """Program head dim j holds reference head dim perm[j]."""
    hd, r = s["head_dim"], s["rotary_dim"]
    perm = np.arange(hd)
    if s["rope_pairs"] == "half" and PROGRAM_ROPE == "interleaved":
        i = np.arange(r // 2)
        perm[0:r:2] = i
        perm[1:r:2] = i + r // 2
    elif s["rope_pairs"] != PROGRAM_ROPE:
        raise ValueError(f"no mapping from {s['rope_pairs']!r} rope pairs")
    return perm


def to_program(conf: dict, s: dict, cfg, w: dict) -> dict:
    """Published-layout weights -> the program's parameter tree (a
    checkpoint conversion: layers split into the program's precision
    groups, rotary head dims reordered to the program's pairing)."""
    perm = rope_permutation(s)
    hd = s["head_dim"]

    def heads(x):  # reorder each head's dims along the last axis
        sh = x.shape
        return x.reshape(*sh[:-1], sh[-1] // hd, hd)[..., perm].reshape(sh)

    lpg = cfg.layers_per_group
    groups = []
    for g in range(cfg.num_groups):
        sl = slice(g * lpg, (g + 1) * lpg)
        attn = {"wq": heads(w["wq"][sl]), "wk": heads(w["wk"][sl]),
                "wv": w["wv"][sl], "wo": w["wo"][sl], "ln": w["ln1"][sl]}
        if s["qkv_bias"]:
            attn.update(bq=heads(w["bq"][sl]), bk=heads(w["bk"][sl]),
                        bv=w["bv"][sl])
        if s["qk_norm"]:
            attn.update(q_norm=w["q_norm"][sl][..., perm],
                        k_norm=w["k_norm"][sl][..., perm])
        mix = {"ln": w["ln2"][sl], "w_gate": w["w_gate"][sl],
               "w_up": w["w_up"][sl], "w_down": w["w_down"][sl]}
        groups.append({"p0": {"attn": attn, "mix": mix}})
    return {"groups": groups, "embed": w["embed"], "head": w["head"],
            "final_norm": w["final_norm"]}
