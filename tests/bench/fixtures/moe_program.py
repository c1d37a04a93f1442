"""Checkpoint conversion of the sparse-expert decoder
(``moe_decoder.py``) into the program's tree: the dense decoder's
conversion, each layer's experts stacked as the program stacks them
(expert first), and the router added to its channel mixer."""
from __future__ import annotations

from bench.harness import registry

dense = registry.beside(__file__, "decoder_program")
program_config = dense.program_config


def to_program(conf: dict, s: dict, cfg, w: dict) -> dict:
    tree = dense.to_program(conf, s, cfg, w)
    lpg = cfg.layers_per_group
    for g, group in enumerate(tree["groups"]):
        group["p0"]["mix"]["router"] = w["router"][g * lpg:(g + 1) * lpg]
    return tree
