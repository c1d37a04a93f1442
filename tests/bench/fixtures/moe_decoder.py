"""Plain reference of a sparse-expert decoder (Mixtral's block): the
dense decoder of ``decoder.py`` with its SwiGLU channel mixer replaced
by ``experts`` SwiGLU experts, of which each token uses ``top_k``.

    p = softmax(ĥ W_router);  e_1..e_k = the k largest of p
    y = Σ_i (p_{e_i} / Σ_j p_{e_j}) · W_down[e_i] · (silu(ĥ W_gate[e_i]) * ĥ W_up[e_i])

in ``jax.numpy`` at ``highest``, every token through every expert it is
routed to, with no capacity and nothing dropped. Like ``decoder.py`` it
imports nothing of the program; it builds on that module, found beside
this file.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from bench.harness import registry

dec = registry.beside(__file__, "decoder")
sizes = dec.sizes
weight_key = dec.weight_key
attention_layers = dec.attention_layers
LAYER_KEYS = dec.LAYER_KEYS + ("router",)
EXPERT_KEYS = ("w_gate", "w_up", "w_down")


def layer_matrices(s: dict) -> dict:
    """Elements of each per-layer weight matrix, every expert counted."""
    m = dec.layer_matrices(s)
    m.update({k: s["experts"] * m[k] for k in EXPERT_KEYS},
             router=s["d_model"] * s["experts"])
    return m


def matmul_params(s: dict) -> int:
    """Parameters a token multiplies through: attention, the router and
    the ``top_k`` experts it is routed to, in every layer, and the head."""
    m = dec.layer_matrices(s)
    per_token = (sum(v for k, v in m.items() if k not in EXPERT_KEYS)
                 + s["top_k"] * sum(m[k] for k in EXPERT_KEYS)
                 + s["d_model"] * s["experts"])
    return s["layers"] * per_token + s["d_model"] * s["vocab"]


def plane_elements(s: dict, leaves, min_elements: int) -> int:
    """Elements of the leaves kept as byte planes, every expert's."""
    return dec.plane_elements(s, leaves, min_elements, layer_matrices)


def weight_shapes(s: dict) -> dict:
    L, d, ff, E = s["layers"], s["d_model"], s["d_ff"], s["experts"]
    shapes = dec.weight_shapes(s)
    shapes.update(w_gate=(L, E, d, ff), w_up=(L, E, d, ff),
                  w_down=(L, E, ff, d), router=(L, d, E))
    return shapes


def make_weights(conf: dict, key_data) -> dict:
    """Weights in the published layout, fp32; traceable."""
    shapes = weight_shapes(sizes(conf))
    key = jax.random.wrap_key_data(jnp.asarray(key_data, jnp.uint32))
    keys = jax.random.split(key, len(shapes))
    return {name: dec._init(name, k, shape)
            for k, (name, shape) in zip(keys, sorted(shapes.items()))}


def as_served(w: dict, planes: dict, keep_bytes: int, rounding: str) -> dict:
    return dec.as_served(w, planes, keep_bytes, rounding, LAYER_KEYS)


class Reference(dec.Reference):
    layer_keys = LAYER_KEYS

    def mix(self, lw: dict, h):
        T, k = h.shape[0], self.s["top_k"]
        p = jax.nn.softmax(dec._mm(h, lw["router"]), axis=-1)
        top_p, top_e = lax.top_k(p, k)
        top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
        weight = jnp.zeros_like(p).at[jnp.arange(T)[:, None], top_e].set(top_p)
        hi = dec.HIGHEST
        g = jnp.einsum("td,edf->tef", h, lw["w_gate"], precision=hi)
        u = jnp.einsum("td,edf->tef", h, lw["w_up"], precision=hi)
        y = jnp.einsum("tef,efd->ted", jax.nn.silu(g) * u, lw["w_down"],
                       precision=hi)
        return jnp.einsum("te,ted->td", weight, y, precision=hi)
