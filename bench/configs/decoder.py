"""Plain reference of a pre-norm decoder-only transformer (Qwen3 and
ChatGLM3 blocks), in straightforward ``jax.numpy`` at float32 and
``highest`` matmul precision, with no kernels, cache or batching.

It imports nothing of the program under test. It makes its own weights
from the seed, in the published layout, and reads its sizes from a
configuration file of ``bench/configs`` through that file's
``reference.sizes`` map (published key for each size).

Per layer, following the published modelling code:

    h = x + W_o · attn(rope(norm_q(x̂ W_q + b_q)), rope(norm_k(x̂ W_k + b_k)), x̂ W_v + b_v)
    y = h + W_down · (silu(ĥ W_gate) * ĥ W_up)

with x̂ = RMSNorm(x), causal softmax attention scaled by 1/sqrt(head_dim),
grouped-query heads (query head i reads key/value head i // (H / KV)),
and rotary embedding on the leading ``rotary_dim`` dims of each head:
``"half"`` pairs dim i with i + rotary_dim/2 (Qwen3, as in Hugging Face
transformers); ``"interleaved"`` pairs 2i with 2i+1 (ChatGLM3's
``apply_rotary_pos_emb``). The q/k norms exist when ``qk_norm`` is set
(Qwen3), the q/k/v biases when ``qkv_bias`` is set (ChatGLM3). The
ChatGLM3 ``dense_h_to_4h`` matrix is stored here as its two halves,
``w_gate`` (first) and ``w_up`` (second), which is the same product.

Weights the configuration's serving format stores as byte planes are
truncated to their leading bytes, as that format defines
(``weight_planes``), before use.

The counts the metric readers use (:func:`matmul_params`,
:func:`plane_elements`, :func:`attention_layers`) are this
architecture's, so they live here beside its forward.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

HIGHEST = lax.Precision.HIGHEST
LAYER_KEYS = ("ln1", "wq", "wk", "wv", "bq", "bk", "bv", "q_norm", "k_norm",
              "wo", "ln2", "w_gate", "w_up", "w_down")


def sizes(conf: dict) -> dict:
    """Sizes by the reference's names, read from the published keys."""
    ref = conf["reference"]
    s = {name: conf[key] for name, key in ref["sizes"].items()}
    s.update({k: ref[k] for k in ("rope_pairs", "rotary_dim", "qk_norm",
                                  "qkv_bias")})
    return s


def weight_key(seed: int) -> np.ndarray:
    """The weights' PRNG key data (uint32[2]) of a seed."""
    words = np.random.SeedSequence([seed % 2**64, 7]).generate_state(2)
    return words.astype(np.uint32)


def weight_shapes(s: dict) -> dict:
    L, d, ff, V = s["layers"], s["d_model"], s["d_ff"], s["vocab"]
    H, K, hd = s["heads"], s["kv_heads"], s["head_dim"]
    shapes = {
        "embed": (V, d), "final_norm": (d,), "head": (d, V),
        "ln1": (L, d), "wq": (L, d, H * hd), "wk": (L, d, K * hd),
        "wv": (L, d, K * hd), "wo": (L, H * hd, d), "ln2": (L, d),
        "w_gate": (L, d, ff), "w_up": (L, d, ff), "w_down": (L, ff, d),
    }
    if s["qkv_bias"]:
        shapes.update(bq=(L, H * hd), bk=(L, K * hd), bv=(L, K * hd))
    if s["qk_norm"]:
        shapes.update(q_norm=(L, hd), k_norm=(L, hd))
    return shapes


def layer_matrices(s: dict) -> dict:
    """Elements of each per-layer weight matrix."""
    d, ff = s["d_model"], s["d_ff"]
    H, K, hd = s["heads"], s["kv_heads"], s["head_dim"]
    return {"wq": d * H * hd, "wk": d * K * hd, "wv": d * K * hd,
            "wo": H * hd * d, "w_gate": d * ff, "w_up": d * ff,
            "w_down": ff * d}


def matmul_params(s: dict) -> int:
    """Parameters a token multiplies through: every layer's matrices
    and the output head; the embedding is a lookup and does not count."""
    return s["layers"] * sum(layer_matrices(s).values()) + s["d_model"] * s["vocab"]


def plane_elements(s: dict, leaves, min_elements: int,
                   matrices=layer_matrices) -> int:
    """Elements of the leaves the serving format keeps as byte planes
    (each is unpacked once per program call): the listed leaves with at
    least ``min_elements`` elements per layer, as :func:`as_served`
    picks them. ``matrices(s)`` gives the per-layer leaves' elements; a
    reference of another channel mixer passes its own."""
    per = matrices(s)
    n = sum(s["layers"] * per[k] for k in leaves
            if k in per and per[k] >= min_elements)
    table = s["d_model"] * s["vocab"]
    n += sum(table for k in ("embed", "head")
             if k in leaves and table >= min_elements)
    return n


def attention_layers(s: dict) -> int:
    """Layers that run the paged and flash attention kernels: all."""
    return s["layers"]


def _init(name: str, key, shape):
    """Random weights whose activations stay of order one: matrices
    N(0, 1/fan_in), the embedding N(0, 1), norm scales 1 + N(0, 0.1^2),
    biases N(0, 0.5^2) (large enough that a dropped bias shows)."""
    z = jax.random.normal(key, shape, jnp.float32)
    if name == "embed":
        return z
    if name in ("final_norm", "ln1", "ln2", "q_norm", "k_norm"):
        return 1.0 + 0.1 * z
    if name in ("bq", "bk", "bv"):
        return 0.5 * z
    return z * shape[-2] ** -0.5


def make_weights(conf: dict, key_data) -> dict:
    """Weights in the published layout, fp32; traceable (call it inside
    ``jax.jit`` with ``key_data`` an argument)."""
    shapes = weight_shapes(sizes(conf))
    key = jax.random.wrap_key_data(jnp.asarray(key_data, jnp.uint32))
    keys = jax.random.split(key, len(shapes))
    return {name: _init(name, k, shape)
            for k, (name, shape) in zip(keys, sorted(shapes.items()))}


def truncate_planes(x, keep_bytes: int):
    """fp32 -> the value its leading ``keep_bytes`` byte planes hold
    (the trailing bytes zero), the serving format's truncate rounding."""
    if keep_bytes >= 4:
        return x
    mask = np.uint32((0xFFFFFFFF << (8 * (4 - keep_bytes))) & 0xFFFFFFFF)
    u = lax.bitcast_convert_type(x, jnp.uint32) & mask
    return lax.bitcast_convert_type(u, jnp.float32)


def as_served(w: dict, planes: dict, keep_bytes: int, rounding: str,
              layer_keys=LAYER_KEYS) -> dict:
    """The weights as the serving format delivers them to the compute.
    ``planes`` (a configuration's ``weight_planes``) names the leaves the
    format stores as byte planes, and the fewest elements per layer a
    leaf must have to be stored so; ``keep_bytes`` planes are kept.
    ``layer_keys`` are the leaves stacked by layer."""
    if rounding != "truncate":
        raise ValueError(f"unknown plane rounding {rounding!r}")
    stacked = set(layer_keys)

    def planed(k, v):
        n = int(np.prod(v.shape[1:] if k in stacked else v.shape))
        return k in planes["leaves"] and n >= planes["min_elements"]

    return {k: truncate_planes(v, keep_bytes) if planed(k, v) else v
            for k, v in w.items()}


def _mm(a, b):
    return jnp.matmul(a, b, precision=HIGHEST)


def _rms(x, scale, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _rope(x, s: dict, theta: float):
    """x (T, heads, hd) at positions 0..T-1."""
    T = x.shape[0]
    r = s["rotary_dim"]
    inv = 1.0 / theta ** (np.arange(0, r, 2, dtype=np.float64) / r)
    ang = np.arange(T, dtype=np.float64)[:, None] * inv[None, :]
    cos = jnp.asarray(np.cos(ang), jnp.float32)[:, None, :]
    sin = jnp.asarray(np.sin(ang), jnp.float32)[:, None, :]
    xr, xp = x[..., :r], x[..., r:]
    if s["rope_pairs"] == "half":
        a, b = xr[..., : r // 2], xr[..., r // 2:]
        y = jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)
    elif s["rope_pairs"] == "interleaved":
        a, b = xr[..., 0::2], xr[..., 1::2]
        y = jnp.stack([a * cos - b * sin, b * cos + a * sin], -1).reshape(xr.shape)
    else:
        raise ValueError(f"unknown rope pairing {s['rope_pairs']!r}")
    return jnp.concatenate([y, xp], axis=-1)


def _attention(q, k, v, block: int):
    """Causal GQA attention, query rows in blocks. q (T,H,hd), k/v
    (T,KV,hd) -> (T,H,hd)."""
    T, H, hd = q.shape
    KV = k.shape[1]
    G = H // KV
    qb = q.reshape(T // block, block, KV, G, hd)
    kpos = jnp.arange(T)

    def one(args):
        i, qi = args
        s = jnp.einsum("qkgd,tkd->kgqt", qi, k, precision=HIGHEST)
        s = s * hd ** -0.5
        qpos = i * block + jnp.arange(block)
        s = jnp.where(kpos[None, :] <= qpos[:, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("kgqt,tkd->qkgd", p, v, precision=HIGHEST)

    out = lax.map(one, (jnp.arange(T // block), qb))
    return out.reshape(T, H, hd)


class Reference:
    """The reference forward of one configuration. ``block`` is the
    query-row block of attention; ``chunk`` the served positions whose
    logits are formed at once. A reference of another channel mixer
    overrides ``layer_keys`` and :meth:`mix`."""

    layer_keys = LAYER_KEYS

    def __init__(self, conf: dict, *, block: int = 512, chunk: int = 256):
        self.conf = conf
        self.s = sizes(conf)
        self.block = block
        self.chunk = chunk
        self._hidden = jax.jit(self._hidden_fn)
        self._gaps = jax.jit(_gaps_fn)

    def _hidden_fn(self, w, tokens):
        s = self.s
        eps, theta = s["norm_eps"], s["rope_theta"]
        T = tokens.shape[0]
        H, K, hd = s["heads"], s["kv_heads"], s["head_dim"]
        x = w["embed"][tokens]
        layers = {k: w[k] for k in self.layer_keys if k in w}

        def layer(x, lw):
            h = _rms(x, lw["ln1"], eps)
            q, k, v = _mm(h, lw["wq"]), _mm(h, lw["wk"]), _mm(h, lw["wv"])
            if s["qkv_bias"]:
                q, k, v = q + lw["bq"], k + lw["bk"], v + lw["bv"]
            q = q.reshape(T, H, hd)
            k, v = k.reshape(T, K, hd), v.reshape(T, K, hd)
            if s["qk_norm"]:
                q, k = _rms(q, lw["q_norm"], eps), _rms(k, lw["k_norm"], eps)
            q, k = _rope(q, s, theta), _rope(k, s, theta)
            a = _attention(q, k, v, self.block).reshape(T, H * hd)
            x = x + _mm(a, lw["wo"])
            return x + self.mix(lw, _rms(x, lw["ln2"], eps)), None

        x, _ = lax.scan(layer, x, layers)
        return _rms(x, w["final_norm"], eps)

    def mix(self, lw: dict, h):
        """One layer's channel mixer on its normed input h (T, d)."""
        m = jax.nn.silu(_mm(h, lw["w_gate"])) * _mm(h, lw["w_up"])
        return _mm(m, lw["w_down"])

    def served_gaps(self, w: dict, prompt, served, *, pad_to: int):
        """(gaps, margins) of every served token. The reference reads
        prompt + served tokens once (teacher forcing); where each served
        token was produced, its gap is the best logit minus the served
        token's logit, and the margin the best logit minus the second
        best. ``w`` are the weights as served (:func:`as_served`);
        ``pad_to`` a fixed length (a multiple of ``block``) so one
        program serves every request of a run."""
        seq = list(prompt) + list(served[:-1])
        if len(seq) > pad_to or pad_to % self.block:
            raise ValueError(f"sequence of {len(seq)} and pad_to={pad_to} "
                             f"(block {self.block})")
        tokens = np.zeros((pad_to,), np.int32)
        tokens[: len(seq)] = seq
        hidden = self._hidden(w, jnp.asarray(tokens))
        n, c = len(served), self.chunk
        pos = len(prompt) - 1 + np.arange(n)
        gaps, margins = [], []
        for lo in range(0, n, c):
            p = np.zeros((c,), np.int32)
            t = np.zeros((c,), np.int32)
            m = min(c, n - lo)
            p[:m], t[:m] = pos[lo:lo + m], served[lo:lo + m]
            g, mg = self._gaps(hidden, w["head"], jnp.asarray(p), jnp.asarray(t))
            gaps.append(np.asarray(g)[:m])
            margins.append(np.asarray(mg)[:m])
        return np.concatenate(gaps), np.concatenate(margins)


def _gaps_fn(hidden, head, positions, targets):
    """For each (position, target): how far the target's logit lies
    below the best logit there, and how far the second best does."""
    logits = _mm(hidden[positions], head)
    top2 = lax.top_k(logits, 2)[0]
    mine = jnp.take_along_axis(logits, targets[:, None], axis=-1)[:, 0]
    return top2[:, 0] - mine, top2[:, 0] - top2[:, 1]
