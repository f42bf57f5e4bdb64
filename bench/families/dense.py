"""Dense (llama-style) decoder family: how the benchmark builds its
weights, what one token costs, and the plain float32 reference.

Nothing here imports the program. The builder returns a plain tree of
device arrays; ``harness.model`` wraps its packed leaves into the
program's ``QTensor`` type without copying them. The reference reads the
same arrays and decodes the packed formats with its own code
(``harness.formats``), so it shares no code with ``models/transformer.py``
or ``core/quantize.py``.
"""
from __future__ import annotations

import fnmatch
import math
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from harness import formats as F

# config.json key -> ModelConfig field, for the keys the program reads
MODEL_KEYS = {
    "num_hidden_layers": "n_layers",
    "hidden_size": "d_model",
    "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads",
    "intermediate_size": "d_ff",
    "vocab_size": "vocab_size",
    "rope_theta": "rope_theta",
    "rms_norm_eps": "norm_eps",
    "tie_word_embeddings": "tie_embeddings",
    "qk_norm": "qk_norm",
}


def dims(conf: dict) -> dict:
    d, H = conf["hidden_size"], conf["num_attention_heads"]
    return dict(L=conf["num_hidden_layers"], d=d, H=H,
                KH=conf["num_key_value_heads"],
                Dh=conf.get("head_dim") or d // H,
                f=conf["intermediate_size"], V=conf["vocab_size"],
                theta=float(conf["rope_theta"]),
                eps=float(conf["rms_norm_eps"]),
                qk_norm=bool(conf.get("qk_norm")),
                window=conf.get("sliding_window"))


def model_overrides(conf: dict) -> dict:
    """ModelConfig fields this file fixes (the program's registry entry
    supplies the rest, and ``check_model`` compares them)."""
    out = {field: conf[key] for key, field in MODEL_KEYS.items()
           if key in conf}
    out["norm_eps"] = float(out["norm_eps"])
    out["rope_theta"] = float(out["rope_theta"])
    out["d_head"] = dims(conf)["Dh"]
    out["kernel_impl"] = conf["datapath"]
    return out


def check_model(conf: dict, mc) -> None:
    """The program's ModelConfig must be the configuration this file
    states: every mapped key, and the head width."""
    want = model_overrides(conf)
    bad = {k: (getattr(mc, k), v) for k, v in want.items()
           if getattr(mc, k) != v}
    if bad:
        raise ValueError(f"ModelConfig differs from the config file: {bad}")
    if mc.family != "dense" or mc.act != "swiglu" or mc.fused_qkv:
        raise ValueError(f"{mc.name} is not a llama-style dense model")


# ---------------------------------------------------------------------------
# shapes and the weight policy
# ---------------------------------------------------------------------------

def leaf_shapes(conf: dict) -> Dict[str, Tuple[int, ...]]:
    """Every weight of the program's dense parameter tree, by path."""
    m = dims(conf)
    L, d, H, KH, Dh, f, V = (m[k] for k in ("L", "d", "H", "KH", "Dh",
                                            "f", "V"))
    out = {
        "wte": (V, d),
        "layers/ln1/w": (L, d), "layers/ln2/w": (L, d),
        "layers/attn/wq": (L, d, H * Dh), "layers/attn/wk": (L, d, KH * Dh),
        "layers/attn/wv": (L, d, KH * Dh), "layers/attn/wo": (L, H * Dh, d),
        "layers/mlp/w_gate": (L, d, f), "layers/mlp/w_up": (L, d, f),
        "layers/mlp/w_down": (L, f, d),
        "ln_f/w": (d,), "lm_head": (d, V),
    }
    if m["qk_norm"]:
        out["layers/attn/q_norm"] = (L, Dh)
        out["layers/attn/k_norm"] = (L, Dh)
    return out


def variant_for(conf: dict, path: str) -> str:
    """First matching [glob, variant] rule of the file's policy."""
    for pat, variant in conf["policy"]:
        if fnmatch.fnmatch(path, pat):
            return variant
    raise ValueError(f"no policy rule matches {path!r}")


def projections(conf: dict) -> List[Tuple[str, int, int, str, int]]:
    """(path, K, N, variant, count) of every packed matmul; count is the
    number of stacked layers."""
    out = []
    for path, shp in leaf_shapes(conf).items():
        v = variant_for(conf, path)
        if v == "none":
            continue
        K, N = shp[-2], shp[-1]
        out.append((path, K, N, v, int(np.prod(shp[:-2])) or 1))
    return out


# ---------------------------------------------------------------------------
# builder: seeded packed payloads, on the device, one jitted call
# ---------------------------------------------------------------------------

def _build(conf: dict, key):
    tree: Dict = {}
    shapes = leaf_shapes(conf)
    keys = jax.random.split(key, len(shapes))
    for k, (path, shp) in zip(keys, sorted(shapes.items())):
        v = variant_for(conf, path)
        if v != "none":
            leaf = F.random_payload(v, k, shp[:-2], shp[-2], shp[-1],
                                    std=1.0 / math.sqrt(shp[-2]))
        elif path == "wte":
            leaf = jax.random.normal(k, shp, jnp.float32).astype(jnp.bfloat16)
        else:                                   # norm gains
            leaf = 1.0 + 0.1 * jax.random.normal(k, shp, jnp.float32)
        node = tree
        parts = path.split("/")
        for p in parts[:-1]:
            node = node.setdefault(p, {})
        node[parts[-1]] = leaf
    return tree


def build_weights(conf: dict, key):
    """The whole weight tree from ``key``, made on the default device in
    one jitted program, in the types it is served in."""
    return jax.jit(lambda k: _build(conf, k))(key)


# ---------------------------------------------------------------------------
# the plain reference: float32 at HIGHEST precision, one sequence at a time
# ---------------------------------------------------------------------------

def _exact(x, axis):
    return x


def _rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * w


def _rope(x, pos, theta):
    """Split-half rotation (the published Qwen3 and Phi-3 convention).
    x: (S, heads, D)."""
    D = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D))
    ang = pos[:, None].astype(jnp.float32) * inv               # (S, D/2)
    c, s = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :D // 2], x[..., D // 2:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def _mm(a, b, rnd):
    """(S, K) @ (K, N) with each operand rounded along K by ``rnd``."""
    return jnp.dot(rnd(a, -1), rnd(b, 0))


def _layer(conf, m, h, lw, pos, rnd):
    S = h.shape[0]
    H, KH, Dh = m["H"], m["KH"], m["Dh"]
    x = _rmsnorm(h, lw["ln1"]["w"], m["eps"])
    at = lw["attn"]
    q = _mm(x, F.decode(at["wq"]), rnd).reshape(S, H, Dh)
    k = _mm(x, F.decode(at["wk"]), rnd).reshape(S, KH, Dh)
    v = _mm(x, F.decode(at["wv"]), rnd).reshape(S, KH, Dh)
    if m["qk_norm"]:
        q = _rmsnorm(q, at["q_norm"], m["eps"])
        k = _rmsnorm(k, at["k_norm"], m["eps"])
    q, k = _rope(q, pos, m["theta"]), _rope(k, pos, m["theta"])
    G = H // KH
    qg = q.reshape(S, KH, G, Dh)
    s = jnp.einsum("sjgd,tjd->jgst", rnd(qg, -1), rnd(k, -1))
    s = s / math.sqrt(Dh)
    keep = pos[None, :] <= pos[:, None]                         # causal
    if m["window"]:
        keep &= pos[None, :] > pos[:, None] - m["window"]
    p = jax.nn.softmax(jnp.where(keep, s, -jnp.inf), axis=-1)
    o = jnp.einsum("jgst,tjd->sjgd", rnd(p, -1), rnd(v, 0))
    h = h + _mm(o.reshape(S, H * Dh), F.decode(at["wo"]), rnd)
    x = _rmsnorm(h, lw["ln2"]["w"], m["eps"])
    ml = lw["mlp"]
    g = _mm(x, F.decode(ml["w_gate"]), rnd)
    u = _mm(x, F.decode(ml["w_up"]), rnd)
    return h + _mm(jax.nn.silu(g) * u, F.decode(ml["w_down"]), rnd)


def reference_logits(conf: dict, weights, tokens, out_start, n_out: int,
                     rnd=_exact):
    """Logits (n_out, V) at positions out_start .. out_start + n_out - 1
    of the sequence ``tokens`` (S,), computed with full causal attention
    in float32 at HIGHEST precision. Layers run one at a time under a
    scan, each decoding its own packed weights, so one layer's float32
    weights are live at a time. ``rnd(x, axis)`` rounds a matmul operand
    along its contraction axis: exact for the reference, lower precision
    for the control."""
    m = dims(conf)
    with jax.default_matmul_precision("highest"):
        S = tokens.shape[0]
        pos = jnp.arange(S, dtype=jnp.int32)
        h = weights["wte"][tokens].astype(jnp.float32)

        def body(hh, lw):
            return _layer(conf, m, hh, lw, pos, rnd), None

        h, _ = jax.lax.scan(body, h, weights["layers"])
        h = _rmsnorm(h, weights["ln_f"]["w"], m["eps"])
        hs = jax.lax.dynamic_slice_in_dim(h, out_start, n_out, 0)
        return _mm(hs, F.decode(weights["lm_head"]), rnd)
