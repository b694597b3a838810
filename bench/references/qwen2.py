"""Plain float32 reference of the Qwen2 decoder family (Qwen2, Qwen1.5).

Follows the published description (Hugging Face ``Qwen2ForCausalLM``):
token embedding; per layer an RMSNorm, grouped-query attention with bias on
the q/k/v projections and none on the output projection, rotary position
embedding in the rotate-half form, causal softmax, a residual add, an
RMSNorm, a SwiGLU MLP (``down(silu(gate(x)) * up(x))``) and a residual
add; a final RMSNorm and the LM head (the transposed embedding where
``tie_word_embeddings``).  Everything is computed in float32 with every
matrix product at ``Precision.HIGHEST``, one layer at a time under a scan,
from weights upcast layer by layer, so the whole model never exists in
float32 at once.  It imports nothing of the serving program.

``make_weights`` draws the benchmark's random weights from a key, in the
tree layout the serving program takes (``params`` of ``repro.models``):
weights are the benchmark's own, and the same arrays feed the program and
this reference.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST

# the embedding's standard deviation (``initializer_range`` of the
# published configs); weight matrices are drawn at 1/sqrt(fan-in), so every
# layer's output is of the order of its input and the next token depends
# on the context at any width; biases and the norms' scales are drawn too,
# so a program that drops one of them is seen
EMBED_STD = 0.02
BIAS_STD = 0.1
NORM_STD = 0.1


def dims(hf: dict) -> dict:
    d = hf["hidden_size"]
    H = hf["num_attention_heads"]
    kvH = hf["num_key_value_heads"]
    hd = hf.get("head_dim", d // H)
    return dict(d=d, H=H, kvH=kvH, G=H // kvH, hd=hd,
                ff=hf["intermediate_size"], L=hf["num_hidden_layers"],
                V=hf["vocab_size"], tied=bool(hf["tie_word_embeddings"]),
                eps=float(hf["rms_norm_eps"]), theta=float(hf["rope_theta"]))


def weight_shapes(hf: dict) -> dict:
    """{path: shape} of every leaf, in the program's layout."""
    m = dims(hf)
    d, L, kvH, G, hd, ff, V = (m[k] for k in
                               ("d", "L", "kvH", "G", "hd", "ff", "V"))
    shapes = {
        ("embed", "tok_embed"): (V, d),
        ("mixer_norm", "scale"): (L, d),
        ("mixer", "wq"): (L, d, kvH, G, hd),
        ("mixer", "wk"): (L, d, kvH, hd),
        ("mixer", "wv"): (L, d, kvH, hd),
        ("mixer", "wo"): (L, kvH, G, hd, d),
        ("mixer", "bq"): (L, kvH, G, hd),
        ("mixer", "bk"): (L, kvH, hd),
        ("mixer", "bv"): (L, kvH, hd),
        ("ffn_norm", "scale"): (L, d),
        ("ffn", "w_gate"): (L, d, ff),
        ("ffn", "w_up"): (L, d, ff),
        ("ffn", "w_down"): (L, ff, d),
        ("final_norm", "scale"): (d,),
    }
    if not m["tied"]:
        shapes[("head", "out_head")] = (d, V)
    return shapes


def make_weights(key, hf: dict, dtype=jnp.bfloat16) -> dict:
    """Random weights from ``key`` in the program's tree layout, in
    ``dtype``.  Leaf ``i`` (in ``weight_shapes`` order) draws from
    ``fold_in(key, i)``.  Jit it, so the draw runs on the device."""
    m = dims(hf)
    fan_in = {"wo": m["H"] * m["hd"], "w_down": m["ff"]}
    leaves = {}
    for i, (path, shape) in enumerate(weight_shapes(hf).items()):
        z = jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)
        if path[-1] == "scale":
            v = 1.0 + NORM_STD * z
        elif path[-1].startswith("b"):
            v = BIAS_STD * z
        elif path[-1] == "tok_embed":
            v = EMBED_STD * z
        else:
            v = z * fan_in.get(path[-1], m["d"]) ** -0.5
        leaves[path] = v.astype(dtype)
    layer = {}
    for (a, b), v in leaves.items():
        if a not in ("embed", "final_norm", "head"):
            layer.setdefault(a, {})[b] = v
    w = {"embed": {"tok_embed": leaves[("embed", "tok_embed")]},
         "stages": [[layer]],
         "final_norm": {"scale": leaves[("final_norm", "scale")]}}
    if ("head", "out_head") in leaves:
        w["head"] = {"out_head": leaves[("head", "out_head")]}
    return w


def _exact(a, b):
    """Matrix product of the reference: float32 at HIGHEST precision."""
    return jnp.dot(a, b, precision=HIGHEST, preferred_element_type=jnp.float32)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, pos, theta):
    """Rotate-half RoPE. x: (S, heads, hd), pos: (S,)."""
    hd = x.shape[-1]
    inv = theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = pos[:, None].astype(jnp.float32) * inv  # (S, hd/2)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def forward_rows(w, hf: dict, tokens, rows, matmul=_exact):
    """Logits (len(rows), V) float32 at positions ``rows`` of the causal
    forward over ``tokens`` (S,).  ``matmul(a, b)`` computes every weight
    product; the default is exact float32 (a control passes a lower
    precision in its place).  Positions past the last real token are
    padding: causality keeps them out of every earlier row."""
    m = dims(hf)
    S = tokens.shape[0]
    f32 = jnp.float32
    pos = jnp.arange(S)
    x = jnp.take(w["embed"]["tok_embed"], tokens, axis=0).astype(f32)
    causal = pos[:, None] >= pos[None, :]
    lp = w["stages"][0][0]

    def layer(x, p):
        p = jax.tree.map(lambda a: a.astype(f32), p)
        a, f = p["mixer"], p["ffn"]
        h = _rms(x, p["mixer_norm"]["scale"], m["eps"])
        q = matmul(h, a["wq"].reshape(m["d"], -1)) + a["bq"].reshape(-1)
        k = matmul(h, a["wk"].reshape(m["d"], -1)) + a["bk"].reshape(-1)
        v = matmul(h, a["wv"].reshape(m["d"], -1)) + a["bv"].reshape(-1)
        q = _rope(q.reshape(S, m["H"], m["hd"]), pos, m["theta"])
        k = _rope(k.reshape(S, m["kvH"], m["hd"]), pos, m["theta"])
        v = v.reshape(S, m["kvH"], m["hd"])
        q = q.reshape(S, m["kvH"], m["G"], m["hd"])
        s = jnp.einsum("qkgd,tkd->kgqt", q, k, precision=HIGHEST)
        s = jnp.where(causal, s * m["hd"] ** -0.5, -jnp.inf)
        o = jnp.einsum("kgqt,tkd->qkgd", jax.nn.softmax(s, -1), v,
                       precision=HIGHEST)
        x = x + matmul(o.reshape(S, -1), a["wo"].reshape(-1, m["d"]))
        h = _rms(x, p["ffn_norm"]["scale"], m["eps"])
        g = jax.nn.silu(matmul(h, f["w_gate"])) * matmul(h, f["w_up"])
        return x + matmul(g, f["w_down"]), None

    x, _ = jax.lax.scan(layer, x, lp)
    x = _rms(jnp.take(x, rows, axis=0),
             w["final_norm"]["scale"].astype(f32), m["eps"])
    if m["tied"]:
        head = w["embed"]["tok_embed"].astype(f32).T
    else:
        head = w["head"]["out_head"].astype(f32)
    return matmul(x, head)


def fp8_matmul(a, b):
    """A float8 (e4m3) product: each operand scaled to float8's range (per
    row of ``a``, per column of ``b``), rounded to float8, and multiplied
    with a float32 accumulate.  The control of the comparison: the
    precision one step below the bfloat16 the configuration serves in."""
    f8 = jnp.float8_e4m3fn
    top = float(jnp.finfo(f8).max)

    def q(x, axis):
        s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / top
        s = jnp.where(s > 0, s, 1.0)
        return (x / s).astype(f8).astype(jnp.float32) * s

    return _exact(q(a, -1), q(b, 0))
