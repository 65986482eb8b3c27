"""granite-3-2b (IBM Granite 3.0 2B base) as the repo's arch stack runs it,
and its plain reference.

Sizes come from a configuration file beside this one whose ``model`` is
``granite-3-2b`` (``granite-3-2b-train.json`` and
``granite-3-2b-serve.json`` cut the depth). ``forward`` is the full
forward to logits that both the training loss and the serving check
follow. The
reference is the Granite block: pre-norm RMSNorm, grouped-query attention
with half-split RoPE, a SwiGLU MLP, a head tied to the embedding, and the
muP multipliers on the embedding, attention, residual branches and logits,
at the values the file says run (``departures``: the program's Llama block
runs none of them). Like the arch stack, each norm scale is held as 1 + w.

Weights are made here, from the seed, in one jitted call, in the layout of
the reference; ``to_program`` rearranges them into the arch stack's tree.
The reference imports nothing of the program.
"""
from __future__ import annotations

import sys
from pathlib import Path

import jax
import jax.numpy as jnp

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))
from bench import reference as R  # noqa: E402

MULTIPLIERS = ("attention_multiplier", "embedding_multiplier",
               "residual_multiplier", "logits_scaling")


def dims(s):
    return (s["hidden_size"], s["num_attention_heads"],
            s["num_key_value_heads"], s["head_dim"], s["intermediate_size"],
            s["num_hidden_layers"], s["vocab_size"])


def multipliers(s):
    """The muP multipliers as run: the file's ``departures`` where it
    lists one, else its own value."""
    dep = s.get("departures", {})
    return {k: dep[k]["run"] if k in dep else s[k] for k in MULTIPLIERS}


# ------------------------------------------------------------------ program

def arch_config(s):
    """The program's ArchConfig for these sizes: the registered granite-3-2b
    with the widths, depth and norm eps of ``s``; what the program cannot
    set (tied head, activation, RoPE, the multipliers it has none of) is
    checked against the file."""
    import dataclasses

    from repro.configs import get_arch

    d, h, kv, hd, ff, n, v = dims(s)
    cfg = dataclasses.replace(get_arch("granite-3-2b"), d_model=d, n_heads=h,
                              n_kv_heads=kv, head_dim=hd, d_ff=ff,
                              n_layers=n, vocab_size=v,
                              norm_eps=s["rms_norm_eps"])
    m = multipliers(s)
    got = (cfg.tie_embeddings, cfg.rope_theta, cfg.ffn_act, cfg.ffn_gated,
           cfg.norm_type, cfg.layer_pattern, cfg.scale_embeddings,
           hd ** -0.5, 1.0, 1.0, 1.0)
    want = (s["tie_word_embeddings"], s["rope_theta"], s["hidden_act"], True,
            "rms", ("attn",), False, *(m[k] for k in MULTIPLIERS))
    if got != want:
        raise ValueError(f"program config {got} differs from the file {want}")
    return cfg


def to_program(s, w, padded_vocab: int):
    """Reference-layout weights -> the arch stack's parameter tree (stacked
    layers, vocabulary padded with zero rows)."""
    L = w["layers"]
    embed = jnp.pad(w["embed"], ((0, padded_vocab - w["embed"].shape[0]),
                                 (0, 0)))
    block = {
        "ln1": {"w": L["ln1"]},
        "mixer": {k: L[k] for k in ("wq", "wk", "wv", "wo")},
        "ln2": {"w": L["ln2"]},
        "ffn": {k: L[k] for k in ("w_gate", "w_up", "w_down")},
    }
    return {"embed": embed, "final_norm": {"w": w["final_norm"]},
            "layers": (block,), "tail": ()}


def program_norms(s, tree):
    """{reference leaf name: norm} of a tree in the arch stack's layout
    (params, or an optimizer moment shaped like them), over the real
    vocabulary rows."""
    b = tree["layers"][0]
    out = {"embed": tree["embed"][: s["vocab_size"]],
           "final_norm": tree["final_norm"]["w"],
           "layers.ln1": b["ln1"]["w"], "layers.ln2": b["ln2"]["w"]}
    for k in ("wq", "wk", "wv", "wo"):
        out[f"layers.{k}"] = b["mixer"][k]
    for k in ("w_gate", "w_up", "w_down"):
        out[f"layers.{k}"] = b["ffn"][k]
    return {k: jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32))))
            for k, v in out.items()}


def program_change_norms(s, new, old):
    """{reference leaf name: norm of new - old} for two arch-stack trees."""
    diff = jax.tree.map(lambda a, b: a.astype(jnp.float32)
                        - b.astype(jnp.float32), new, old)
    return program_norms(s, diff)


# ---------------------------------------------------------------- reference

def init(s, key):
    """Random weights in the reference layout: float32, the embedding with
    std 0.02, every matrix with std fan_in**-0.5; each norm's scale is
    1 + w with w from 0 (so that weight decay pulls the scale toward 1, as
    in the arch stack)."""
    d, h, kv, hd, ff, n, v = dims(s)
    ks = jax.random.split(key, 8)

    def mat(k, shape, fan_in):
        return jax.random.normal(k, shape, jnp.float32) * fan_in ** -0.5

    layers = {
        "ln1": jnp.zeros((n, d), jnp.float32),
        "ln2": jnp.zeros((n, d), jnp.float32),
        "wq": mat(ks[1], (n, d, h * hd), d),
        "wk": mat(ks[2], (n, d, kv * hd), d),
        "wv": mat(ks[3], (n, d, kv * hd), d),
        "wo": mat(ks[4], (n, h * hd, d), h * hd),
        "w_gate": mat(ks[5], (n, d, ff), d),
        "w_up": mat(ks[6], (n, d, ff), d),
        "w_down": mat(ks[7], (n, ff, d), ff),
    }
    return {"embed": jax.random.normal(ks[0], (v, d), jnp.float32) * 0.02,
            "final_norm": jnp.zeros((d,), jnp.float32), "layers": layers}


def _block(s, x, p):
    d, h, kv, hd, ff, _, _ = dims(s)
    eps, m = s["rms_norm_eps"], multipliers(s)
    b, t, _ = x.shape
    y = R.rms_norm(x, 1.0 + p["ln1"], eps)
    q = R.rope((y @ p["wq"]).reshape(b, t, h, hd), s["rope_theta"])
    k = R.rope((y @ p["wk"]).reshape(b, t, kv, hd), s["rope_theta"])
    v = (y @ p["wv"]).reshape(b, t, kv, hd)
    a = R.causal_attention(q.reshape(b, t, kv, h // kv, hd), k, v,
                           m["attention_multiplier"])
    x = x + m["residual_multiplier"] * (a.reshape(b, t, h * hd) @ p["wo"])
    y = R.rms_norm(x, 1.0 + p["ln2"], eps)
    mlp = (jax.nn.silu(y @ p["w_gate"]) * (y @ p["w_up"])) @ p["w_down"]
    return x + m["residual_multiplier"] * mlp


def forward(s, w, tokens, dtype=jnp.float32):
    """Logits (B, S, vocab) in float32 of the whole model; activations in
    ``dtype``, and weights too (cast one layer at a time, so that no second
    copy of float32 weights is held; the control's are bfloat16 already)."""
    m = multipliers(s)
    embed = w["embed"].astype(dtype)
    x = embed[tokens] * m["embedding_multiplier"]

    def body(x, p):
        p = jax.tree.map(lambda a: a.astype(dtype), p)
        return jax.checkpoint(lambda x, p: _block(s, x, p))(x, p), None

    x, _ = jax.lax.scan(body, x, w["layers"])
    x = R.rms_norm(x, 1.0 + w["final_norm"].astype(dtype), s["rms_norm_eps"])
    return (x @ embed.T).astype(jnp.float32) / m["logits_scaling"]


def loss(s, w, tokens, targets, dtype=jnp.float32, row_weight=None):
    return R.cross_entropy(forward(s, w, tokens, dtype), targets, row_weight)


def optimizer(s):
    o = s["optimizer"]
    sch = o["schedule"]
    return R.adamw(o, lambda t: R.warmup_cosine(t, sch["warmup"], sch["total"],
                                                sch["floor"]))


def reference_norms(w):
    """{leaf name: norm} in the reference layout (stacked layers form one
    leaf each, as in the program's tree)."""
    return R.named_norms(w)


# ------------------------------------------------------------ operations

def layer_matmul_params(s):
    d, h, kv, hd, ff, _, _ = dims(s)
    return d * h * hd * 2 + 2 * d * kv * hd + 3 * d * ff


def matmul_params(s):
    """Parameters that take part in a matrix product for each token: every
    layer's projections and the (tied) head."""
    d, *_, n, v = dims(s)
    return n * layer_matmul_params(s) + d * v


def train_flops_per_token(s, seq: int) -> float:
    """Forward and backward of one token at sequence length ``seq``: 6 per
    matmul parameter, and causal attention counted once (2*H*hd*S per layer
    forward for QK^T and PV together, times 3). No recomputation counted."""
    d, h, kv, hd, ff, n, v = dims(s)
    return 6.0 * matmul_params(s) + 6.0 * n * seq * h * hd


# ------------------------------------------------------------ serving

def serve_token_flops(s, ctx: int, head: bool) -> float:
    """Forward of one token that attends ``ctx`` positions, itself
    included: 2 per matmul parameter of every layer, 4*H*hd*ctx per layer
    for QK^T and PV, and the tied head's 2*d*V only where the token's
    logits are used."""
    d, h, kv, hd, ff, n, v = dims(s)
    return (2.0 * n * layer_matmul_params(s) + 4.0 * n * h * hd * ctx
            + (2.0 * d * v if head else 0.0))


def prefill_flops(s, length: int) -> float:
    """A causal prefill of ``length`` real tokens (padding not counted),
    with the head on the last position alone: the one whose logits pick
    the first served token."""
    d, h, kv, hd, ff, n, v = dims(s)
    return (2.0 * n * layer_matmul_params(s) * length
            + 4.0 * n * h * hd * length * (length + 1) / 2 + 2.0 * d * v)


def decode_tick_bytes(s, live_positions: float, itemsize: int = 4) -> float:
    """Bytes one tick of the slot decode must read: every layer's
    projections and norms, the final norm, the tied head over the real
    vocabulary, and the K and V of the live positions (the positions that
    the active slots attend, summed over them)."""
    d, h, kv, hd, ff, n, v = dims(s)
    weights = n * (layer_matmul_params(s) + 2 * d) + d + d * v
    return itemsize * (weights + live_positions * n * 2 * kv * hd)
