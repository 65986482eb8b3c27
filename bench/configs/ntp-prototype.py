"""The NTP prototype block (``repro.core.ntp_train``) at the widths of a
configuration file beside this one whose ``model`` is ``ntp-prototype``,
and its plain reference.

The block, as the file's ``departures`` say it runs: pre-norm RMSNorm (a
scale w, eps 1e-6), grouped-query causal attention with no position
encoding and scores scaled by head_dim**-0.5, an MLP B(gelu_tanh(A x)) that
is not gated, a head that is not tied to the embedding, and a mean
next-token cross-entropy over the rows that count. Weights are held
unit-major, the prototype's canonical layout: attention by KV group
(``wq`` (KV, d, G*hd), ``wk``/``wv`` (KV, d, hd), ``wo`` (KV, G*hd, d)),
the MLP by units of ``mlp_unit_rows`` rows (``A`` (U, d, rows), ``B``
(U, rows, d)).

Weights are made here, from the seed, in one jitted call; the program is
handed them as canonical parameters. The reference imports nothing of the
program.
"""
from __future__ import annotations

import sys
from pathlib import Path

import jax
import jax.numpy as jnp

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))
from bench import reference as R  # noqa: E402

# the block the reference implements: each departure's value under run
RUNS = {"hidden_act": "gelu_tanh, not gated (B(gelu_tanh(A x)))",
        "tie_word_embeddings": False, "rope_theta": None,
        "rms_norm_eps": 1e-6, "embedding_multiplier": 1.0,
        "residual_multiplier": 1.0, "logits_scaling": 1.0}


def dims(s):
    return (s["hidden_size"], s["num_attention_heads"],
            s["num_key_value_heads"], s["head_dim"], s["intermediate_size"],
            s["num_hidden_layers"], s["vocab_size"], s["mlp_unit_rows"])


def _runs(s):
    """The file's departures must be the block this reference implements."""
    dep = s["departures"]
    want = dict(RUNS, attention_multiplier=s["head_dim"] ** -0.5)
    got = {k: dep[k]["run"] for k in want}
    if got != want:
        raise ValueError(f"departures {got} are not the prototype block "
                         f"{want}")
    return got


# ------------------------------------------------------------------ program

def model_config(s):
    """The program's NTPModelConfig at these sizes (the widths
    ``chip_smoke.ntp_config`` gives it)."""
    from repro.runtime import NTPModelConfig

    _runs(s)
    d, h, kv, hd, ff, n, v, rows = dims(s)
    return NTPModelConfig(d_model=d, n_kv_groups=kv, q_per_kv=h // kv,
                          head_dim=hd, d_ff=ff, unit_rows=rows, n_layers=n,
                          vocab=v)


# ---------------------------------------------------------------- reference

def init(s, key):
    """Random weights in the canonical layout, float32: the embedding with
    std 0.02, every matrix and the head with std fan_in**-0.5, norm scales
    1."""
    d, h, kv, hd, ff, n, v, rows = dims(s)
    g = h // kv
    ks = jax.random.split(key, 2 + 6 * n)

    def mat(k, shape, fan_in):
        return jax.random.normal(k, shape, jnp.float32) * fan_in ** -0.5

    layers = []
    for i in range(n):
        k = ks[2 + 6 * i: 8 + 6 * i]
        layers.append({
            "ln1": jnp.ones((d,), jnp.float32),
            "ln2": jnp.ones((d,), jnp.float32),
            "wq": mat(k[0], (kv, d, g * hd), d),
            "wk": mat(k[1], (kv, d, hd), d),
            "wv": mat(k[2], (kv, d, hd), d),
            "wo": mat(k[3], (kv, g * hd, d), h * hd),
            "A": mat(k[4], (ff // rows, d, rows), d),
            "B": mat(k[5], (ff // rows, rows, d), ff),
        })
    return {"embed": jax.random.normal(ks[0], (v, d), jnp.float32) * 0.02,
            "head": mat(ks[1], (d, v), d),
            "final_norm": jnp.ones((d,), jnp.float32),
            "layers": layers}


def _block(s, x, p):
    d, h, kv, hd, *_ = dims(s)
    eps = _runs(s)["rms_norm_eps"]
    b, t, _ = x.shape
    y = R.rms_norm(x, p["ln1"], eps)
    q = jnp.einsum("btd,kdr->btkr", y, p["wq"]).reshape(b, t, kv, h // kv, hd)
    k = jnp.einsum("btd,kdh->btkh", y, p["wk"])
    v = jnp.einsum("btd,kdh->btkh", y, p["wv"])
    a = R.causal_attention(q, k, v, hd ** -0.5)
    x = x + jnp.einsum("btkr,krd->btd", a.reshape(b, t, kv, -1), p["wo"])
    y = R.rms_norm(x, p["ln2"], eps)
    u = jax.nn.gelu(jnp.einsum("btd,udf->btuf", y, p["A"]), approximate=True)
    return x + jnp.einsum("btuf,ufd->btd", u, p["B"])


def token_losses(s, w, tokens, dtype=jnp.float32):
    """Next-token cross-entropy (B, S) in float32 of rows of S+1 tokens;
    activations in ``dtype``, and weights too (cast a layer at a time)."""
    cast = lambda t: jax.tree.map(lambda a: a.astype(dtype), t)
    inp, tgt = tokens[:, :-1], tokens[:, 1:]
    x = w["embed"].astype(dtype)[inp]
    for p in w["layers"]:
        x = jax.checkpoint(lambda x, p: _block(s, x, p))(x, cast(p))
    x = R.rms_norm(x, w["final_norm"].astype(dtype), _runs(s)["rms_norm_eps"])
    logits = (x @ w["head"].astype(dtype)).astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, tgt[..., None], axis=-1)[..., 0]
    return lse - ll


def loss_total(s, w, tokens, row_weight, dtype=jnp.float32):
    """Sum of the token losses of the rows that count (``row_weight`` 1):
    the numerator of the masked mean; its denominator is the rows that
    count times S."""
    return jnp.sum(token_losses(s, w, tokens, dtype) * row_weight[:, None])


def optimizer(s):
    o = s["optimizer"]
    sch = o["schedule"]
    return R.adamw(o, lambda t: R.warmup_cosine(t, sch["warmup"], sch["total"],
                                                sch["floor"]))


def reference_norms(w):
    """{leaf name: norm} of a canonical tree (``layers.<i>.<leaf>``)."""
    return R.named_norms(w)


# ------------------------------------------------------------ operations

def layer_matmul_params(s):
    d, h, kv, hd, ff, *_ = dims(s)
    return 2 * d * h * hd + 2 * d * kv * hd + 2 * d * ff


def train_flops_per_token(s, seq: int) -> float:
    """Forward and backward of one token at sequence length ``seq``: 6 per
    matmul parameter (every layer's projections and MLP, and the untied
    head), and causal attention counted once (2*H*hd*S per layer forward
    for QK^T and PV together, times 3). No recomputation counted, nor the
    head's second copy on the other chip of a scale-up domain."""
    d, h, kv, hd, ff, n, v, _ = dims(s)
    return (6.0 * (n * layer_matmul_params(s) + d * v)
            + 6.0 * n * seq * h * hd)
