"""The benchmark's operation counts against the compiler's own count of the
same programs (``launch/hlo_analysis.analyze_hlo``) at a tiny size on the
CPU, and the table of peaks."""
from __future__ import annotations

import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import perfbench_tiny as T  # noqa: E402
from bench import harness  # noqa: E402

SIZES = {"hidden_size": 256, "intermediate_size": 1024,
         "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 64,
         "num_hidden_layers": 2, "vocab_size": 512}
B, S = 2, 128


def _hlo_flops(lowered) -> float:
    from repro.launch.hlo_analysis import analyze_hlo

    return analyze_hlo(lowered.compile().as_text())["flops"]


def _bounds(counted: float, attn_extra: float, hlo: float):
    """The compiler counts at least what the benchmark counts, and at most
    that plus what the count leaves out on purpose: the masked half of the
    program's full S x S attention, and elementwise work (norms, softmax,
    activations, the optimizer), taken as 15% of the total here."""
    assert counted <= hlo <= (counted + attn_extra) * 1.15, (
        counted, attn_extra, hlo)


def test_granite_train_flops_match_the_compiled_step():
    import jax
    import jax.numpy as jnp

    from repro.configs.shapes import ShapeSpec
    from repro.train.steps import make_setup

    cell = T.tiny_cell("granite-train", batch=B, seq=S)
    s = T.resized(cell.sizes, SIZES)
    C = cell.config
    su = make_setup(C.arch_config(s), ShapeSpec("t", S, B, "train"), None,
                    param_dtype=jnp.float32, remat=False)
    hlo = _hlo_flops(jax.jit(su.step_fn).lower(*su.abstract_args()))
    counted = C.train_flops_per_token(s, S) * B * S
    n, h, hd = (s["num_hidden_layers"], s["num_attention_heads"],
                s["head_dim"])
    _bounds(counted, 6.0 * n * S * h * hd * B * S, hlo)


def test_peaks_table_names_v5e_and_refuses_an_unknown_device():
    peaks = harness.device_peaks("TPU v5 lite")
    assert peaks["bf16_flops_per_s"] == 197e12
    assert peaks["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        harness.device_peaks("cpu")


def test_granite_serve_flops_match_the_compiled_programs():
    """A prefill of P real tokens and one slot decode at a known position,
    against the compiled ``Model.prefill`` and ``Model.decode_slots``. The
    compiler also counts what the benchmark leaves out on purpose: the
    head over every prefilled position but the last, the masked half of
    the prefill's attention, and the decode's attention over the cache
    positions past the live ones."""
    import jax
    import jax.numpy as jnp

    from repro.models.transformer import build_model

    cell = T.tiny_cell("granite-serve-chat")
    s = T.resized(cell.sizes, SIZES)
    C = cell.config
    cfg = C.arch_config(s)
    assert cfg.padded_vocab() == s["vocab_size"]
    model = build_model(cfg, remat=False)
    params = jax.eval_shape(lambda k: C.to_program(s, C.init(s, k),
                                                   cfg.padded_vocab()),
                            jax.random.PRNGKey(0))
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)
    P, T_, slots, ctx = 128, 256, 4, 100
    n, h, hd, d, v = (s["num_hidden_layers"], s["num_attention_heads"],
                      s["head_dim"], s["hidden_size"], s["vocab_size"])
    cache1 = jax.eval_shape(lambda: model.init_cache(1, T_, jnp.float32))
    hlo = _hlo_flops(jax.jit(model.prefill).lower(params, i32(1, P), cache1))
    _bounds(C.prefill_flops(s, P),
            2.0 * d * v * (P - 1) + 4.0 * n * h * hd * P * (P - 1) / 2, hlo)
    cache = jax.eval_shape(lambda: model.init_slot_cache(slots, T_,
                                                         jnp.float32))
    hlo = _hlo_flops(jax.jit(model.decode_slots).lower(
        params, cache, i32(slots), i32(slots)))
    _bounds(slots * C.serve_token_flops(s, ctx, head=True),
            slots * 4.0 * n * h * hd * (T_ - ctx), hlo)
