"""Named scopes in the training steps: the compiled step's op metadata names
each layer, so that a profiler trace can give device time per layer.

Each ``op_name`` is a path of components; a ``jax.named_scope`` is one of
them, wrapped by the transformation it ran under (``jvp(mlp)``,
``transpose(jvp(attention))``)."""
import re

import jax
import jax.numpy as jnp
import numpy as np

from conftest import reduced_cfg
from repro.configs.shapes import ShapeSpec
from repro.core import ntp_train as nt
from repro.core.nonuniform import FailurePlan
from repro.optim import AdamWConfig, adamw
from repro.train.steps import make_setup

TRAIN_SCOPES = {"attention", "mlp", "loss_head", "optimizer"}
WRAPPED = re.compile(r"^(?:[\w-]+\()*([^()]*)\)*$")


def scope_components(compiled_text: str):
    """Every path component of every op_name in a compiled module's text,
    unwrapped from its transformation."""
    out = set()
    for name in re.findall(r'op_name="([^"]*)"', compiled_text):
        for part in name.split("/"):
            m = WRAPPED.match(part)
            if m:
                out.add(m.group(1))
    return out


def test_arch_train_step_names_its_layers():
    cfg = reduced_cfg("granite-3-2b")
    su = make_setup(cfg, ShapeSpec("t", 16, 2, "train"), None,
                    param_dtype=jnp.float32)
    text = su.jit_step().lower(*su.abstract_args()).compile().as_text()
    names = scope_components(text)
    assert TRAIN_SCOPES | {"embed", "norm"} <= names, names
    # backward ops keep the layer's name under transpose(jvp(...))
    assert re.search(r'op_name="[^"]*transpose\(jvp\([^"]*attention', text)


def test_ntp_train_step_names_its_layers():
    cfg = nt.NTPModelConfig(d_model=32, n_kv_groups=2, q_per_kv=1,
                            head_dim=16, d_ff=64, unit_rows=32, n_layers=1,
                            vocab=64)
    plan = FailurePlan(n1=1, replica_tp=(1,))
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    opt = adamw(AdamWConfig(lr=1e-3))
    step = nt.make_ntp_train_step(cfg, plan, mesh, local_batch=2,
                                  optimizer=opt)
    params = nt.pack_params(cfg, nt.init_canonical(cfg, jax.random.PRNGKey(0)),
                            plan)
    batch = jnp.asarray(np.zeros((2, 9)), jnp.int32)
    text = step.lower(params, opt.init(params), batch).compile().as_text()
    assert TRAIN_SCOPES | {"embed", "norm"} <= scope_components(text)


def test_arch_train_step_calls_the_attention_kernel_in_its_scope(monkeypatch):
    """On the TPU (the backend steered here; lowered for the TPU on the
    CPU) each computation that holds a flash-kernel call is called from the
    `attention` scope: the forward, its recomputation and the backward.
    The compiled step's whole op_names, and which of them read as
    backward, are checked at granite's widths in test_tpu_compile.py."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = reduced_cfg("granite-3-2b")
    su = make_setup(cfg, ShapeSpec("t", 1024, 2, "train"), None,
                    param_dtype=jnp.float32)
    hlo = su.jit_step().trace(*su.abstract_args()).lower(
        lowering_platforms=("tpu",)).as_text(dialect="hlo", debug_info=True)
    heads = list(re.finditer(r"^(?:ENTRY )?%?([\w.\-]+) [^\n]*\{\s*$", hlo,
                             re.M))
    holders = {m.group(1) for m, nxt in zip(heads, heads[1:] + [None])
               if "tpu_custom_call" in hlo[m.end():nxt and nxt.start()]}
    sites = [name for callee, name in re.findall(
        r'to_apply=%?([\w.\-]+)[^\n]*?op_name="([^"]*)"', hlo)
        if callee in holders]
    assert len(holders) == 3 and len(sites) == 3, (holders, sites)
    assert all("attention" in scope_components(f'op_name="{n}"')
               for n in sites), sites
