"""Pallas execution-mode resolution, shared by every kernel module and the
jit'd wrappers in `kernels.ops` (kept out of ``ops`` so the kernel modules
can import it without a cycle).

Kernels are COMPILED wherever a non-CPU backend exists: the kernels target
TPU, so on real accelerators the compiled path is the hot path. On CPU
(tests, CI) the TPU lowering does not exist, so interpret mode — executing
the kernel body op by op — validates the kernel math.

Resolution, per call: an explicit ``interpret=True`` always interprets;
otherwise the backend decides (interpret on CPU, compiled elsewhere). An
explicit ``interpret=False`` on CPU asks for the TPU lowering, which only a
compile for a described TPU (tests/test_tpu_compile.py) can use.
"""
from __future__ import annotations

from typing import Optional

import jax

from repro import telemetry


def pallas_interpret(override: Optional[bool] = None,
                     kernel: Optional[str] = None) -> bool:
    """True → run the kernel in interpret mode. See the module docstring for
    the resolution order (explicit ``True`` > backend default).

    ``kernel`` names the dispatch site for telemetry: each resolution with a
    name counts into the ``kernels.dispatch`` series (labels: kernel, mode),
    so a run can prove which kernels actually took the compiled path. Only
    the named public wrappers in `kernels.ops` pass it — internal re-entries
    resolve anonymously and are not double-counted."""
    if override is not None:
        interpret = bool(override)
    else:
        interpret = jax.default_backend() == "cpu"
    if kernel is not None:
        tel = telemetry.get()
        if tel.enabled:
            tel.counter("kernels.dispatch", kernel=kernel,
                        mode="interpret" if interpret else "compiled")
    return interpret
