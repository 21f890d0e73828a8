"""Backend probe and Pallas interpret switch for the installed JAX.

``default_interpret`` is the one place the code asks which backend it
is on; every Pallas kernel threads its answer through
``pallas_interpret``.
"""
from __future__ import annotations

import jax
from jax.experimental.pallas import tpu as pltpu


def default_interpret() -> bool:
    """Default for the Pallas kernels' ``interpret=`` parameter:
    interpret on CPU backends (CI and dev boxes run the kernels through
    the Pallas TPU interpreter), compile everywhere else.

    This is the ONE sanctioned backend probe — taxlint rule PL001 flags
    inline ``jax.default_backend() == "cpu"`` comparisons outside this
    module, so the default can never again be copy-pasted into each
    kernel file and drift apart.
    """
    return jax.default_backend() == "cpu"


def pallas_interpret(interpret: bool):
    """Value for ``pl.pallas_call(interpret=...)``: the TPU-interpreter
    params (eager DMA, so ring kernels make progress) when interpreting,
    else ``False``."""
    if not interpret:
        return False
    return pltpu.InterpretParams(dma_execution_mode="eager")
