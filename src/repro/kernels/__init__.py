"""Pallas TPU kernels (raw entry points in their own modules, jitted
wrappers in ``ops``, pure-jnp oracles in ``ref``)."""
from __future__ import annotations

import jax


def resolve_interpret(interpret=None) -> bool:
    """Interpret mode for a Pallas call: on exactly when the backend is
    not a TPU. ``None`` resolves from the backend; an explicit
    ``interpret=True`` on a TPU raises, because the interpreter there
    would stand in for the compiled kernel without saying so."""
    on_tpu = jax.default_backend() == "tpu"
    if interpret is None:
        return not on_tpu
    if interpret and on_tpu:
        raise ValueError("Pallas interpret mode requested on a TPU backend; "
                         "the kernels run compiled there")
    return bool(interpret)
