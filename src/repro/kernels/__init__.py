# OPTIONAL layer. Add <name>.py (or .cu) + ops.py + ref.py ONLY
# for compute hot-spots the paper itself optimizes with a custom
# kernel. Leave this package empty if the paper has none.
from __future__ import annotations

import jax


def interpret_mode(interpret: bool | None) -> bool:
    """Resolve a kernel's ``interpret`` argument. ``None`` compiles the
    kernel with Mosaic on a TPU backend and runs the Pallas interpreter
    everywhere else (the CPU test path), so a kernel never interprets on
    the chip unless the caller asks for it."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return interpret
