"""Where a Pallas kernel runs: compiled on a TPU, interpreted on the CPU.

The platform is a fact of the dispatch, not a caller's choice: every op
asks :func:`interpret_mode` for the device its dispatch goes to.  The CPU
has no Mosaic backend, so kernels run in the Pallas interpreter there
(the bit-accurate validation path the tests use); on a TPU they compile.
Any other platform has no kernel path, and a dispatch there raises
instead of silently falling back to the interpreter.
"""
from __future__ import annotations

import jax


def interpret_mode(device=None) -> bool:
    """True when a Pallas dispatch to ``device`` must run in the
    interpreter, False when it compiles.  ``device`` None means where an
    uncommitted dispatch goes: the ``jax.default_device`` in force (a
    device or a platform name), else the default backend."""
    if device is None:
        device = jax.config.jax_default_device
    platform = (jax.default_backend() if device is None
                else getattr(device, "platform", device))
    if platform == "cpu":
        return True
    if platform == "tpu":
        return False
    raise RuntimeError(f"no Pallas kernel path on platform {platform!r}")
