"""Pallas TPU kernels, each beside its pure-jnp oracle (``ref.py``).

Every op wrapper takes ``impl``, the one backend name that
``QuantContext.matmul_impl`` and the serving engine carry:

  * ``"pallas"`` — the Mosaic kernel, compiled natively for the TPU;
  * ``"pallas_interpret"`` — the same kernel body under the Pallas
    interpreter, for CPU tests only;
  * ``"ref"`` — the jnp oracle.

``pallas_flags`` is the single place that turns the name into the
``(use_pallas, interpret)`` pair the kernels take, and it refuses the
interpreter on a TPU backend, so no served kernel runs interpreted there.
"""

from __future__ import annotations

import jax

IMPLS = ("ref", "pallas", "pallas_interpret")


def pallas_flags(impl: str) -> tuple[bool, bool]:
    """``(use_pallas, interpret)`` for a backend name in ``IMPLS``."""
    if impl not in IMPLS:
        raise ValueError(f"impl must be one of {IMPLS}, got {impl!r}")
    interpret = impl == "pallas_interpret"
    if interpret and jax.default_backend() == "tpu":
        raise ValueError("impl='pallas_interpret' is for CPU tests; on a "
                         "TPU the kernels run with impl='pallas'")
    return impl != "ref", interpret
