"""``compile_s``: seconds of JAX backend compilation during set-up,
persistent-cache loads included (``jax.monitoring`` compile events)."""


def read(rec):
    return rec["setup_compile_s"]
