"""``window_compiles``: ``jax.monitoring`` trace and backend-compile events
inside the window. A warmed cell reads 0; anything else is an engine cache
that missed (a kernel key, a capacity hint, a plan-feedback flip)."""


def read(obs: dict):
    return float(len(obs["window_compile_events"]))
