"""Programs built inside the window: ``jax.monitoring``'s
``backend_compile_duration`` events, which JAX records for a compile and
for a load from the persistent cache alike (as chip_smoke.Meter counts
them). Either means a shape the warm-up missed."""


def read(run):
    return run.window_compiles
