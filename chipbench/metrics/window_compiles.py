"""Programs lowered inside the measured window: each is a jit cache miss
that compiles, or loads from the persistent cache, on the window's clock.
Every shape the window uses is warmed up in set-up, so this reads 0.
Counted by the harness from JAX's compile events (``CompileCounter``)."""


def read(run):
    return run["window_compiles"]["jaxpr_to_mlir_module_duration"]
