"""compiles_in_window: XLA compilations (or compile-cache loads) while the
window was open, from jax.monitoring; the target is 0."""


def read(cell):
    return float(len(cell.compiles))
