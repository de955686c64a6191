"""Seconds per fit in JAX's compile spans (jaxpr tracing, lowering to MLIR,
backend compile with its persistent-cache read): the union of their
intervals within each fit, so nested spans count once, averaged over the
window's fits."""


def read(run):
    return sum(f.compile_s for f in run.fits) / len(run.fits)
