"""Compilations inside the window: new signatures the program's churn
detector counted (``dl4j_recompiles_total``) plus JAX's own backend
compiles. 0 is the only healthy reading."""


def read(ctx):
    c = ctx.result["counters"]
    return c["recompiles"] + c["jax_compiles"]
