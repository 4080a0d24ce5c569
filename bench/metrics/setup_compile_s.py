"""setup_compile_s (s): seconds the set-up spent obtaining compiled
programs, as ``jax.monitoring`` reports them: backend compiles plus
loads from the persistent compilation cache. In a warm run nearly all
of it is cache loads; a program that compiles again shows here."""


def read(ctx):
    c = ctx.get("compile")
    if c is None:
        return None
    return c["backend_compile_s"] + c["cache_retrieval_s"]
