"""compile_s: seconds of the run's set-up spent getting compiled programs,
backend compiles plus reads from JAX's persistent cache (JAX's monitoring
events).  Moves ``setup_s``."""


def read(ctx):
    return ctx.counters.get("compile_s")
