"""Programs this process compiled and wrote to the persistent compile
cache (registry counter ``compile.cache_misses``, fed by the
``jax.monitoring`` listener of ``configure_compile_cache``): 0 on a warm
run, every large program of the cell on the first run of a checkout.
``None`` where the program never set the counter up."""


def read(obs):
    from tpu_sandbox.obs import get_registry

    return get_registry().snapshot()["counters"].get("compile.cache_misses")
