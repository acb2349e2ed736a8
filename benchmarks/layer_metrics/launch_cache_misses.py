"""How many executables a launch compiled anew and wrote to the persistent
compilation cache (`/jax/compilation_cache/cache_misses`): 0 on a warm
start, or something compiles again every launch.
`launch_jit.cache_misses` of the trainer's summary."""


def read(run):
    jit = run["summary"].get("launch_jit")
    return None if not jit else jit.get("cache_misses")
