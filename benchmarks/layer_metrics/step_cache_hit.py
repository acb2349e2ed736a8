"""1 if the step came from the persistent compilation cache, 0 if XLA ran."""


def read(run):
    hit = run["summary"].get("compile_cache_hit")
    return None if hit is None else float(bool(hit))
