"""How many functions jax traced to a jaxpr between the trainer's entry and
the first step's drain (`/jax/core/compile/jaxpr_trace_duration` events: the
step, the initialisers, every eager op and every kernel caller traced once a
call site). `launch_jit.jit_traces` of the trainer's summary."""


def read(run):
    jit = run["summary"].get("launch_jit")
    return None if not jit else jit.get("jit_traces")
