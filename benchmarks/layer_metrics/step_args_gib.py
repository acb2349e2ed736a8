"""`memory_analysis().argument_size_in_bytes` of the compiled step, a chip:
parameters, optimizer state and the batch."""


def read(run):
    return run["memory"].get("args_gib")
