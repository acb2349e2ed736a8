"""`memory_analysis().temp_size_in_bytes` of the compiled step, a chip:
activations, gradients and workspace."""


def read(run):
    return run["memory"].get("temp_gib")
