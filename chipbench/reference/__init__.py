"""Plain float32 references of each model family the benchmark runs.

Written from the published architecture descriptions with ``jax.numpy``
alone; nothing here imports the program under test.  Each family module
gives the parameter layout (the shapes and the initialisation rule the
benchmark draws its weights with), the forward pass and loss, and the
operation and byte counts the per-layer metrics divide by.
"""
import importlib


def family(name: str):
    """The reference module of a model family, by the name a config gives."""
    return importlib.import_module(f"{__name__}.{name}")
