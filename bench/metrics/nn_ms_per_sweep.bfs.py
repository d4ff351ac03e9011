"""Device self time under the traversal step's ``msbfs.nn.*`` scopes (the
nn edge-chunk scan and the slot exchange: point-to-point traffic of the
low-degree vertices) per sweep of the window (ms,
``bench/attribution.py``)."""
import attribution


def read(ctx):
    return attribution.ms_per_sweep(ctx, attribution.NN)
