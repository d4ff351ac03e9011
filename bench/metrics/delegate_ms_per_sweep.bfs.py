"""Device self time under the traversal step's delegate scopes (push and
pull over the dd, nd and dn subgraphs and the delegates' global OR
reduction: the paper's high-degree path) per sweep of the window (ms,
``bench/attribution.py``)."""
import attribution


def read(ctx):
    return attribution.ms_per_sweep(ctx, attribution.DELEGATE)
