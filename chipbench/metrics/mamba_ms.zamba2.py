"""Device milliseconds per serve step in the program's ``mamba_layer``
scope (each Mamba layer's input norm, mixer and residual, with the
recurrence's ``ssm_update`` inside), and in the operations XLA added that
feed it, such as the asynchronous copies and slices that stream its
weights and state (``scopes.inherited``): their leaf operations on the
first device, over the serve steps of the traced window.  Nothing where
the runner gave no map of those operations."""

from chipbench import scopes


def read(r):
    inherit = r.get("inherited_scopes")
    if inherit is None:
        return None
    return scopes.ms_per_step(r.get("events"), "mamba_layer", inherit)
