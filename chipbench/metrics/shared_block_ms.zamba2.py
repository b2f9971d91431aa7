"""Device milliseconds per serve step in the program's ``shared_block``
scope (at each hybrid site: the concat, both norms, attention, the MLP
with the site's adapter and the site linear), and in the operations XLA
added that feed it (``scopes.inherited``): their leaf operations on the
first device, over the serve steps of the traced window.  Nothing where
the runner gave no map of those operations."""

from chipbench import scopes


def read(r):
    inherit = r.get("inherited_scopes")
    if inherit is None:
        return None
    return scopes.ms_per_step(r.get("events"), "shared_block", inherit)
