"""collective_pct: share of the device's busy time in collective ops
(layer: Parallel, ``parallel/halo.py``, ``haloplan.py``, ``mesh.py``).

Ops whose name matches the configuration's ``trace.collective_op``,
over the union of all op intervals, averaged over the chips. Nothing to
read where no collective ran."""


def read(ctx):
    if ctx.trace is None:
        return None
    share = ctx.trace.op_share(ctx.config["trace"]["collective_op"])
    return None if share is None else 100.0 * share
