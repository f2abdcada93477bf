"""Percent of the summed time of the window's EC shard pushes (the
primary's ``call:shard_write`` spans; ``benchmark/lib/pushes.py``) spent
in the holder's handler span: the op scheduler, the PG lock and the
store commit."""

from benchmark.lib.pushes import share


def read(run):
    return share(run, "handler")
