"""Percent of the summed time of the window's EC shard pushes (the
primary's ``call:shard_write`` spans; ``benchmark/lib/pushes.py``) spent
from ``sent`` to the holder's receipt of the frame: the socket write
and the holder's reader thread."""

from benchmark.lib.pushes import share


def read(run):
    return share(run, "transit")
