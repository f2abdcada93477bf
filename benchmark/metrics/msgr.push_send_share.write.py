"""Percent of the summed time of the window's EC shard pushes (the
primary's ``call:shard_write`` spans; ``benchmark/lib/pushes.py``) spent
from the push span's start to its ``sent`` event: the session lock,
the frame encode and any wait for the socket's writer."""

from benchmark.lib.pushes import share


def read(run):
    return share(run, "send")
