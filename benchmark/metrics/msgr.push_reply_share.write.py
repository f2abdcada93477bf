"""Percent of the summed time of the window's EC shard pushes (the
primary's ``call:shard_write`` spans; ``benchmark/lib/pushes.py``) spent
from the handler's end to the push span's end: the reply frame and
waking the waiting primary."""

from benchmark.lib.pushes import share


def read(run):
    return share(run, "reply")
