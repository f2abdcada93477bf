"""Acknowledged writes per encode dispatch (the EC engine's
``encode_ops``) in the traced window: how many objects the OSDs' encode
batcher puts in one kernel launch."""


def read(run):
    enc = run.counters.get("ec.encode_ops", 0)
    n = len(run.window.done())
    return n / enc if enc and n else None
