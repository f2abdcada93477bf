"""Objects the OSDs' recovery decoded in the traced window (the
``recovered_objects`` counter).  Set-up leaves every shard landed, so
each is background work for the stopped OSD's positions that competes
with the reads for the host."""


def read(run):
    return run.facts.get("recovered_in_window")
