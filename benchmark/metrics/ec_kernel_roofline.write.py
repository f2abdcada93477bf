"""Percent of its roofline reached by the fused GF kernel's encode
calls (k rows in, m rows out), over the unpadded chunks of the objects
written in the traced window."""

from benchmark.lib.readers import gf_roofline


def read(run):
    f = run.facts
    return gf_roofline(run, f["ec_k"], f["ec_m"], f["objects_written"])
