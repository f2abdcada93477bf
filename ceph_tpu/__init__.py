"""ceph_tpu — a TPU-native storage placement + erasure coding framework.

A ground-up reimplementation of the capabilities of Ceph's pure math engines
(reference: wjwithagen/ceph) designed for JAX/XLA/Pallas on TPU:

- ``ceph_tpu.crush``: the CRUSH placement solver.  The straw2 draw and the
  rule-step walk of the reference (src/crush/mapper.c) become a vmapped JAX
  program (``crush_do_rule_batched``) that maps millions of placement-group
  inputs to OSD sets in a single device launch.
- ``ceph_tpu.ec``: erasure coding.  Reed-Solomon/GF(2^8) encode and decode
  (the role of the reference's jerasure / ISA-L plugins behind
  src/erasure-code/ErasureCodeInterface.h) as bit-sliced XOR matmuls on the
  MXU, plus the LRC / SHEC / CLAY composed codes.
- ``ceph_tpu.osdmap``: the cluster-map placement pipeline
  (pps seed -> crush -> upmap -> up filter -> primary affinity), fused into
  one batched program, and the upmap balancer built around it.
- ``ceph_tpu.parallel``: sharding the PG axis / chunk striping across a
  ``jax.sharding.Mesh`` (ICI/DCN collectives take the place of the
  reference's AsyncMessenger data plane).
- ``ceph_tpu.tools``: crushtool / osdmaptool / EC-benchmark equivalents.

Bit-exactness contract: every placement this package computes matches the
reference C core bit for bit; see tests/golden/ (vectors generated from the
reference implementation) and ceph_tpu/crush/mapper_ref.py (the executable
scalar specification).
"""

__version__ = "0.1.0"
