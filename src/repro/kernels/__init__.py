"""Pallas TPU kernels for the PowerSGD hot loop.

  * lowrank.py  — P = M Q and Q = Mᵀ P̂ tall-skinny matmuls (VMEM tiled).
                  2-D inputs use a (n/bn, k/bk) grid; 3-D inputs — the
                  bucketed engine's (B, n, m) shape-bucket slabs — add a
                  leading batch grid dimension so one ``pallas_call``
                  covers the whole bucket.
  * ef_apply.py — fused decompress + momentum + parameter update
  * flash_attention.py — causal flash attention (forward, dk/dv, dq) for
                  the model's attention core on the TPU; selected by
                  platform and shape in ``models/attention.py``, not by
                  ``use_pallas``
  * ops.py      — jit'd public wrappers (`lowrank_project`,
                  `lowrank_backproject`, `ef_apply`); rank-polymorphic over
                  leading batch dims
  * ref.py      — pure-jnp oracles for the allclose tests; every oracle is
                  batched over leading dims exactly like the kernels

All kernels accumulate in fp32.  On the CPU they run in interpret mode
and are checked against ``ref.py``; ``tests/test_chip_compile.py`` compiles
each one with Mosaic for a described TPU v5e (no chip needed), where the
low-rank matmuls go to the MXU with the rank dim padded to the 128 lane
width.
"""
