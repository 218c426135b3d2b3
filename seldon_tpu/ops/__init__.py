"""Hand-written TPU kernels (pallas), each beside the jnp reference that
tests compare it against.

The reference has no kernel layer at all (CPU serving only). Here the hot
ops get pallas implementations tuned to the TPU memory hierarchy
(HBM->VMEM->MXU, /opt/skills/guides/pallas_guide.md). A kernel compiles
for the chip or raises; off the chip only tests run one, interpreted
(tests/pallas_interpret.py).
"""

from seldon_tpu.ops.flash_attention import flash_attention

__all__ = ["flash_attention"]
