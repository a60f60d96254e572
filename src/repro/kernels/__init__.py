# FantastIC4 Pallas TPU kernels: packed-int4 ACM matmul with fused epilogue
# (fantastic4_matmul.py), the whole-stack serving megakernel
# (fantastic4_fused_mlp.py), fused ECL assignment+dequant (ecl_quant.py),
# and the shape-aware block autotuner (autotune.py).
# ops.py holds the jit'd public wrappers; ref.py the pure-jnp oracles,
# including the literal bit-plane ACM form of eq. (1).
from . import ops, ref  # noqa: F401
