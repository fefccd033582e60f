# Pallas TPU kernels for the paper's compute hot-spot (Section IV-C):
# fused scatter-gather aggregation + systolic update, and the hot cache's
# scatter update.  ops.py = jit'd wrappers (and the layer-0 feature
# combine, XLA's gather); ref.py = pure-jnp oracles;
# gather_scatter_mm.py = pallas_call.
from . import ops, ref

__all__ = ["ops", "ref"]
